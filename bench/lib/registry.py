"""Finds every part of the benchmark by the name `BENCHMARK.json` or a data
file gives it.

Data, under ``bench/``:

* a configuration is ``configs/<config>.json``;
* a traffic mix is ``traffic/<traffic>.json``.

Code, one module per kind under ``bench/``, named by a data file:

* ``hmms/<kind>.py``: an HMM family, named by a configuration's ``hmm``;
* ``arrivals/<kind>.py``: an arrival process, named by an open mix's
  ``arrivals.process``;
* ``specs/<kind>.py``: a decode-spec builder, named by a closed mix's
  ``spec.builder``;
* ``metrics/<metric>.py``: a per-layer metric (``read(run) -> float |
  None``), named by `BENCHMARK.json`.

Adding a cell, a configuration, a mix, a metric or a new kind of any of
these adds files and entries; no file that is there changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[c['name'] for c in bench['workloads']]}")


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(name: str, base: Path = BENCH) -> dict:
    return _json(base / "configs" / f"{name}.json")


def load_traffic(name: str, base: Path = BENCH) -> dict:
    return _json(base / "traffic" / f"{name}.json")


def load_module(kind: str, name: str, base: Path = BENCH):
    """The module ``<base>/<kind>/<name>.py``."""
    path = base / kind / f"{name}.py"
    if not path.is_file():
        have = sorted(p.stem for p in (base / kind).glob("*.py"))
        raise FileNotFoundError(f"no {kind} named {name!r} ({path}); have "
                                f"{have}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str, base: Path = BENCH):
    return load_module("metrics", name, base)


def check_keys(what: str, given, allowed) -> None:
    """Refuse keys that nothing reads: a value stated and never used would
    let a file say one thing while the run measures another."""
    extra = sorted(set(given) - set(allowed))
    if extra:
        raise ValueError(f"{what}: keys {extra} are read by nothing; "
                         f"allowed {sorted(allowed)}")


def metrics_for(bench: dict, cell: str, kind: str) -> list[dict]:
    """The `end_to_end` or `per_layer` entries this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]
