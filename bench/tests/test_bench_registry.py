"""Every part of the benchmark is found by name; adding one edits nothing."""

import json
import re

import pytest

from lib import registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return registry.load_benchmark()


def test_every_cell_loads_by_name(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        assert registry.find_cell(bench, cell["name"]) is cell
        cfg = registry.load_config(cell["config"])
        assert cfg["name"] == cell["config"]
        assert configs[cell["config"]]["file"] == (
            f"bench/configs/{cell['config']}.json")
        mix = registry.load_traffic(cell["traffic"])
        assert mix["kind"] in ("closed", "open")
        e2e = registry.metrics_for(bench, cell["name"], "end_to_end")
        assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
        assert registry.metrics_for(bench, cell["name"], "per_layer")


def test_every_metric_reader_loads_and_reads_nothing_from_nothing(bench):
    empty = {"counters": {}, "trace": {"devices": 0}, "peaks": {}}
    for m in bench["per_layer"]:
        mod = registry.load_metric(m["name"])
        assert mod.read(empty) is None, m["name"]


def test_names_and_keys_follow_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
    texts = ([c["why"] for c in bench["configs"] + bench["workloads"]]
             + [c["source"] for c in bench["configs"]]
             + [m["layer"] for m in bench["per_layer"]] + bench["command"])
    assert all(0 < len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts)
    assert all(UNIT.match(m["unit"])
               for m in bench["end_to_end"] + bench["per_layer"])
    layers = {m["layer"] for m in bench["per_layer"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["layer"] in layers
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 2)


HMM_FAMILY = """
import jax.numpy as jnp
PARAMS = ("num_states", "num_obs")


def make(key, *, num_states, num_obs):
    log_A = jnp.full((num_states, num_states), -jnp.log(num_states * 1.0))
    log_B = jnp.full((num_states, num_obs), -jnp.log(num_obs * 1.0))
    return log_A[0], log_A, log_B
"""

ARRIVALS = """
import numpy as np
PARAMS = ("spread",)


def gaps(n, rate, rng, *, spread):
    return rng.permutation(np.linspace(1 - spread, 1 + spread, n) / rate)
"""

SPEC = """
PARAMS = ("bt",)


def build(params, *, K, T, batch, log_pi, log_A):
    import repro.core as core
    return core.FusedSpec(), f"fixed, bt={params['bt']}"
"""


def test_a_new_cell_needs_only_new_files(tmp_path, data_dir):
    """A configuration, a mix and a metric, and a new HMM family, arrival
    process and spec builder, added as files load by name, from a tree
    where nothing that was there changed."""
    from lib.hmm import make_inputs
    from lib.offline import resolve_spec
    from lib.traffic import open_schedule
    for sub in ("configs", "traffic", "metrics", "hmms", "arrivals",
                "specs"):
        (tmp_path / sub).mkdir()
    (tmp_path / "hmms" / "flat.py").write_text(HMM_FAMILY)
    (tmp_path / "arrivals" / "even.py").write_text(ARRIVALS)
    (tmp_path / "specs" / "fixed.py").write_text(SPEC)
    cfg = {"name": "new_cfg", "hmm": "flat", "num_states": 8, "num_obs": 3,
           "seq_len": 16, "dtype": "float32", "limits": {}}
    (tmp_path / "configs" / "new_cfg.json").write_text(json.dumps(cfg))
    mix = json.loads((data_dir / "traffic" / "tiny_stream.json").read_text())
    mix["arrivals"] = {"process": "even", "spread": 0.5}
    (tmp_path / "traffic" / "new_mix.json").write_text(json.dumps(mix))
    (tmp_path / "metrics" / "new.metric.x.py").write_text(
        "def read(run):\n    return run['counters'].get('frames')\n")

    cfg = registry.load_config("new_cfg", tmp_path)
    log_pi, log_A, pool = make_inputs(cfg, 2147483649, 3, 16, base=tmp_path)
    assert pool.shape == (3, 16, 8) and log_A.shape == (8, 8)
    mix = registry.load_traffic("new_mix", tmp_path)
    sessions = open_schedule(mix, 5, 10.0, base=tmp_path)
    gaps = [b.arrival - a.arrival for a, b in zip(sessions, sessions[1:])]
    assert min(gaps) >= 0.5 / mix["rate_per_s"] - 1e-9
    spec, why = resolve_spec({"builder": "fixed", "bt": 8}, K=8, T=16,
                             batch=2, log_pi=log_pi, log_A=log_A,
                             base=tmp_path)
    assert why == "fixed, bt=8"
    mod = registry.load_metric("new.metric.x", tmp_path)
    assert mod.read({"counters": {"frames": 7}}) == 7
    bench = {"workloads": [{"name": "new_cfg.new_mix", "config": "new_cfg",
                            "traffic": "new_mix", "chips": 1}],
             "per_layer": [{"name": "new.metric.x",
                            "workloads": ["new_cfg.new_mix"]},
                           {"name": "other", "workloads": ["x"]}]}
    assert [m["name"] for m in registry.metrics_for(
        bench, "new_cfg.new_mix", "per_layer")] == ["new.metric.x"]


def test_keys_that_nothing_reads_are_refused(data_dir):
    """A value stated in a file and never used is refused, so a file
    cannot say one thing while the run measures another."""
    from lib.hmm import make_inputs
    from lib.offline import OfflineClient, resolve_spec
    from lib.spans import Spans
    from lib.traffic import open_schedule
    cfg = registry.load_config("tiny_ltr", data_dir)
    with pytest.raises(ValueError, match="edge_prob"):
        make_inputs(dict(cfg, edge_prob=0.3), 1, 2, 8)
    with pytest.raises(KeyError):
        make_inputs({k: v for k, v in cfg.items() if k != "max_skip"},
                    1, 2, 8)
    with pytest.raises(ValueError, match="seq_len"):
        make_inputs(cfg, 1, 2, cfg["seq_len"] + 1)
    mix = registry.load_traffic("tiny_offline", data_dir)
    with pytest.raises(ValueError, match="data_parallel"):
        OfflineClient(dict(mix, data_parallel=4), None, None, None, [], 1,
                      Spans(False))
    with pytest.raises(ValueError, match="budget_bytes"):
        resolve_spec({"builder": "named", "class": "FusedSpec",
                      "budget_bytes": 1}, K=8, T=8, batch=1, log_pi=None,
                     log_A=None)
    stream = registry.load_traffic("tiny_stream", data_dir)
    with pytest.raises(ValueError, match="burst_factor"):
        open_schedule(dict(stream, arrivals={"process": "poisson",
                                             "burst_factor": 8}), 1, 5.0)


def test_the_benchmarks_own_files_state_only_what_is_read(bench):
    """Every configuration and mix the cells name passes the key checks."""
    from lib.hmm import family
    from lib.offline import OfflineClient
    from lib.stream import StreamClient
    for cell in bench["workloads"]:
        family(registry.load_config(cell["config"]))
        mix = registry.load_traffic(cell["traffic"])
        keys = {"closed": OfflineClient, "open": StreamClient}[mix["kind"]]
        registry.check_keys(cell["traffic"], mix, keys.KEYS)
