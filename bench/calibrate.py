#!/usr/bin/env python3
"""Readings that set a cell's limits: many seeds and the control, one process.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5 \\
        [--controls bf16]

Runs the cell once per seed through `run.run_cell`, with the window at the
cell's own load, and after each run decodes the same pool entries with the
reference in the control precisions in place of the program.  Prints one
``calibrate {...}`` line per seed with the numbers compared (sound runs: the
lower readings) and each control's (the upper readings), then a summary.
The benchmark's own runs never do this.  Limits are set from these
readings, as PERF.md records.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run
from lib import registry


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--controls", default="bf16")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    bench = registry.load_benchmark()
    cell = registry.find_cell(bench, args.workload)
    controls = tuple(c for c in args.controls.split(",") if c)
    worst: dict = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        res, info = run.run_cell(
            cell, registry.load_config(cell["config"]),
            registry.load_traffic(cell["traffic"]),
            registry.metrics_for(bench, cell["name"], "end_to_end"), [],
            seed, args.seconds, False, t_start=time.perf_counter(),
            controls=controls)
        line = {"seed": seed, "correct": res["correct"],
                "checks": res["checks"], "readings": info["readings"],
                "control": info.get("control", {}),
                "compiles_in_window": info["compiles_in_window"],
                "gc_in_window": info["gc_in_window"],
                "lateness_ms": info.get("lateness_ms"), "e2e": info["e2e"]}
        print("calibrate " + json.dumps(line), flush=True)
        for k, v in info["readings"].items():
            worst.setdefault("program", {})[k] = max(
                worst.get("program", {}).get(k, 0), v)
        for mode, r in info.get("control", {}).items():
            for k, v in r.items():
                if isinstance(v, (int, float)):
                    worst.setdefault(mode + "_min", {})[k] = min(
                        worst.get(mode + "_min", {}).get(k, float("inf")), v)
    print("summary " + json.dumps(worst), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
