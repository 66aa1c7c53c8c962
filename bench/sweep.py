#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest session rate it sustains.

    python bench/sweep.py --workload default_k512.stream --seed 7 \\
        --rates 10,20,40,60 --seconds 8

One process, one set of inputs; for each rate a fresh `InflightScheduler`
runs the cell's traffic at that rate (warm-up, then the window) and one
``sweep {...}`` line reports the tails, the generator's lateness and the
backlog (queued sessions, frames fed but not consumed) at the start and end
of the window.  A rate is sustained when the backlog does not grow over the
window and the generator keeps to its schedule.  The benchmark's runs use
the rate fixed in the traffic file; this is run once, by hand, on the chip.
"""

from __future__ import annotations

import argparse
import json
import sys

import run
from lib import registry
from lib.hmm import make_inputs
from lib.spans import Spans
from lib.stream import StreamClient
from lib.traffic import pool_lengths


class _Marks:
    def settle(self):
        pass

    def window_start(self):
        pass

    def window_end(self):
        pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--tail-limit", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    run.enable_compile_cache()
    bench = registry.load_benchmark()
    cell = registry.find_cell(bench, args.workload)
    config = registry.load_config(cell["config"])
    mix = dict(registry.load_traffic(cell["traffic"]),
               tail_limit_s=args.tail_limit)
    run.chip_devices(int(cell["chips"]))
    lengths = pool_lengths(mix)
    log_pi, log_A, pool = make_inputs(config, args.seed, len(lengths),
                                      int(lengths.max()))
    for rate in (float(r) for r in args.rates.split(",")):
        client = StreamClient(mix, log_pi, log_A, pool, lengths, args.seed,
                              Spans(False), rate=rate)
        client.setup()
        tails = client.run(args.seconds, _Marks())
        c = client.counters
        line = {"rate_per_s": rate, **tails, **client.info,
                "sessions": c["sessions"], "chunks": c["chunks"],
                "steps": c["steps"], "frames": c["frames"],
                "slot_use_pct": 100.0 * c["frames"] / max(1, c["steps"] * c[
                    "max_slots"] * c["block"]),
                "step_ms": 1e3 * c["busy_s"] / max(1, c["steps"]),
                "missing": c["chunks_missing"] + c["sessions_missing"]}
        print("sweep " + json.dumps(line, default=str), flush=True)
        client.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
