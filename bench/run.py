#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic mix
and per-layer metrics are files under ``bench/`` found by name
(`lib/registry.py`).  One run:

1. checks that jax sees a TPU with as many chips as the cell asks for, and
   exits non-zero with no result otherwise;
2. set-up (``setup_s``, from process start): makes the HMM and the request
   pool on the device from ``--seed``, builds the program's serving path and
   warms every shape the window uses (compiles are cached in
   ``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``);
3. the window: ``--seconds`` of the cell's traffic; with ``--trace 1`` under
   the profiler, with the harness's host phases as spans;
4. after the window: reads the peak device memory, frees the program's
   state, decodes every pool entry that was served with the plain reference
   (`lib/reference.py`) and judges every answer (`lib/check.py`).

Earlier lines of standard output are ``info {...}``: the spec, compile
seconds, compiles inside the window (there should be none), counts and the
generator's lateness.  The last lines of standard error are the numbers
compared, each with its limit.  The last line of standard output is the
result: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / ".out"
sys.path.insert(0, str(BENCH))

from lib import registry  # noqa: E402


class NoChip(RuntimeError):
    """jax sees no TPU, or fewer chips than the cell asks for."""


def enable_compile_cache() -> str:
    """The program's rule: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache``; every compiled program is written."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def chip_devices(chips: int, require_tpu: bool = True) -> list:
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, but jax found platform "
                     f"{devices[0].platform!r} ({devices[0].device_kind}); "
                     f"nothing was run")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, jax found "
                     f"{len(devices)}")
    return devices[:chips]


class Marks:
    """Window start and end: set-up time, compile counts, collector pauses,
    the trace.  A client calls `settle` once before its load starts."""

    def __init__(self, clock, trace_dir: str | None, t_start: float):
        from lib.gcwatch import GcWatch
        self.clock = clock
        self.trace_dir = trace_dir
        self.t_start = t_start
        self.gc = GcWatch()
        self._window = None

    def settle(self):
        from lib.gcwatch import settle
        settle()

    def unsettle(self):
        from lib.gcwatch import unsettle
        unsettle()

    def window_start(self):
        self.setup_s = time.perf_counter() - self.t_start
        self.compile_at_start = self.clock.snapshot()
        self.gc.start()
        if self.trace_dir:
            import jax
            from lib.spans import WINDOW
            from lib.trace import profile_options
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=profile_options())
            self._window = jax.profiler.TraceAnnotation(WINDOW)
            self._window.__enter__()

    def window_end(self):
        if self._window is not None:
            self._window.__exit__(None, None, None)
            self._window = None
        self.compile_at_end = self.clock.snapshot()
        self.gc.stop()

    def stop_trace(self):
        if self.trace_dir:
            import jax
            jax.profiler.stop_trace()


def make_client(mix: dict, *args, **kw):
    from lib.offline import OfflineClient
    from lib.stream import StreamClient
    kinds = {"closed": OfflineClient, "open": StreamClient}
    return kinds[mix["kind"]](mix, *args, **kw)


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def run_cell(cell: dict, config: dict, mix: dict, e2e: list[dict],
             per_layer: list[dict], seed: int, seconds: float, trace: bool,
             *, metric_base: Path = BENCH, require_tpu: bool = True,
             t_start: float = T_START, wrap_program=None,
             controls: tuple[str, ...] = ()) -> dict:
    """One run of a cell; returns (result line, info line) as dicts.

    `wrap_program(client)` runs after the client's set-up and before the
    window; the tests use it to break the timed path underneath.
    `controls` names reference precisions (`lib.reference.MODES`) to put in
    the program's place on the same pool entries; their readings go to the
    info line (`bench/calibrate.py` uses this, the benchmark's runs do not).
    """
    from lib.clock import CompileClock
    clock = CompileClock()
    cache_dir = enable_compile_cache()
    import jax
    from lib.check import judge, reference_for, verdict
    from lib.hmm import make_inputs
    from lib.spans import Spans
    from lib.traffic import pool_lengths

    devices = chip_devices(int(cell["chips"]), require_tpu)
    dev = devices[0]
    lengths = pool_lengths(mix)
    log_pi, log_A, pool = make_inputs(config, seed, len(lengths),
                                      int(lengths.max()))
    trace_dir = None
    if trace:
        trace_dir = str(OUT / "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    marks = Marks(clock, trace_dir, t_start)
    client = make_client(mix, log_pi, log_A, pool, lengths, seed,
                         Spans(trace))
    client.setup()
    if wrap_program is not None:
        wrap_program(client)
    values = client.run(seconds, marks)
    marks.stop_trace()
    marks.unsettle()
    mem = [d.memory_stats() or {} for d in devices]
    memory_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

    answers = client.answers
    prefix_bad = getattr(client, "prefix_bad", 0)
    counters = dict(client.counters, K=int(pool.shape[-1]))
    info = dict(client.info)
    client.close()
    del client
    gc.collect()

    t_check = time.perf_counter()
    ref = reference_for(log_pi, log_A, pool, lengths,
                        [a[0] for a in answers])
    readings = judge(answers, ref, log_pi, log_A, pool, lengths)
    limits = dict(config["limits"], malformed=0)
    attempted = len(answers)
    failed = readings["malformed"]
    if mix["kind"] == "open":
        readings["prefix_bad"] = prefix_bad
        readings["missing"] = (counters["chunks_missing"]
                               + counters["sessions_missing"])
        limits.update(prefix_bad=0, missing=0)
        attempted += counters["sessions_missing"]
        failed += readings["prefix_bad"] + counters["sessions_missing"]
    correct, checks = verdict(readings, limits)
    check_s = time.perf_counter() - t_check
    control = {}
    for mode in controls:
        ctl = reference_for(log_pi, log_A, pool, lengths, list(ref), mode)
        ctl_answers = [(i, p, s) for i, (p, s) in ctl.items()]
        control[mode] = judge(ctl_answers, ref, log_pi, log_A, pool, lengths)
        control[mode]["correct"] = verdict(control[mode], limits)[0]

    metrics = {}
    unread: list[str] = []
    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    if trace:
        from lib.peaks import peaks_for
        from lib.trace import breakdown as make_breakdown
        from lib.trace import load_events, reduce_events, summary
        from lib.trace import find_xplane
        path = find_xplane(trace_dir)
        raw = load_events(path) if path else {"devices": {}, "host": []}
        red = reduce_events(raw, len(devices))
        OUT.mkdir(exist_ok=True)
        with open(OUT / "trace_summary.json", "w") as f:
            json.dump({"reduced": red, "raw": summary(raw)}, f, indent=1)
        run = {"counters": counters, "trace": red,
               "peaks": peaks_for(dev.device_kind) if require_tpu else {}}
        for m in per_layer:
            v = registry.load_metric(m["name"], metric_base).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            elif require_tpu:
                unread.append(m["name"])
                print(f"bench: per-layer metric {m['name']!r} is listed for "
                      f"this cell and read nothing in its trace",
                      file=sys.stderr)
        device.update(busy_s=red.get("busy_s", 0.0),
                      window_s=red.get("window_s", 0.0))
        breakdown = make_breakdown(red)
    else:
        values["setup_s"] = marks.setup_s
        for m in e2e:       # `<quantity>.<group>` reports the quantity
            metrics[m["name"]] = {"value": values[m["name"].split(".")[0]],
                                  "unit": m["unit"]}
    for m in metrics.values():
        if not _finite(m["value"]):      # no NaN in the JSON line
            m["value"] = None
            correct = False

    window_compiles = (marks.compile_at_end["compiles"]
                       - marks.compile_at_start["compiles"])
    info.update(
        cell=cell["name"], seed=seed, seconds=seconds, trace=bool(trace),
        setup_s=marks.setup_s, compile=marks.compile_at_start,
        compiles_in_window=window_compiles, compile_cache=cache_dir,
        gc_in_window=marks.gc.summary(), unread_metrics=unread,
        counters=counters, readings=readings, check_s=check_s,
        e2e=values, **({"control": control} if controls else {}))
    print("info " + json.dumps(info, default=str), flush=True)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result, info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program's sources are not in this checkout "
              f"(no {ROOT / 'src' / 'repro'})", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    bench = registry.load_benchmark()
    cell = registry.find_cell(bench, args.workload)
    try:
        result, _ = run_cell(
            cell, registry.load_config(cell["config"]),
            registry.load_traffic(cell["traffic"]),
            registry.metrics_for(bench, cell["name"], "end_to_end"),
            registry.metrics_for(bench, cell["name"], "per_layer"),
            args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
