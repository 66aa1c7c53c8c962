"""From a profiler trace to device busy time, op times and idle gaps.

The traced run records one `jax.profiler` trace over the measured window.
`reduce_trace` reads its ``.xplane.pb`` with `jax.profiler.ProfileData` and
keeps, for the window (the host span ``bench.window``):

* per device used (one on which an op ran in the window): the union of
  the intervals in which an op ran (busy), the summed time of each op
  name, and each program's (module's) executions;
* on the host: the harness's ``bench.<phase>`` spans;
* the idle gaps of each device, each split over the host phases that
  overlapped it (what the host was doing while the device waited).

Devices are the planes ``/device:TPU:<n>``; ops are the events of their
``XLA Ops`` line and programs those of their ``XLA Modules`` line.  A
trace without such planes (the CPU) yields no devices, and the readers that
need them return nothing.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

from .spans import PREFIX, WINDOW

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
PROGRAM_ID = re.compile(r"\(\d+\)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def profile_options():
    import jax.profiler
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint union."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def overlap(a, b) -> float:
    """Total length of the intersection of two disjoint sorted lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(busy, lo: float, hi: float):
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def short_name(name: str) -> str:
    """An op's HLO instruction name (``%fusion.3`` of ``%fusion.3 = ...``)
    or a program's name without its id (``jit_f`` of ``jit_f(123)``)."""
    return PROGRAM_ID.sub("", name.split(" = ", 1)[0])


def load_events(path: str) -> dict:
    """Raw events from one xplane: {"devices": {id: {"ops": [...],
    "modules": [...]}}, "host": [(name, start_ns, end_ns)]}; an op or
    module is (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: dict[int, dict] = {}
    host = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    dev[key].append((short_name(ev.name), ev.start_ns,
                                     ev.end_ns, dict(ev.stats)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        host.append((ev.name, ev.start_ns, ev.end_ns))
    return {"devices": devices, "host": host}


def reduce_events(raw: dict, n_devices: int | None = None) -> dict:
    """The window's busy time, op and module times and idle attribution."""
    windows = [(s, e) for name, s, e in raw["host"] if name == WINDOW]
    if not windows:
        return {"devices": 0}
    lo, hi = windows[0]
    phases = defaultdict(list)
    for name, s, e in raw["host"]:
        if name != WINDOW:
            phases[name[len(PREFIX):]].append((s, e))
    phases = {k: union(clip(v, lo, hi)) for k, v in phases.items()}
    # the chips used: of the cell's chips, those on which an op ran in the
    # window (a cell may hold four chips and run its program on one)
    dev_ids = sorted(raw["devices"])
    if n_devices is not None:
        dev_ids = dev_ids[:n_devices]
    dev_ids = [d for d in dev_ids
               if any(e > lo and s < hi for _, s, e, _ in
                      raw["devices"][d]["ops"])]
    busy_ns = 0.0
    op_ns: dict[str, float] = defaultdict(float)
    module_ns: dict[str, float] = defaultdict(float)
    module_calls: dict[str, int] = defaultdict(int)
    idle_by_phase: dict[str, float] = defaultdict(float)
    for d in dev_ids:
        dev = raw["devices"][d]
        ops = clip([(s, e) for _, s, e, _ in dev["ops"]], lo, hi)
        busy = union(ops)
        busy_ns += sum(e - s for s, e in busy)
        for name, s, e, _ in dev["ops"]:
            if e > lo and s < hi:
                op_ns[name] += min(e, hi) - max(s, lo)
        for name, s, e, _ in dev["modules"]:
            if e > lo and s < hi:
                module_ns[name] += min(e, hi) - max(s, lo)
                module_calls[name] += 1
        idle = gaps(busy, lo, hi)
        covered = 0.0
        for phase, spans in phases.items():
            t = overlap(idle, spans)
            idle_by_phase[phase] += t
            covered += t
        idle_by_phase["(no harness phase)"] += max(
            0.0, sum(e - s for s, e in idle) - covered)
    n = max(1, len(dev_ids))
    return {
        "devices": len(dev_ids),
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n,
        "op_s": {k: v * 1e-9 / n for k, v in op_ns.items()},
        "op_s_total": {k: v * 1e-9 for k, v in op_ns.items()},
        "module_s": {k: v * 1e-9 / n for k, v in module_ns.items()},
        "module_s_total": {k: v * 1e-9 for k, v in module_ns.items()},
        "module_calls": dict(module_calls),
        "idle_s_by_phase": {k: v * 1e-9 / n
                            for k, v in idle_by_phase.items()},
        "host_phase_s": {k: sum(e - s for s, e in v) * 1e-9
                         for k, v in phases.items()},
    }


def reduce_trace(trace_dir: str, n_devices: int | None = None) -> dict:
    path = find_xplane(trace_dir)
    if path is None:
        return {"devices": 0}
    return reduce_events(load_events(path), n_devices)


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(red: dict) -> dict:
    return {"device_ops": top(red.get("op_s", {})),
            "idle_gaps": top(red.get("idle_s_by_phase", {}))}


def summary(raw: dict, n: int = 25) -> dict:
    """What a trace holds, for reading by hand: per device the most costly
    op and module names with one sample of their stats."""
    out = {"host_spans": len(raw["host"]), "devices": {}}
    for d, dev in raw["devices"].items():
        entry = {}
        for key in ("ops", "modules"):
            tot: dict[str, float] = defaultdict(float)
            sample = {}
            for name, s, e, stats in dev[key]:
                tot[name] += e - s
                sample.setdefault(name, {k: str(v)[:120]
                                         for k, v in stats.items()})
            entry[key] = [{"name": k, "ns": v, "stats": sample[k]}
                          for k, v in sorted(tot.items(),
                                             key=lambda kv: -kv[1])[:n]]
        out["devices"][str(d)] = entry
    return out
