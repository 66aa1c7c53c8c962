"""The program's own spans, from the traced run's profile.

The program names its host work with ``repro.*`` spans
(`repro.runtime.spans`: ``repro.batch.pad``, ``repro.inflight.commit``,
...).  `lib/trace.py` reduces the harness's ``bench.*`` phases and leaves
these out; this module reads the same ``.xplane.pb`` and adds, for the
window (``bench.window``):

* ``span_s``, ``span_n``: seconds and count of each program span;
* ``idle_s_by_span``: every idle instant of a device under the innermost
  program span that covers it, keyed ``<phase>/<span>``; the rest stays
  under ``<phase>``, so a phase's entries sum to its `reduce_events`
  ``idle_s_by_phase`` value;
* ``steps``, ``finish_steps``: the inflight steps in the window (one
  ``repro.inflight.dispatch`` each) and those inside the harness's
  ``finish`` phase;
* ``devices``: the devices reduced, chosen as `reduce_events` chooses.

A metric reads it through `for_run`, which reduces the run's trace once.
A trace without the program's spans (a program from before them) gives
empty tables, and the metrics read nothing.

The program's device scopes (`jax.named_scope`: ``viterbi.backtrack``,
``flash.initial_pass``, ``flash.wavefront``) are not read here: on the
TPU an op event carries its HLO instruction text and the stats
``device_offset_ps``, ``device_duration_ps`` and ``Time Scale
Multiplier``, and no op_name path.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path

from . import registry
from .spans import PREFIX as PHASE_PREFIX
from .spans import WINDOW
from .trace import clip, find_xplane, gaps, load_events, overlap, union

#: where `bench/run.py` records the traced run's profile
TRACE_DIR = registry.BENCH / ".out" / "trace"
PROGRAM_PREFIX = "repro."
#: the span each inflight step opens once, and the harness phase of finish
STEP_SPAN = PROGRAM_PREFIX + "inflight.dispatch"
FINISH_PHASE = "finish"
NO_PHASE = "(no harness phase)"


def load_program(path: str) -> list[tuple[str, int, int]]:
    """The ``repro.*`` host events of one xplane: (name, start_ns,
    end_ns)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    out.append((ev.name, ev.start_ns, ev.end_ns))
    return out


def intersect(a, b):
    """Intersection of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def innermost(spans) -> dict[str, list]:
    """{name: disjoint sorted intervals} in which that span is the
    innermost of the (name, start, end) spans covering the instant (the
    latest started; of two started together, the shorter)."""
    points = sorted({p for _, s, e in spans for p in (s, e)})
    by_start = sorted(spans, key=lambda x: (x[1], -x[2]))
    out: dict[str, list] = defaultdict(list)
    active: list = []
    k = 0
    for lo, hi in zip(points, points[1:]):
        while k < len(by_start) and by_start[k][1] <= lo:
            active.append(by_start[k])
            k += 1
        active = [sp for sp in active if sp[2] > lo]
        if not active:
            continue
        name = max(active, key=lambda x: (x[1], -x[2]))[0]
        seg = out[name]
        if seg and seg[-1][1] == lo:
            seg[-1] = (seg[-1][0], hi)
        else:
            seg.append((lo, hi))
    return dict(out)


def reduce_program(raw: dict, program: list, n_devices: int | None = None
                   ) -> dict:
    """`raw` from `trace.load_events`, `program` from `load_program`;
    `n_devices` as in `trace.reduce_events`."""
    windows = [(s, e) for name, s, e in raw["host"] if name == WINDOW]
    if not windows:
        return {}
    lo, hi = windows[0]
    phases = defaultdict(list)
    for name, s, e in raw["host"]:
        if name != WINDOW:
            phases[name[len(PHASE_PREFIX):]].append((s, e))
    phases = {k: union(clip(v, lo, hi)) for k, v in phases.items()}

    span_ns: dict[str, float] = defaultdict(float)
    span_n: dict[str, int] = defaultdict(int)
    spans = []
    for name, s, e in program:
        if e > lo and s < hi:
            s, e = max(s, lo), min(e, hi)
            span_ns[name] += e - s
            span_n[name] += 1
            spans.append((name, s, e))
    inner = innermost(spans)
    steps = [(s, e) for name, s, e in spans if name == STEP_SPAN]
    finish = phases.get(FINISH_PHASE, [])
    finish_steps = sum(_inside(iv, finish) for iv in steps)

    # the devices `reduce_events` reduces: of the first `n_devices`, those
    # on which an op ran in the window
    dev_ids = sorted(raw["devices"])
    if n_devices is not None:
        dev_ids = dev_ids[:n_devices]
    dev_ids = [d for d in dev_ids
               if any(e > lo and s < hi for _, s, e, _ in
                      raw["devices"][d]["ops"])]
    idle_by_span: dict[str, float] = defaultdict(float)
    for d in dev_ids:
        ops = raw["devices"][d]["ops"]
        busy = union(clip([(s, e) for _, s, e, _ in ops], lo, hi))
        idle = gaps(busy, lo, hi)
        in_phase = []
        for phase, ph in phases.items():
            part = intersect(idle, ph)
            in_phase.extend(part)
            _attribute(idle_by_span, phase, part, inner)
        _attribute(idle_by_span, NO_PHASE,
                   intersect(idle, gaps(union(in_phase), lo, hi)), inner)
    n = max(1, len(dev_ids))
    return {
        "devices": len(dev_ids),
        "span_s": {k: v * 1e-9 for k, v in span_ns.items()},
        "span_n": dict(span_n),
        "idle_s_by_span": {k: v * 1e-9 / n for k, v in idle_by_span.items()},
        "steps": len(steps),
        "finish_steps": finish_steps,
    }


def _inside(iv, covers) -> bool:
    """Whether interval `iv` lies within one of the disjoint sorted
    intervals `covers`."""
    i = bisect.bisect_right(covers, (iv[0], float("inf"))) - 1
    return i >= 0 and covers[i][0] <= iv[0] and iv[1] <= covers[i][1]


def _attribute(acc: dict, phase: str, part, inner: dict) -> None:
    """Add the idle intervals `part` of `phase` to `acc`: under
    ``<phase>/<span>`` where a program span covers them, else ``<phase>``
    (a key is added even at 0, as `reduce_events` adds every phase)."""
    total = sum(e - s for s, e in part)
    named = 0.0
    for name, segs in inner.items():
        t = overlap(part, segs)
        if t:
            acc[f"{phase}/{name}"] += t
            named += t
    acc[phase] += total - named


def reduce_trace(trace_dir: str | Path | None = None) -> dict:
    """The program reduction of the profile in `trace_dir` (default
    `TRACE_DIR`) over every device that ran an op; {} where there is
    none."""
    path = find_xplane(str(trace_dir or TRACE_DIR))
    if path is None:
        return {}
    return reduce_program(load_events(path), load_program(path))


def for_run(run: dict) -> dict:
    """The program reduction of the run's trace (`TRACE_DIR`), made once
    per run; {} where the harness's reduction found no window.

    The harness reduces the cell's chips, this every device in the trace:
    a superset, so the same devices where the counts agree.  Where they
    do not, ``idle_s_by_span`` would not sum to the harness's
    ``idle_s_by_phase`` and is left out; the span tables hold all the same.
    """
    if "program" not in run:
        red = run.get("trace", {})
        prog = reduce_trace() if red.get("window_s") else {}
        if prog.get("devices") != red.get("devices"):
            prog.pop("idle_s_by_span", None)
        run["program"] = prog
    return run["program"]


def ms_per_span(run: dict, span: str):
    """Mean milliseconds of one program span, or None."""
    prog = for_run(run)
    n = prog.get("span_n", {}).get(span)
    if not n:
        return None
    return 1e3 * prog["span_s"][span] / n
