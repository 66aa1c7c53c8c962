"""Arithmetic that several per-layer metrics share.

Each metric is a file ``bench/metrics/<name>.py`` with ``read(run)``; where
two cells report the same quantity under two names (one per end-to-end
metric it moves), both files read it here.  ``run`` holds the client's
``counters``, the reduced ``trace`` (`lib/trace.py`) and the chip's
``peaks`` (`lib/peaks.py`).  A reader that finds nothing returns None.
"""

from __future__ import annotations

import re

#: the program (XLA module) of the offline decode, as the trace names it:
#: `core/decoder._jit_decode_batch` jits a `functools.partial`, which jax
#: names ``jit__unknown``
DECODE_PROGRAM = "jit__unknown"

#: the fused forward kernel's op, as the trace names it (``%name.<n>``)
FWD_KERNEL_OP = re.compile(r"^%viterbi_forward_batch(\.\d+)?$")


def pad_pct(run):
    """Pad frames over the frames the decode ran, frame-weighted over every
    batch `BatchScheduler.step()` handed back in the window."""
    c = run["counters"]
    if not c.get("run_frames"):
        return None
    return 100.0 * (c["run_frames"] - c["frames"]) / c["run_frames"]


def idle_pct(run):
    """Share of the window in which no op ran on the device."""
    red = run["trace"]
    if not red.get("devices") or not red.get("window_s"):
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def decode_roofline(run):
    """The decode program's share of its HBM roofline.

    Required bytes count the work, not the implementation: the emissions of
    the real frames read once, `log_A` read once per call, the int32 path
    written.  The least time is those bytes over the chip's HBM bandwidth;
    the share is that over the device time of the decode program's
    executions in the window.  The bound is HBM: a Viterbi step is (max, +)
    work on the vector unit, whose peak is not published.
    """
    red, c = run["trace"], run["counters"]
    t = red.get("module_s_total", {}).get(DECODE_PROGRAM)
    if not t or not c.get("frames"):
        return None
    K = c["K"]
    need = 4 * K * c["frames"] + 4 * c["frames"] + 4 * K * K * c["batches"]
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / t


def fwd_kernel_ns_per_frame(run):
    """Device time of the Pallas forward kernel per real frame delivered;
    pad frames cost the kernel the same, so padding shows here too."""
    red, c = run["trace"], run["counters"]
    t = sum(v for k, v in red.get("op_s_total", {}).items()
            if FWD_KERNEL_OP.match(k))
    if not t or not c.get("frames"):
        return None
    return 1e9 * t / c["frames"]
