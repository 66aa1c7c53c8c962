"""Host wall time inside `pump()` and `finish()` per slot-pool step.

The harness times every `pump()` and `finish()` call in the window; all
of that time over the steps taken in the window.  It holds the kernel
dispatch, the psi copy to the host and the host commit scan.
"""


def read(run):
    c = run["counters"]
    if not c.get("steps"):
        return None
    return 1e3 * c["busy_s"] / c["steps"]
