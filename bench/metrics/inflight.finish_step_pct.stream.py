"""Share of the slot-pool steps in the window that ran inside `finish()`.

Each step opens the program span `repro.inflight.dispatch` once; a step
whose span lies inside the harness's `finish` phase was run by `finish()`
to drain the finishing session (the steps `InflightScheduler.stats
["finish_steps"]` counts), the rest by `pump()`.
"""

from lib.program_trace import for_run


def read(run):
    prog = for_run(run)
    if not prog.get("steps"):
        return None
    return 100.0 * prog["finish_steps"] / prog["steps"]
