"""Real frames advanced over the frames the slot pool could advance.

From `InflightScheduler.stats` over the window: frames / (steps x
max_slots x block).  Slots with nothing buffered still run whole identity
blocks, so this is the share of the step kernel's work that was useful.
"""


def read(run):
    c = run["counters"]
    if not c.get("steps"):
        return None
    return 100.0 * c["frames"] / (c["steps"] * c["max_slots"] * c["block"])
