"""The planner's pick for the cell's K, longest bucket and batch.

    "spec": {"builder": "plan", "budget_bytes": 1073741824}

Calls `repro.core.plan(K, T, ResourceBudget(budget_bytes), batch=batch)`;
the spec it returns and its reason go to the info line.
"""

PARAMS = ("budget_bytes",)


def build(params: dict, *, K: int, T: int, batch: int, log_pi, log_A):
    import repro.core as core
    p = core.plan(K, T, core.ResourceBudget(int(params["budget_bytes"])),
                  batch=batch)
    return p.spec, p.why
