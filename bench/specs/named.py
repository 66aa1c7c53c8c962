"""A decode spec named by its class in `repro.core`, with its arguments.

    "spec": {"builder": "named", "class": "FusedSpec", "args": {}}
"""

PARAMS = ("class", "args")


def build(params: dict, *, K: int, T: int, batch: int, log_pi, log_A):
    import repro.core as core
    spec = getattr(core, params["class"])(**params.get("args", {}))
    return spec, "as named by the traffic mix"
