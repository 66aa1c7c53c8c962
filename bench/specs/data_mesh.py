"""A decode spec named by its class in `repro.core`, placed on a data mesh.

    "spec": {"builder": "data_mesh", "class": "FusedSpec", "data": 4}

Returns `repro.core.DataPlacement.over_devices(spec, data)`: the spec on a
one-axis ``data`` mesh of the first `data` devices, each holding a replica
of the HMM and decoding ``batch / data`` rows of every batch; more devices
than jax sees are refused there.  Nothing is built at import.
"""

PARAMS = ("class", "args", "data")


def build(params: dict, *, K: int, T: int, batch: int, log_pi, log_A):
    import repro.core as core
    n = int(params["data"])
    spec = getattr(core, params["class"])(**params.get("args", {}))
    return core.DataPlacement.over_devices(spec, n), (
        f"as named by the traffic mix, on a data mesh of {n} devices, "
        f"{batch // n} rows of each batch per device")
