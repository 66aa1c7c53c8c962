"""The sharded decode program's share of its HBM roofline, over the chips
of the data-parallel offline cell, which reports `frames_per_s`.

The program is `core/batch._sharded_decoder`'s jitted `shard_map`, which
the device trace names ``jit__decode_shard``.  Bytes are counted as
`lib/readers.decode_roofline` counts them (the work, not the
implementation), except that every chip reads its own replica of `log_A`
once a batch.  The device time is summed over the chips, so the share is
of the chips' summed HBM bandwidth.
"""

#: the sharded decode program, as the trace names it
SHARDED_PROGRAM = "jit__decode_shard"


def read(run):
    red, c = run["trace"], run["counters"]
    t = red.get("module_s_total", {}).get(SHARDED_PROGRAM)
    if not t or not c.get("frames"):
        return None
    K = c["K"]
    need = (4 * K * c["frames"] + 4 * c["frames"]
            + 4 * K * K * c["batches"] * red["devices"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / t
