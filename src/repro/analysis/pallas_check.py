"""flashprove pass 2 — static VMEM residency + tile alignment for Pallas kernels.

`kernels/ops.py` guards the TPU path at *runtime* (`_kernel_fits` falls back
to XLA when a config is too big) — but the raw kernels in `viterbi_dp.py`,
`beam_stream.py` and `tropical.py` will happily compile a `pallas_call`
whose blocks cannot fit VMEM, and the failure mode on hardware is a
compile-time or on-device OOM long after planning said yes.  This pass makes
that a lint failure instead.

It does not parse source.  Each kernel entry point is traced
(`jax.make_jaxpr`, `interpret=True` — tracing never executes the kernel) at
every tile config the decode stack can reach (spec defaults and the tile
ladder `ops.tropical_matmul` picks, across the K grid the planner serves),
and the `pallas_call` equations are read straight out of the jaxpr: the
`GridMapping` carries every declared `BlockSpec`'s block shape, the array
aval it blocks, and the traced index map.  From those declarations:

  * **Residency (PV202).**  Per grid step, each operand holds one block of
    ``prod(block_shape) x itemsize`` bytes in VMEM.  An index map whose
    output *moves* with the grid marks a streamed block — the pipeline
    double-buffers it (x2) to overlap the next DMA with compute; a constant
    index map marks a revisited/resident block (x1).  Scratch shapes are
    VMEM by construction.  The sum must fit `DEFAULT_VMEM_BUDGET`
    (= the 12 MiB working limit `ops._kernel_fits` enforces at runtime —
    the two bounds are deliberately the same number).

  * **Tile alignment (PV201).**  TPU vector memory tiles f32 as (8, 128):
    a block whose lane (last) dimension is not a multiple of 128, or whose
    sublane dimension is not a multiple of 8, pads every tile it touches —
    silent bandwidth loss.  Dimensions that cover the whole (unpadded)
    array axis are exempt: the array itself is that shape, so the layout
    cost is the data's, not the blocking's.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .findings import Finding, ProveReport
from .jaxpr_check import iter_eqns

__all__ = [
    "DEFAULT_VMEM_BUDGET", "LANE", "SUBLANE", "BlockInfo", "KernelSummary",
    "harvest_pallas_calls", "kernel_configs", "check_pallas",
]

#: Per-grid-step VMEM budget — matches `ops._kernel_fits`' runtime limit.
DEFAULT_VMEM_BUDGET = 12 * 2**20
#: f32 VMEM tile: (sublane, lane).
SUBLANE, LANE = 8, 128


@dataclasses.dataclass(frozen=True)
class BlockInfo:
    """One declared BlockSpec as read back from a traced `pallas_call`."""
    label: str                  # "in[0]", "out[1]", "scratch[0]"
    block_shape: tuple[int, ...]
    array_shape: tuple[int, ...]
    dtype: str
    streamed: bool              # index map moves with the grid

    @property
    def block_bytes(self) -> int:
        return (math.prod(self.block_shape)
                * np.dtype(self.dtype).itemsize)

    @property
    def resident_bytes(self) -> int:
        """VMEM held per grid step: streamed blocks are double-buffered."""
        return self.block_bytes * (2 if self.streamed else 1)


@dataclasses.dataclass(frozen=True)
class KernelSummary:
    """One `pallas_call` equation: its grid and every operand's residency."""
    grid: tuple[int, ...]
    blocks: tuple[BlockInfo, ...]

    @property
    def vmem_bytes(self) -> int:
        return sum(b.resident_bytes for b in self.blocks)


def _index_map_moves(block_mapping) -> bool:
    """True when the block's index map output depends on the grid position.

    Decided by evaluating the traced index map at two grid corners — no
    structural guessing about literals vs. vars.
    """
    closed = block_mapping.index_map_jaxpr
    n = len(closed.jaxpr.invars)
    zeros = [jnp.int32(0)] * n
    probe = [jnp.int32(3 + i) for i in range(n)]
    at0 = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *zeros)
    at1 = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *probe)
    return any(int(a) != int(b) for a, b in zip(at0, at1))


def _block_dim(d) -> int:
    """One declared block dim: a `pl.Blocked`/`pl.Squeezed` or a plain int."""
    size = getattr(d, "block_size", d)
    return int(size) if isinstance(size, int) else 1   # squeezed: one row


def harvest_pallas_calls(closed) -> list[KernelSummary]:
    """Every `pallas_call` in a traced jaxpr, as `KernelSummary` objects."""
    out = []
    for eqn in iter_eqns(getattr(closed, "jaxpr", closed)):
        if eqn.primitive.name != "pallas_call":
            continue
        gm = eqn.params["grid_mapping"]
        blocks: list[BlockInfo] = []
        n_in = len(eqn.invars)
        n_out = len(eqn.outvars)
        for i, bm in enumerate(gm.block_mappings):
            label = f"in[{i}]" if i < n_in else f"out[{i - n_in}]"
            sd = bm.array_aval
            blocks.append(BlockInfo(
                label=label,
                block_shape=tuple(_block_dim(d) for d in bm.block_shape),
                array_shape=tuple(int(d) for d in sd.shape),
                dtype=np.dtype(sd.dtype).name,
                streamed=_index_map_moves(bm)))
        n_scratch = getattr(gm, "num_scratch_operands", 0)
        if n_scratch:
            body = eqn.params["jaxpr"]
            for j, v in enumerate(body.invars[-n_scratch:]):
                aval = v.aval
                shape = tuple(int(d) for d in getattr(aval, "shape", ()))
                blocks.append(BlockInfo(
                    label=f"scratch[{j}]", block_shape=shape,
                    array_shape=shape,
                    dtype=np.dtype(getattr(aval, "dtype", jnp.float32)).name,
                    streamed=False))
        out.append(KernelSummary(
            grid=tuple(int(g) for g in gm.grid), blocks=tuple(blocks)))
    if not out:
        raise ValueError("traced entry contains no pallas_call")
    return out


def _alignment_findings(subject: str, block: BlockInfo) -> list[Finding]:
    bs, arr = block.block_shape, block.array_shape
    found = []

    def _bad(axis_name: str, dim: int, full: int, mult: int) -> None:
        found.append(Finding(
            "PV201", subject,
            f"{block.label} block {bs} of {block.dtype}{list(arr)}: "
            f"{axis_name} dimension {dim} is neither a multiple of {mult} "
            f"nor the full array axis ({full}); every tile it touches is "
            f"padded on TPU"))

    if not bs:
        return found
    lane, full_lane = bs[-1], arr[-1] if arr else bs[-1]
    if lane % LANE and lane != full_lane:
        _bad("lane", lane, full_lane, LANE)
    if len(bs) >= 2:
        sub, full_sub = bs[-2], arr[-2] if len(arr) >= 2 else bs[-2]
        # sublane 1 is the squeeze/batch-axis idiom (a grid axis indexes
        # single rows); the layout unit that matters is the lane dim.
        if sub % SUBLANE and sub != full_sub and sub != 1:
            _bad("sublane", sub, full_sub, SUBLANE)
    return found


def _check_entry(subject: str, trace: Callable[[], object],
                 budget: int, report: ProveReport) -> None:
    try:
        closed = jax.make_jaxpr(trace)()
    except Exception as e:
        report.findings.append(Finding(
            "PV202", subject, f"trace error {e!r}"))
        return
    for ki, summary in enumerate(harvest_pallas_calls(closed)):
        ksub = subject if ki == 0 else f"{subject}#{ki}"
        for block in summary.blocks:
            report.findings.extend(_alignment_findings(ksub, block))
        vmem = summary.vmem_bytes
        report.stats[ksub] = {
            "grid": list(summary.grid),
            "vmem_bytes": vmem,
            "budget_bytes": budget,
            "blocks": {b.label: {"block_shape": list(b.block_shape),
                                 "dtype": b.dtype,
                                 "streamed": b.streamed,
                                 "resident_bytes": b.resident_bytes}
                       for b in summary.blocks},
        }
        if vmem > budget:
            worst = max(summary.blocks, key=lambda b: b.resident_bytes)
            report.findings.append(Finding(
                "PV202", ksub,
                f"per-grid-step VMEM residency {vmem:,}B exceeds budget "
                f"{budget:,}B (largest: {worst.label} "
                f"{worst.block_shape} {worst.dtype}"
                f"{' x2 streamed' if worst.streamed else ''})"))
    report.checks.append(subject)


def kernel_configs(deep: bool = False) -> list[tuple[str, Callable[[], object]]]:
    """(subject, thunk) per kernel entry x reachable tile config.

    Configs come from the decode stack, not thin air: `FusedSpec.bt` (the
    only bt the planner's typed specs carry), the K ladder the fused/online
    Pallas path accepts (`ops._kernel_fits` requires K % 128 == 0; --deep
    walks it up to the largest config that still passes the runtime guard),
    `ops.beam_step`'s B/chunk defaults, and both tile corners
    `ops.tropical_matmul`'s shape-adaptive ladder can pick.
    """
    from repro.core.spec import FusedSpec
    from repro.kernels import beam_stream, ops, tropical, viterbi_dp

    bt = FusedSpec().bt
    f32 = jnp.float32
    ks = (128, 512, 1024) if deep else (128, 512)
    configs: list[tuple[str, Callable[[], object]]] = []

    def _fused(K: int, bt: int, B: int = 2):
        T = 4 * bt
        A = jnp.zeros((K, K), f32)
        em = jnp.zeros((B, T, K), f32)
        d0 = jnp.zeros((B, K), f32)
        return lambda: viterbi_dp.viterbi_forward_batch(
            A, em, d0, bt=bt, interpret=True)

    for K in ks:
        configs.append((f"pallas:viterbi_dp.viterbi_forward_batch"
                        f"[K={K},bt={bt}]", _fused(K, bt)))

    def _beam(K: int, B: int, chunk: int):
        A = jnp.zeros((K, K), f32)
        em = jnp.zeros((K,), f32)
        sc = jnp.zeros((B,), f32)
        st = jnp.zeros((B,), jnp.int32)
        return lambda: beam_stream.beam_step(
            A, em, sc, st, chunk=chunk, interpret=True)

    for B in (128, 256):
        configs.append((f"pallas:beam_stream.beam_step[K=512,B={B},chunk=256]",
                        _beam(512, B, 256)))

    def _trop(I: int, K: int, J: int):
        a = jnp.zeros((I, K), f32)
        b = jnp.zeros((K, J), f32)
        return lambda: ops.tropical_matmul(a, b, interpret=True)

    # both corners of ops.tropical_matmul's tile ladder:
    # small -> (bi,bk,bj)=(8,8,128), large -> (64,16,256).
    configs.append(("pallas:tropical.tropical_matmul[tiles=8x8x128]",
                    _trop(32, 8, 128)))
    configs.append(("pallas:tropical.tropical_matmul[tiles=64x16x256]",
                    _trop(128, 128, 512)))
    return configs


def check_pallas(quick: bool = False, deep: bool = False,
                 budget: int = DEFAULT_VMEM_BUDGET) -> ProveReport:
    """Verify every kernel x reachable tile config fits VMEM and the tile
    grid.  ``quick`` keeps one config per kernel; ``deep`` extends the K
    ladder to the runtime guard's edge."""
    report = ProveReport()
    configs = kernel_configs(deep=deep)
    if quick:
        seen: set[str] = set()
        kept = []
        for subject, thunk in configs:
            key = subject.split("[")[0]
            if key in seen:
                report.skipped.append(subject)
                continue
            seen.add(key)
            kept.append((subject, thunk))
        configs = kept
    for subject, thunk in configs:
        _check_entry(subject, thunk, budget, report)
    return report
