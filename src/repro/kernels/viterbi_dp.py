"""Fused Viterbi forward-pass Pallas TPU kernel (single-sequence and batched).

Runs the whole DP recursion
    delta_t[j] = max_k (delta_{t-1}[k] + log_A[k, j]) + em[t, j]
inside one kernel: the transition matrix stays resident in VMEM for the entire
launch, emissions stream in (bt, K) blocks through the Pallas pipeline (which
double-buffers them — the paper's DDR->BRAM double-buffering scheme realised as
HBM->VMEM), backpointers stream out, and delta is carried across sequential grid
steps in a VMEM scratch.  Compared with the XLA `lax.scan` lowering this removes
the per-step HBM round-trip of delta (2*K*4 B/step) and the per-step kernel
launch — the DP becomes emission-streaming-bound, its roofline floor.

The grid is (B, T // bt): the batch axis is the outer (slowest) grid dimension,
so one launch decodes a whole request bucket with `log_A` loaded exactly once.
The delta scratch is re-seeded from `delta0[b]` at each sequence's first block,
which is what makes the cross-block carry legal per sequence.

Ragged batches are handled by a per-step pad mask streamed alongside the
emissions: a masked step is a *tropical identity* — delta is left unchanged and
the emitted backpointer row is the identity permutation — so scores and
backtracked paths are bit-identical to decoding each sequence at its true
length.  The same mask lets odd T pad up to a bt multiple instead of degrading
the block size.

Constraints (checked in `ops.viterbi_forward[_batch]`):
  * K multiple of 128 (lane width), K^2 * 4 B + working set within VMEM
    (K <= 1024 fp32 with default bt; larger K falls back to the XLA path).
  * TPU grid iteration is sequential ("arbitrary" dimension semantics), which is
    what makes the scratch carry legal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Per-sequence operands carry a unit axis so every block's last two dims are
# legal on the chip: (bt, 1) for the pad flags (1 == the full lane axis) and
# (1, K) for the delta rows (1 == the full sublane axis, K lane-aligned).  A
# (1, bt) / (1, K) block over a (B, T) / (B, K) array is refused by Mosaic.

def _tiled_operands(em, delta0, pad):
    """(B, T, 1) pad flags and (B, 1, K) initial deltas for the kernels."""
    B, T, K = em.shape
    if pad is None:
        pad = jnp.zeros((B, T), em.dtype)
    return pad.astype(em.dtype)[:, :, None], delta0[:, None, :]


def _pad_spec(bt: int):
    return pl.BlockSpec((1, bt, 1), lambda b, ti: (b, ti, 0))


def _row_spec(K: int):
    return pl.BlockSpec((1, 1, K), lambda b, ti: (b, 0, 0))


def _out_shape(B: int, T: int, K: int, dtype):
    return [jax.ShapeDtypeStruct((B, T, K), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, K), dtype)]


def _viterbi_fwd_kernel(a_ref, em_ref, pad_ref, d0_ref, psi_ref, dT_ref, dscr,
                        *, bt: int, nsteps: int):
    ti = pl.program_id(1)                    # time-block index (b is axis 0)

    @pl.when(ti == 0)
    def _seed():                             # new sequence: re-seed the carry
        dscr[0, :] = d0_ref[0, 0, :]

    log_a = a_ref[...]                       # (K, K), resident
    K = log_a.shape[0]
    eye = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)[0]

    def body(s, delta):
        scores = delta[:, None] + log_a      # (K_src, K_dst)
        psi = jnp.argmax(scores, axis=0).astype(jnp.int32)
        new = jnp.max(scores, axis=0) + em_ref[0, s, :]
        is_pad = pad_ref[0, s, 0] > 0.5      # tropical-identity step
        psi_ref[0, s, :] = jnp.where(is_pad, eye, psi)
        return jnp.where(is_pad, delta, new)

    delta = jax.lax.fori_loop(0, bt, body, dscr[0, :])
    dscr[0, :] = delta

    @pl.when(ti == nsteps - 1)
    def _emit():
        dT_ref[0, 0, :] = delta


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def viterbi_forward_batch(log_A: jax.Array, em: jax.Array, delta0: jax.Array,
                          pad: jax.Array | None = None, *,
                          bt: int = 8, interpret: bool = False):
    """Batched fused forward pass.

    Args:
      log_A:  (K, K) transition log-probs, shared across the batch.
      em:     (B, T, K) emission scores for steps 1..T (step 0 is in `delta0`).
      delta0: (B, K) initial DP states.
      pad:    optional (B, T); entries > 0.5 mark tropical-identity steps
              (delta frozen, identity backpointers).  None means no padding.

    Returns:
      (psi, delta_T): (B, T, K) int32 backpointers and final (B, K) DP states.
    """
    B, T, K = em.shape
    assert T % bt == 0, (T, bt)
    nsteps = T // bt
    pad3, d03 = _tiled_operands(em, delta0, pad)

    psi, dT = pl.pallas_call(
        functools.partial(_viterbi_fwd_kernel, bt=bt, nsteps=nsteps),
        grid=(B, nsteps),
        in_specs=[
            pl.BlockSpec((K, K), lambda b, ti: (0, 0)),      # resident
            pl.BlockSpec((1, bt, K), lambda b, ti: (b, ti, 0)),  # streamed
            _pad_spec(bt),
            _row_spec(K),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, K), lambda b, ti: (b, ti, 0)),  # streamed out
            _row_spec(K),
        ],
        out_shape=_out_shape(B, T, K, em.dtype),
        scratch_shapes=[pltpu.VMEM((1, K), em.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(log_A, em, pad3, d03)
    return psi, dT[:, 0]


def _viterbi_fwd_masked_kernel(*refs, bt: int, nsteps: int, has_tmask: bool,
                               has_smask: bool):
    """Constraint-masked variant of `_viterbi_fwd_kernel`.

    The masks arrive as additive f32 penalties ({0, NEG_INF}, see
    `core.constraints`): the static transition penalty rides VMEM-resident
    next to `log_A` and is added once per grid step, the per-step state
    penalty streams in (bt, K) blocks alongside the emissions (shared across
    the batch — one schedule per constraint).  Both adds reproduce the
    reference `log_A + t_pen` / `em + s_pen` elementwise adds exactly, so
    the masked kernel is bit-identical to decoding pre-masked inputs.
    """
    it = iter(refs)
    a_ref = next(it)
    tm_ref = next(it) if has_tmask else None
    em_ref = next(it)
    sm_ref = next(it) if has_smask else None
    pad_ref = next(it)
    d0_ref = next(it)
    psi_ref = next(it)
    dT_ref = next(it)
    dscr = next(it)

    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _seed():
        dscr[0, :] = d0_ref[0, 0, :]

    log_a = a_ref[...]
    if has_tmask:
        log_a = log_a + tm_ref[...]
    K = log_a.shape[0]
    eye = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)[0]

    def body(s, delta):
        scores = delta[:, None] + log_a
        psi = jnp.argmax(scores, axis=0).astype(jnp.int32)
        em_t = em_ref[0, s, :]
        if has_smask:
            em_t = em_t + sm_ref[s, :]
        new = jnp.max(scores, axis=0) + em_t
        is_pad = pad_ref[0, s, 0] > 0.5
        psi_ref[0, s, :] = jnp.where(is_pad, eye, psi)
        return jnp.where(is_pad, delta, new)

    delta = jax.lax.fori_loop(0, bt, body, dscr[0, :])
    dscr[0, :] = delta

    @pl.when(ti == nsteps - 1)
    def _emit():
        dT_ref[0, 0, :] = delta


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def viterbi_forward_batch_masked(log_A: jax.Array, em: jax.Array,
                                 delta0: jax.Array,
                                 pad: jax.Array | None = None,
                                 tmask: jax.Array | None = None,
                                 smask: jax.Array | None = None, *,
                                 bt: int = 8, interpret: bool = False):
    """Batched fused forward pass with fused constraint penalties.

    Args:
      log_A, em, delta0, pad: as in `viterbi_forward_batch`.
      tmask: optional (K, K) f32 additive transition penalty (VMEM-resident).
      smask: optional (T, K) f32 additive per-step state penalty, shared
             across the batch, streamed in (bt, K) blocks with the emissions.
             Row t masks em[:, t] (the caller aligns step offsets).

    Returns:
      (psi, delta_T): (B, T, K) int32 backpointers and final (B, K) states.
    """
    B, T, K = em.shape
    assert T % bt == 0, (T, bt)
    nsteps = T // bt
    pad3, d03 = _tiled_operands(em, delta0, pad)
    has_tmask = tmask is not None
    has_smask = smask is not None

    inputs = [log_A]
    in_specs = [pl.BlockSpec((K, K), lambda b, ti: (0, 0))]
    if has_tmask:
        inputs.append(tmask)
        in_specs.append(pl.BlockSpec((K, K), lambda b, ti: (0, 0)))
    inputs.append(em)
    in_specs.append(pl.BlockSpec((1, bt, K), lambda b, ti: (b, ti, 0)))
    if has_smask:
        inputs.append(smask)
        in_specs.append(pl.BlockSpec((bt, K), lambda b, ti: (ti, 0)))
    inputs += [pad3, d03]
    in_specs += [_pad_spec(bt), _row_spec(K)]

    psi, dT = pl.pallas_call(
        functools.partial(_viterbi_fwd_masked_kernel, bt=bt, nsteps=nsteps,
                          has_tmask=has_tmask, has_smask=has_smask),
        grid=(B, nsteps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bt, K), lambda b, ti: (b, ti, 0)),
            _row_spec(K),
        ],
        out_shape=_out_shape(B, T, K, em.dtype),
        scratch_shapes=[pltpu.VMEM((1, K), em.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*inputs)
    return psi, dT[:, 0]


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def viterbi_forward(log_A: jax.Array, em: jax.Array, delta0: jax.Array,
                    pad: jax.Array | None = None, *,
                    bt: int = 8, interpret: bool = False):
    """Single-sequence fused forward pass (B=1 view of the batched kernel).

    Args:
      log_A:  (K, K) transition log-probs.
      em:     (T, K) emission scores for steps 1..T (step 0 is in `delta0`).
      delta0: (K,) initial DP state.
      pad:    optional (T,) tropical-identity step mask (see batch variant).

    Returns:
      (psi, delta_T): (T, K) int32 backpointers and final (K,) DP state.
    """
    psi, delta_T = viterbi_forward_batch(
        log_A, em[None], delta0[None], None if pad is None else pad[None],
        bt=bt, interpret=interpret)
    return psi[0], delta_T[0]


__all__ = ["viterbi_forward", "viterbi_forward_batch",
           "viterbi_forward_batch_masked"]
