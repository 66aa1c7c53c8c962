"""Streaming dynamic-beam-search step — Pallas TPU kernel.

One FLASH-BS timestep: expand the B-wide beam against a chunk of C target states
at a time, keeping only the running top-B in VMEM scratch.  This is the TPU
adaptation of the paper's double-buffered min-heap pair (Sec. V-C-2): the
running beam plays `heap_total`, the incoming beam `heap_pre`, and the merge is
a vectorised select instead of sift-down — same O(B) live state, no scalar ops.

Per grid step (one chunk of C targets):
  * the (K, C) column block of log_A streams HBM->VMEM via the Pallas pipeline;
  * beam rows are gathered with a one-hot matmul (MXU-friendly, avoids dynamic
    gathers): rows = onehot(states, K) @ A_block                (B, C);
  * candidates cand[b, c] = score[b] + rows[b, c] + em[c];
  * per-target reduction over the beam, then a B-round vectorised selection
    merges (C candidates + running B) back into the top-B scratch.

Grid iteration is sequential, so the scratch beam carries across chunks; the
final chunk writes the new beam out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SENTINEL = -4.0e9


def _select_top_b(vals, idxs, froms, B: int):
    """Vectorised top-B selection from (N,) candidates (N = B + C)."""
    N = vals.shape[0]
    iota_b = jnp.arange(B, dtype=jnp.int32)

    def body(i, carry):
        vals_m, out_v, out_i, out_f = carry
        m = jnp.max(vals_m)
        am = jnp.argmax(vals_m).astype(jnp.int32)
        sel = iota_b == i
        out_v = jnp.where(sel, m, out_v)
        out_i = jnp.where(sel, idxs[am], out_i)
        out_f = jnp.where(sel, froms[am], out_f)
        vals_m = jnp.where(jnp.arange(N) == am, _SENTINEL * 2, vals_m)
        return vals_m, out_v, out_i, out_f

    init = (vals,
            jnp.full((B,), _SENTINEL, vals.dtype),
            jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), jnp.int32))
    _, out_v, out_i, out_f = jax.lax.fori_loop(0, B, body, init)
    return out_v, out_i, out_f


def _beam_step_kernel(a_ref, em_ref, scores_ref, states_ref,
                      out_s_ref, out_st_ref, out_f_ref,
                      run_s, run_st, run_f, *, B: int, C: int, K: int,
                      nchunks: int):
    c = pl.program_id(0)

    @pl.when(c == 0)
    def _seed():
        run_s[...] = jnp.full((B,), _SENTINEL, run_s.dtype)
        run_st[...] = jnp.zeros((B,), jnp.int32)
        run_f[...] = jnp.zeros((B,), jnp.int32)

    scores = scores_ref[...]                    # (B,)
    states = states_ref[...]                    # (B,) int32
    a_blk = a_ref[...]                          # (K, C) column block
    em_c = em_ref[...]                          # (C,)

    onehot = (states[:, None] == jnp.arange(K, dtype=jnp.int32)[None, :])
    rows = jax.lax.dot_general(
        onehot.astype(a_blk.dtype), a_blk,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (B, C)

    cand = scores[:, None] + rows + em_c[None, :]
    best = jnp.max(cand, axis=0)                               # (C,)
    from_b = jnp.argmax(cand, axis=0).astype(jnp.int32)        # (C,)
    tgt = (c * C + jnp.arange(C)).astype(jnp.int32)

    vals = jnp.concatenate([run_s[...], best])
    idxs = jnp.concatenate([run_st[...], tgt])
    froms = jnp.concatenate([run_f[...], from_b])
    nv, ni, nf = _select_top_b(vals, idxs, froms, B)
    run_s[...] = nv
    run_st[...] = ni
    run_f[...] = nf

    @pl.when(c == nchunks - 1)
    def _emit():
        out_s_ref[...] = run_s[...]
        out_st_ref[...] = run_st[...]
        out_f_ref[...] = run_f[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def beam_step(log_A: jax.Array, em_t: jax.Array, scores: jax.Array,
              states: jax.Array, *, chunk: int = 256, interpret: bool = False):
    """One dynamic-beam transition.

    Args:
      log_A:  (K, K) transitions; K multiple of `chunk`.
      em_t:   (K,) emissions at this step.
      scores/states: (B,) current beam.

    Returns:
      (new_scores, new_states, from_slots) — each (B,).
    """
    K = log_A.shape[0]
    B = scores.shape[0]
    assert K % chunk == 0, (K, chunk)
    nchunks = K // chunk

    return pl.pallas_call(
        functools.partial(_beam_step_kernel, B=B, C=chunk, K=K,
                          nchunks=nchunks),
        grid=(nchunks,),
        in_specs=[
            pl.BlockSpec((K, chunk), lambda c: (0, c)),  # A column block
            pl.BlockSpec((chunk,), lambda c: (c,)),
            pl.BlockSpec((B,), lambda c: (0,)),
            pl.BlockSpec((B,), lambda c: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((B,), lambda c: (0,)),
            pl.BlockSpec((B,), lambda c: (0,)),
            pl.BlockSpec((B,), lambda c: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B,), scores.dtype),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B,), scores.dtype),
            pltpu.VMEM((B,), jnp.int32),
            pltpu.VMEM((B,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(log_A, em_t, scores, states)


__all__ = ["beam_step"]
