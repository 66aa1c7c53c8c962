"""Jit'd public wrappers around the Pallas kernels.

These handle padding/tile selection/fallbacks so callers never see the kernels'
alignment constraints.  `interpret=None` (the default) compiles the Mosaic
kernel on a TPU backend and runs the Pallas interpreter on the CPU backend,
which is how the test-suite validates kernels; any other backend raises
instead of silently interpreting.  `tests/test_tpu_compile.py` compiles the
main-path kernels for a described TPU v5e, and `chip_smoke.py` runs them on
one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref as _ref
from .tropical import tropical_matmul as _tropical_pallas
from .viterbi_dp import viterbi_forward as _vit_fwd_pallas
from .viterbi_dp import viterbi_forward_batch as _vit_fwd_batch_pallas
from .viterbi_dp import (
    viterbi_forward_batch_masked as _vit_fwd_batch_masked_pallas)
from .beam_stream import beam_step as _beam_step_pallas

_NEG = -1.0e9


def _default_interpret() -> bool:
    """Interpret on the CPU backend, compile on the TPU; nothing else."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run on the TPU (compiled) or the CPU (interpreted); "
        f"the default backend is {backend!r}. Pass interpret= explicitly.")


def _pad_to(x: jax.Array, axis: int, mult: int, value) -> jax.Array:
    n = x.shape[axis]
    target = int(np.ceil(n / mult)) * mult
    if target == n:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - n)
    return jnp.pad(x, widths, constant_values=value)


def tropical_matmul(a: jax.Array, b: jax.Array, *, interpret: bool | None = None):
    """(max,+) product with argmax, arbitrary shapes. Returns (vals, args)."""
    if interpret is None:
        interpret = _default_interpret()
    I, K = a.shape
    _, J = b.shape
    bi = 8 if I < 64 else 64
    bk = 8 if K < 16 else 16
    bj = 128 if J < 256 else 256
    ap = _pad_to(_pad_to(a, 0, bi, _NEG), 1, bk, _NEG)
    bp = _pad_to(_pad_to(b, 0, bk, _NEG), 1, bj, _NEG)
    vals, args = _tropical_pallas(ap, bp, bi=bi, bk=bk, bj=bj,
                                  interpret=interpret)
    args = jnp.minimum(args, K - 1)  # pad-K argmax can only win on pad rows
    return vals[:I, :J], args[:I, :J]


_ref_fwd_jit = jax.jit(_ref.viterbi_forward_ref)
_ref_fwd_masked_batch_jit = jax.jit(
    jax.vmap(_ref.viterbi_forward_masked_ref, in_axes=(None, 0, 0, 0)))


def _kernel_fits(log_A: jax.Array, K: int, bt: int, limit: int) -> bool:
    a_bytes = K * K * log_A.dtype.itemsize
    work = a_bytes + 3 * bt * K * 4 + K * K * 4  # A + streams + scores intermediate
    return K % 128 == 0 and work <= limit


def viterbi_forward(log_A: jax.Array, em: jax.Array, delta0: jax.Array, *,
                    bt: int = 8, interpret: bool | None = None,
                    vmem_limit_bytes: int = 12 * 2**20):
    """Fused Viterbi forward pass with XLA fallback when K exceeds VMEM.

    em covers steps 1..T (delta0 is step 0). Returns (psi (T,K) i32, delta_T).
    """
    if interpret is None:
        interpret = _default_interpret()
    T, K = em.shape
    if T == 0:
        return jnp.zeros((0, K), jnp.int32), delta0
    if not _kernel_fits(log_A, K, bt, vmem_limit_bytes):
        return _ref_fwd_jit(log_A, em, delta0)  # XLA path, retrace-cached
    Tp = int(np.ceil(T / bt)) * bt
    if Tp == T:
        return _vit_fwd_pallas(log_A, em, delta0, bt=bt, interpret=interpret)
    # pad T up to a bt multiple with tropical-identity steps — exact, and keeps
    # the full block size instead of degrading the tiling on odd lengths
    em_p = jnp.pad(em, ((0, Tp - T), (0, 0)))
    pad = (jnp.arange(Tp) >= T).astype(em.dtype)
    psi, delta_T = _vit_fwd_pallas(log_A, em_p, delta0, pad, bt=bt,
                                   interpret=interpret)
    return psi[:T], delta_T


def viterbi_forward_batch(log_A: jax.Array, em: jax.Array, delta0: jax.Array,
                          lengths: jax.Array | None = None, *,
                          bt: int = 8, interpret: bool | None = None,
                          vmem_limit_bytes: int = 12 * 2**20):
    """Batched fused forward pass over (B, T, K) emissions with ragged lengths.

    One kernel launch covers the whole batch: the grid gains a batch dimension
    and `log_A` stays resident in VMEM across every sequence.  `lengths[i]`
    counts the *real* rows of `em[i]` (delta0 is step 0 and always real); the
    remaining rows run as tropical-identity steps, so per-sequence results are
    bit-identical to `viterbi_forward` on the unpadded prefix.

    Returns (psi (B, T, K) int32, delta_T (B, K)).  psi rows at padded steps
    are the identity permutation.
    """
    if interpret is None:
        interpret = _default_interpret()
    B, T, K = em.shape
    if T == 0:
        return jnp.zeros((B, 0, K), jnp.int32), delta0
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if not _kernel_fits(log_A, K, bt, vmem_limit_bytes):
        pad = jnp.arange(T)[None, :] >= lengths[:, None]
        return _ref_fwd_masked_batch_jit(log_A, em, delta0, pad)
    Tp = int(np.ceil(T / bt)) * bt
    em_p = jnp.pad(em, ((0, 0), (0, Tp - T), (0, 0)))
    pad = (jnp.arange(Tp)[None, :] >= lengths[:, None]).astype(em.dtype)
    psi, delta_T = _vit_fwd_batch_pallas(log_A, em_p, delta0, pad, bt=bt,
                                         interpret=interpret)
    return psi[:, :T], delta_T


def viterbi_chunk_step(log_A: jax.Array, em_chunk: jax.Array, delta: jax.Array,
                       *, bt: int = 8, interpret: bool | None = None):
    """One streaming DP advance: carry delta through a (C, K) emission chunk.

    The online decoders feed arbitrary-length chunks; each chunk runs the same
    fused Pallas forward kernel as the offline path (log_A resident in VMEM,
    emissions streamed) instead of a per-timestep Python loop.

    Returns (psi (C, K) int32, delta' (K,)).
    """
    return viterbi_forward(log_A, em_chunk, delta, bt=bt, interpret=interpret)


def viterbi_slot_step(log_A: jax.Array, em: jax.Array, delta: jax.Array,
                      nfeed: jax.Array, *, bt: int = 8,
                      interpret: bool | None = None):
    """One inflight-batching advance: carry S slot deltas through a block.

    This is the slot-masked block step the continuous-batching scheduler
    issues once per `step()`: `em` is (S, block, K) with slot s holding
    `nfeed[s]` real emission rows (0 <= nfeed[s] <= block) followed by
    arbitrary padding.  Slots with `nfeed[s] == 0` — free slots, or live
    slots with nothing buffered — run the whole block as tropical-identity
    steps: their delta comes back bit-identical and their psi rows are the
    identity permutation.  Because the shapes (S, block, K) are fixed for
    the scheduler's lifetime, sessions joining and leaving only ever change
    array *contents*, so this traces exactly once (pinned by the retrace
    battery).

    Per-slot results are bit-identical to `viterbi_chunk_step` on the
    unpadded prefix (the batch-grid kernel's per-sequence equivalence is
    pinned by the PR 2 tests).

    Returns (psi (S, block, K) int32, delta' (S, K)).
    """
    return viterbi_forward_batch(log_A, em, delta, nfeed, bt=bt,
                                 interpret=interpret)


def viterbi_decode_fused(log_pi: jax.Array, log_A: jax.Array, em: jax.Array,
                         *, bt: int = 8, interpret: bool | None = None):
    """Full Viterbi decode using the fused forward kernel + XLA backtracking."""
    delta0 = log_pi + em[0]
    psi, delta_T = viterbi_forward(log_A, em[1:], delta0, bt=bt,
                                   interpret=interpret)
    q_last = jnp.argmax(delta_T).astype(jnp.int32)

    def back(q, psi_t):
        q_prev = psi_t[q].astype(jnp.int32)
        return q_prev, q_prev

    _, prefix = jax.lax.scan(back, q_last, psi, reverse=True)
    return jnp.concatenate([prefix, q_last[None]]), delta_T[q_last]


def viterbi_decode_fused_batch(log_pi: jax.Array, log_A: jax.Array,
                               em: jax.Array, lengths: jax.Array | None = None,
                               *, bt: int = 8, interpret: bool | None = None):
    """Batched full Viterbi decode: one batch-grid kernel launch + vmapped
    XLA backtracking.

    Args:
      em:      (B, T, K) emissions, row i real for the first lengths[i] steps.
      lengths: optional (B,) int32 true lengths (None means full length).

    Returns:
      (paths (B, T) int32, scores (B,)).  paths[i, t] for t >= lengths[i]
      repeat the sequence's final decoded state (the identity backpointers of
      the pad steps); slice to [:lengths[i]] for the true decode.
    """
    B, T, K = em.shape
    delta0 = log_pi[None, :] + em[:, 0, :]
    if T == 1:
        q = jnp.argmax(delta0, axis=1).astype(jnp.int32)
        return q[:, None], jnp.max(delta0, axis=1)
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    psi, delta_T = viterbi_forward_batch(
        log_A, em[:, 1:], delta0, jnp.maximum(lengths - 1, 0),
        bt=bt, interpret=interpret)
    q_last = jnp.argmax(delta_T, axis=1).astype(jnp.int32)

    def back_one(q, psis):
        def back(q, psi_t):
            q_prev = psi_t[q].astype(jnp.int32)
            return q_prev, q_prev
        _, prefix = jax.lax.scan(back, q, psis, reverse=True)
        return prefix

    prefix = jax.vmap(back_one)(q_last, psi)
    paths = jnp.concatenate([prefix, q_last[:, None]], axis=1)
    scores = jnp.take_along_axis(delta_T, q_last[:, None], axis=1)[:, 0]
    return paths, scores


def _kernel_fits_masked(log_A, K: int, bt: int, limit: int,
                        has_tmask: bool, has_smask: bool) -> bool:
    a_bytes = K * K * log_A.dtype.itemsize
    work = a_bytes + 3 * bt * K * 4 + K * K * 4
    if has_tmask:
        work += 2 * K * K * 4        # resident penalty + masked-A intermediate
    if has_smask:
        work += bt * K * 4           # penalty block streamed with the emissions
    return K % 128 == 0 and work <= limit


def viterbi_forward_batch_masked(log_A: jax.Array, em: jax.Array,
                                 delta0: jax.Array,
                                 lengths: jax.Array | None = None, *,
                                 tmask=None, smask=None,
                                 bt: int = 8, interpret: bool | None = None,
                                 vmem_limit_bytes: int = 12 * 2**20):
    """Constraint-masked batched forward pass (fallback: pre-masked XLA ref).

    `tmask` (K, K) / `smask` (T, K) are additive f32 penalties ({0, NEG_INF},
    compiled by `core.constraints`); `smask` row t masks `em[:, t]` and is
    shared across the batch.  Results are bit-identical to
    `viterbi_forward_batch(log_A + tmask, em + smask, ...)` without the
    masked operands ever being materialised on the kernel path.
    """
    if interpret is None:
        interpret = _default_interpret()
    B, T, K = em.shape
    if tmask is not None:
        tmask = jnp.asarray(tmask, em.dtype)
    if smask is not None:
        smask = jnp.asarray(smask, em.dtype)
    if T == 0:
        return jnp.zeros((B, 0, K), jnp.int32), delta0
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if not _kernel_fits_masked(log_A, K, bt, vmem_limit_bytes,
                               tmask is not None, smask is not None):
        pad = jnp.arange(T)[None, :] >= lengths[:, None]
        la = log_A if tmask is None else log_A + tmask
        em2 = em if smask is None else em + smask[None]
        return _ref_fwd_masked_batch_jit(la, em2, delta0, pad)
    Tp = int(np.ceil(T / bt)) * bt
    em_p = jnp.pad(em, ((0, 0), (0, Tp - T), (0, 0)))
    if smask is not None:
        smask = jnp.pad(smask, ((0, Tp - T), (0, 0)))  # pad steps: identity
    pad = (jnp.arange(Tp)[None, :] >= lengths[:, None]).astype(em.dtype)
    psi, delta_T = _vit_fwd_batch_masked_pallas(
        log_A, em_p, delta0, pad, tmask, smask, bt=bt, interpret=interpret)
    return psi[:, :T], delta_T


def viterbi_decode_fused_masked(log_pi: jax.Array, log_A: jax.Array,
                                em: jax.Array, *, t_pen=None, pi_pen=None,
                                s_pen=None, bt: int = 8,
                                interpret: bool | None = None):
    """Constrained fused decode: penalty adds fused into the DP step.

    The penalties come from `core.constraints.compiled_penalties`; every add
    here reproduces `constrain_inputs`' elementwise adds operand-for-operand,
    so the result is bit-identical to `viterbi_decode_fused` over the
    pre-masked inputs.
    """
    if pi_pen is not None:
        log_pi = log_pi + jnp.asarray(pi_pen, log_pi.dtype)
    em0 = em[0]
    smask = None
    if s_pen is not None:
        s_pen = jnp.asarray(s_pen, em.dtype)
        em0 = em0 + s_pen[0]
        smask = s_pen[1:]
    delta0 = log_pi + em0
    psi, delta_T = viterbi_forward_batch_masked(
        log_A, em[None, 1:], delta0[None], tmask=t_pen, smask=smask,
        bt=bt, interpret=interpret)
    psi, delta_T = psi[0], delta_T[0]
    q_last = jnp.argmax(delta_T).astype(jnp.int32)

    def back(q, psi_t):
        q_prev = psi_t[q].astype(jnp.int32)
        return q_prev, q_prev

    _, prefix = jax.lax.scan(back, q_last, psi, reverse=True)
    return jnp.concatenate([prefix, q_last[None]]), delta_T[q_last]


def viterbi_decode_fused_batch_masked(log_pi: jax.Array, log_A: jax.Array,
                                      em: jax.Array,
                                      lengths: jax.Array | None = None, *,
                                      t_pen=None, pi_pen=None, s_pen=None,
                                      bt: int = 8,
                                      interpret: bool | None = None):
    """Constrained batched fused decode (ragged lengths, shared schedule).

    The per-step penalty indexes *absolute* step t, so ragged tails simply
    never reach the later rows; pad steps stay tropical-identity.  Bit-
    identical to `viterbi_decode_fused_batch` over pre-masked inputs.
    """
    B, T, K = em.shape
    if pi_pen is not None:
        log_pi = log_pi + jnp.asarray(pi_pen, log_pi.dtype)
    em0 = em[:, 0, :]
    smask = None
    if s_pen is not None:
        s_pen = jnp.asarray(s_pen, em.dtype)
        em0 = em0 + s_pen[0][None]
        smask = s_pen[1:]
    delta0 = log_pi[None, :] + em0
    if T == 1:
        q = jnp.argmax(delta0, axis=1).astype(jnp.int32)
        return q[:, None], jnp.max(delta0, axis=1)
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    psi, delta_T = viterbi_forward_batch_masked(
        log_A, em[:, 1:], delta0, jnp.maximum(lengths - 1, 0),
        tmask=t_pen, smask=smask, bt=bt, interpret=interpret)
    q_last = jnp.argmax(delta_T, axis=1).astype(jnp.int32)

    def back_one(q, psis):
        def back(q, psi_t):
            q_prev = psi_t[q].astype(jnp.int32)
            return q_prev, q_prev
        _, prefix = jax.lax.scan(back, q, psis, reverse=True)
        return prefix

    prefix = jax.vmap(back_one)(q_last, psi)
    paths = jnp.concatenate([prefix, q_last[:, None]], axis=1)
    scores = jnp.take_along_axis(delta_T, q_last[:, None], axis=1)[:, 0]
    return paths, scores


@functools.partial(jax.jit, static_argnames=("width",))
def viterbi_decode_banded(log_pi: jax.Array, log_A: jax.Array, em: jax.Array,
                          centers, *, width: int):
    """Banded Viterbi decode: O(T * Kb^2) work, Kb = 2*width+1 window.

    At step t only states within `width` of `centers[t]` (clipped into
    [0, K-1]) are legal — the `BandConstraint` semantics.  The DP slides a
    contiguous Kb window over the state axis (`lax.dynamic_slice` of the
    (Kb, Kb) transition block per step), so K-wide rows are never
    materialised: live state is the Kb frontier plus T windows of local
    backpointers (`core.constraints.banded_state_bytes`).

    Bit-identity with the dense masked decode holds because (a) the window
    always contains the whole allowed band, (b) the in-window penalty add is
    the same `em + s_pen` elementwise add the dense path performs, and (c)
    out-of-band states sit >= ~1e9 below every in-band score (NEG_INF is a
    finite sentinel), so they can neither win nor tie a max/argmax, and the
    contiguous window preserves dense argmax tie order.  Requires in-band
    states to keep feasible paths (dense `log_A`) — with sparse transitions,
    pre-mask `log_A` instead.

    Returns (path (T,) int32 of *global* state ids, score).
    """
    T, K = em.shape
    w = int(width)
    Kb = min(2 * w + 1, K)
    centers = jnp.clip(jnp.asarray(centers, jnp.int32)[:T], 0, K - 1)
    starts = jnp.clip(centers - w, 0, K - Kb).astype(jnp.int32)
    offs = jnp.arange(Kb, dtype=jnp.int32)

    def win_pen(c, start):
        idx = start + offs
        return jnp.where(jnp.abs(idx - c) <= w,
                         jnp.asarray(0.0, em.dtype),
                         jnp.asarray(_NEG, em.dtype))

    s0 = starts[0]
    d0 = (jax.lax.dynamic_slice(log_pi, (s0,), (Kb,))
          + (jax.lax.dynamic_slice(em[0], (s0,), (Kb,))
             + win_pen(centers[0], s0)))

    def step(carry, inp):
        delta_w, prev_start = carry
        c, start, em_t = inp
        a_sub = jax.lax.dynamic_slice(log_A, (prev_start, start), (Kb, Kb))
        scores = delta_w[:, None] + a_sub
        psi = jnp.argmax(scores, axis=0).astype(jnp.int32)
        em_w = (jax.lax.dynamic_slice(em_t, (start,), (Kb,))
                + win_pen(c, start))
        new = jnp.max(scores, axis=0) + em_w
        return (new, start), psi

    (delta_w, _), psis = jax.lax.scan(
        step, (d0, s0), (centers[1:], starts[1:], em[1:]))
    q_loc = jnp.argmax(delta_w).astype(jnp.int32)

    def back(q, psi_t):
        q_prev = psi_t[q].astype(jnp.int32)
        return q_prev, q_prev

    _, prefix = jax.lax.scan(back, q_loc, psis, reverse=True)
    loc = jnp.concatenate([prefix, q_loc[None]])
    return (starts + loc).astype(jnp.int32), delta_w[q_loc]


def beam_step(log_A: jax.Array, em_t: jax.Array, scores: jax.Array,
              states: jax.Array, *, chunk: int = 256,
              interpret: bool | None = None):
    """Streaming dynamic-beam step, arbitrary K (padded to chunk)."""
    if interpret is None:
        interpret = _default_interpret()
    K = log_A.shape[0]
    chunk = min(chunk, int(np.ceil(K / 128)) * 128)
    Ap = _pad_to(_pad_to(log_A, 0, chunk, _NEG * 4), 1, chunk, _NEG * 4)
    em_p = _pad_to(em_t, 0, chunk, _NEG * 4)
    return _beam_step_pallas(Ap, em_p, scores, states, chunk=chunk,
                             interpret=interpret)


__all__ = ["tropical_matmul", "viterbi_forward", "viterbi_forward_batch",
           "viterbi_forward_batch_masked", "viterbi_chunk_step",
           "viterbi_slot_step", "viterbi_decode_fused",
           "viterbi_decode_fused_batch", "viterbi_decode_fused_masked",
           "viterbi_decode_fused_batch_masked", "viterbi_decode_banded",
           "beam_step"]
