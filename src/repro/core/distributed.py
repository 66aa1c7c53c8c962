"""Distributed FLASH Viterbi — the paper's parallelism mapped onto a TPU mesh.

Two orthogonal axes, composable on the production (data, model) mesh:

  * **Subtask parallelism over the `data` axis** — the paper's P threads.  Each
    wavefront layer's tiles are sharded across the data axis with `shard_map`;
    pruning (Sec. V-B) guarantees tiles are data-independent, so no collective
    is needed *within* a layer — only the pinned boundary states (a few int32s)
    are exchanged between layers.  This is the paper's claim "pruning removes
    inter-subtask dependencies to enable parallel decoding" made literal: the
    compiled HLO for a layer contains zero cross-tile communication.

  * **State parallelism over the `model` axis (tropical tensor parallelism)** —
    beyond the paper's thread model.  The DP step
        delta'[j] = max_k (delta[k] + log_A[k, j]) + em[j]
    is a (max,+) mat-vec: shard log_A by *source rows* across the model axis,
    compute each shard's partial max over its K/mp rows, and combine with an
    all-reduce-MAX (`lax.pmax`) — the exact tropical analogue of megatron-style
    row-parallel matmul + psum.  Backpointers combine with a second pmax over
    (value-matched) global row indices; ties resolve to the largest index
    (single-device argmax resolves to the smallest — path *scores* are
    invariant, asserted in tests).

Per-step collective cost on the model axis: 2 x all-reduce of K floats/ints.

A third axis, **sequence parallelism over `data`** (`make_batched_flash_decoder`),
is the serving configuration: whole sequences shard across devices and decode
through `core.batch.viterbi_decode_batch`, inheriting its ragged-`lengths`
contract (pad frames are tropical-identity steps — no pad mass in scores).

All `shard_map` use goes through `runtime.jaxcompat`, the one call site for
jax's shard_map / mesh API (written for jax 0.9.0).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..runtime.jaxcompat import shard_map
from .hmm import NEG_INF
from .flash import plan_padding, pad_emissions


# ---------------------------------------------------------------------------
# Tropical tensor-parallel DP step (model axis)
# ---------------------------------------------------------------------------

def _tp_dp_step(delta, log_A_local, em_t, is_pad, axis: str):
    """One row-sharded Viterbi step inside shard_map.

    delta: (K,) replicated; log_A_local: (K/mp, K) this shard's source rows;
    returns (delta', psi') both (K,) replicated (combined via pmax).
    """
    K = delta.shape[0]
    kl = log_A_local.shape[0]
    shard = jax.lax.axis_index(axis)
    row0 = shard * kl
    delta_local = jax.lax.dynamic_slice(delta, (row0,), (kl,))

    scores = delta_local[:, None] + log_A_local          # (kl, K)
    part_val = jnp.max(scores, axis=0)                   # (K,)
    part_arg = jnp.argmax(scores, axis=0).astype(jnp.int32) + row0

    vmax = jax.lax.pmax(part_val, axis)                  # all-reduce-MAX
    contrib = jnp.where(part_val >= vmax, part_arg, jnp.int32(-1))
    psi = jax.lax.pmax(contrib, axis)                    # argmax combine

    new = vmax + em_t
    eye = jnp.arange(K, dtype=jnp.int32)
    return jnp.where(is_pad, delta, new), jnp.where(is_pad, eye, psi)


def _tp_dp_step_col(delta, log_A_local, em_local, is_pad, axis: str):
    """Column(target)-sharded DP step — §Perf iteration 2.

    Row-sharding needs two all-reduce-MAX combines per step (values +
    argmax-packing).  Sharding log_A by TARGET columns instead gives each
    shard its own delta'/psi slice computed over ALL sources locally — the
    combine becomes two plain all-gathers of K/mp-slices (half the link bytes
    under ring accounting, and no argmax packing)."""
    K = delta.shape[0]
    kl = log_A_local.shape[1]
    shard = jax.lax.axis_index(axis)

    scores = delta[:, None] + log_A_local               # (K, K/mp)
    part_val = jnp.max(scores, axis=0) + em_local       # (K/mp,)
    part_psi = jnp.argmax(scores, axis=0).astype(jnp.int32)

    new = jax.lax.all_gather(part_val, axis, tiled=True)     # (K,)
    psi = jax.lax.all_gather(part_psi, axis, tiled=True)
    eye = jnp.arange(K, dtype=jnp.int32)
    return jnp.where(is_pad, delta, new), jnp.where(is_pad, eye, psi)


def _tp_initial_pass(log_pi, log_A_local, em, pad, boundaries, axis: str,
                     dp_step=None):
    """TP-sharded version of flash._initial_pass (runs inside shard_map).

    em is (Tp, K) for the row layout or (Tp, K/mp) for the column layout;
    delta/psi/div always track the full K (gathered)."""
    dp_step = dp_step or _tp_dp_step
    Tp = em.shape[0]
    K = log_A_local.shape[1] if dp_step is _tp_dp_step else log_A_local.shape[0]
    nb = boundaries.shape[0]
    bnd = boundaries

    if dp_step is _tp_dp_step_col:
        d0_local = jax.lax.dynamic_slice(
            log_pi, (jax.lax.axis_index(axis) * em.shape[1],),
            (em.shape[1],)) + em[0]
        delta0 = jax.lax.all_gather(d0_local, axis, tiled=True)
    else:
        delta0 = log_pi + em[0]
    div0 = jnp.zeros((K, nb), dtype=jnp.int32)

    def step(carry, inp):
        delta, div = carry
        em_t, is_pad, t = inp
        new, psi = dp_step(delta, log_A_local, em_t, is_pad, axis)
        just = (t == bnd + 1)
        div_new = jnp.where(just[None, :], psi[:, None], div[psi, :])
        return (new, div_new), None

    ts = jnp.arange(1, Tp, dtype=jnp.int32)
    (delta_T, div_T), _ = jax.lax.scan(step, (delta0, div0), (em[1:], pad[1:], ts))
    q_last = jnp.argmax(delta_T).astype(jnp.int32)
    return div_T[q_last, :], q_last, delta_T[q_last]


def _tp_segment_decode(log_pi, log_A_local, em_seg, pad_seg, entry, exit_state,
                       is_first, axis: str, dp_step=None):
    """TP-sharded version of flash._segment_decode (inside shard_map; vmapped
    over the shard's local tiles — the collectives vectorise across tiles)."""
    dp_step = dp_step or _tp_dp_step
    s = em_seg.shape[0]
    shard = jax.lax.axis_index(axis)

    if dp_step is _tp_dp_step_col:
        K = log_A_local.shape[0]
        tm = s // 2 - 1
        # pruned re-init: every shard owns the full `entry` row's local columns
        row_local = log_A_local[entry]                         # (K/mp,)
        pi_local = jax.lax.dynamic_slice(
            log_pi, (shard * em_seg.shape[1],), (em_seg.shape[1],))
        d0_local = jnp.where(is_first, pi_local, row_local) + em_seg[0]
        delta0 = jax.lax.all_gather(d0_local, axis, tiled=True)
    else:
        K = log_A_local.shape[1]
        tm = s // 2 - 1
        kl = log_A_local.shape[0]
        row0 = shard * kl
        # pruned re-init needs row log_A[entry]: only one shard owns it -> pmax
        local_has = (entry >= row0) & (entry < row0 + kl)
        local_row = log_A_local[jnp.clip(entry - row0, 0, kl - 1)]
        # flashlint: disable=FL007(pmax reduction identity for the non-owning shards, not an allowed-set mask)
        row = jax.lax.pmax(jnp.where(local_has, local_row, NEG_INF * 2), axis)
        delta0 = jnp.where(is_first, log_pi + em_seg[0], row + em_seg[0])
    mid0 = jnp.zeros((K,), dtype=jnp.int32)

    def step(carry, inp):
        delta, mid = carry
        em_t, is_pad, tl = inp
        new, psi = dp_step(delta, log_A_local, em_t, is_pad, axis)
        mid_new = jnp.where(tl == tm + 1, psi, mid[psi])
        return (new, mid_new), None

    tls = jnp.arange(1, s, dtype=jnp.int32)
    (_, mid_T), _ = jax.lax.scan(step, (delta0, mid0), (em_seg[1:], pad_seg[1:], tls))
    return mid_T[exit_state]


# ---------------------------------------------------------------------------
# 2-D sharded FLASH decoder
# ---------------------------------------------------------------------------

def make_flash_viterbi_2d(mesh: Mesh, T: int, K: int, parallelism: int | None = None,
                          data_axis: str = "data", model_axis: str = "model",
                          shard: str = "row"):
    """Build a jitted 2-D-parallel FLASH decoder for fixed (T, K).

    Layer tiles shard over `data_axis` (the paper's P := data-axis size);
    each DP step shards log_A over `model_axis`: shard="row" (sources,
    all-reduce-MAX combines — the baseline) or shard="col" (targets, plain
    all-gathers + local psi — §Perf iteration 2, ~2x fewer link bytes).
    Returns decode(log_pi, log_A, em) -> (path (T,), score).
    """
    dp = mesh.shape[data_axis]
    mp = mesh.shape[model_axis]
    P_par = parallelism or dp
    assert K % mp == 0, f"K={K} must divide model axis {mp}"
    Tp, L = plan_padding(T, P_par)
    dp_step = _tp_dp_step_col if shard == "col" else _tp_dp_step
    a_spec = P(None, model_axis) if shard == "col" else P(model_axis, None)
    em_spec = P(None, model_axis) if shard == "col" else P()
    em_tile_spec = (P(data_axis, None, model_axis) if shard == "col"
                    else P(data_axis, None, None))
    em_tile_repl = (P(None, None, model_axis) if shard == "col"
                    else P(None, None, None))

    seg0 = Tp // P_par
    boundaries = (np.arange(1, P_par) * seg0 - 1).astype(np.int32)

    def _initial(log_pi, log_A_local, em, pad):
        return _tp_initial_pass(log_pi, log_A_local, em, pad,
                                jnp.asarray(boundaries), model_axis,
                                dp_step=dp_step)

    initial_sharded = shard_map(
        _initial, mesh=mesh,
        in_specs=(P(), a_spec, em_spec, P()),
        out_specs=(P(), P(), P()),
        check_replication=False)

    def _layer(log_pi, log_A_local, em_tiles, pad_tiles, entries, exits, firsts):
        fn = partial(_tp_segment_decode, axis=model_axis, dp_step=dp_step)
        return jax.vmap(
            lambda e, pd, en, ex, fi: fn(log_pi, log_A_local, e, pd, en, ex, fi)
        )(em_tiles, pad_tiles, entries, exits, firsts)

    def decode(log_pi, log_A, em):
        em_p, pad = pad_emissions(em, Tp)
        q_bounds, q_last, score = initial_sharded(log_pi, log_A, em_p, pad)

        q_star = jnp.zeros((Tp,), dtype=jnp.int32)
        q_star = q_star.at[Tp - 1].set(q_last)
        if P_par > 1:
            q_star = q_star.at[jnp.asarray(boundaries)].set(q_bounds)

        s = seg0
        while s >= 2:
            n = Tp // s
            starts = np.arange(n, dtype=np.int64) * s
            em_tiles = em_p.reshape(n, s, K)
            pad_tiles = pad.reshape(n, s)
            entries = q_star[jnp.asarray(np.maximum(starts - 1, 0))]
            exits = q_star[jnp.asarray(starts + s - 1)]
            firsts = jnp.asarray(starts == 0)

            if n % dp == 0:  # shard tiles over the data axis
                layer_sharded = shard_map(
                    _layer, mesh=mesh,
                    in_specs=(P(), a_spec,
                              em_tile_spec, P(data_axis, None),
                              P(data_axis), P(data_axis), P(data_axis)),
                    out_specs=P(data_axis),
                    check_replication=False)
            else:  # thin layers stay replicated over data (still TP over model)
                layer_sharded = shard_map(
                    _layer, mesh=mesh,
                    in_specs=(P(), a_spec,
                              em_tile_repl, P(None, None),
                              P(None), P(None), P(None)),
                    out_specs=P(None),
                    check_replication=False)
            mids = layer_sharded(log_pi, log_A, em_tiles, pad_tiles,
                                 entries, exits, firsts)
            q_star = q_star.at[jnp.asarray(starts + s // 2 - 1)].set(mids)
            s //= 2
        return q_star[:T], score

    repl = NamedSharding(mesh, P())
    return jax.jit(decode, in_shardings=(repl, repl, repl),
                   out_shardings=(repl, repl))


BATCHED_DECODER_METHODS = ("vanilla", "flash", "flash_bs", "fused")


def make_batched_flash_decoder(mesh: Mesh, data_axis: str = "data",
                               method: str = "flash", *,
                               spec=None,
                               parallelism: int = 8, lanes: int | None = None,
                               beam_width: int = 128, chunk: int = 128,
                               bt: int = 8):
    """Batch-of-sequences serving decoder: sequences shard over `data_axis`.

    Built on `core.batch.viterbi_decode_batch` (the single entry point every
    serving path goes through), so it inherits the ragged-``lengths``
    contract: pad frames run as tropical-identity steps, scores carry no
    pad-transition mass, and each sequence's result is bit-identical to a
    single-device unbatched decode of its unpadded payload.

    Args:
      mesh: the device mesh; ``mesh.shape[data_axis]`` must divide B.
      spec: a batchable `core.DecodeSpec` — the preferred form; supplies the
        method and all tunables (``method``/``parallelism``/``lanes``/``bt``
        are then ignored).
      method: legacy string form — ``vanilla`` (masked-scan oracle), ``flash``
        (wavefront, fully vectorised per sequence with lanes=None by
        default), ``flash_bs`` (dynamic beam), or ``fused`` (batch-grid
        Pallas kernel).
      parallelism / lanes / beam_width / chunk / bt: forwarded to
        `viterbi_decode_batch` (beam_width/chunk only matter for flash_bs).

    Returns a jitted ``decode(log_pi, log_A, ems (B, T, K), lengths (B,))
    -> (paths (B, T), scores (B,))``.
    """
    from .batch import viterbi_decode_batch
    if spec is not None:
        if spec.batch_method is None:
            raise ValueError(f"{type(spec).__name__} has no batched path; "
                             f"choose a spec whose method is in "
                             f"{BATCHED_DECODER_METHODS}")
        method = spec.batch_method
        tunables = spec.batch_tunables()
    else:
        if method not in BATCHED_DECODER_METHODS:
            raise ValueError(f"unknown method {method!r}; choose from "
                             f"{BATCHED_DECODER_METHODS}")
        tunables = dict(parallelism=parallelism, lanes=lanes,
                        beam_width=beam_width, chunk=chunk, bt=bt)

    def decode(log_pi, log_A, ems, lengths):
        return viterbi_decode_batch(ems, log_pi, log_A, lengths,
                                    method=method, mesh=mesh,
                                    data_axis=data_axis, **tunables)

    repl = NamedSharding(mesh, P())
    return jax.jit(
        decode,
        in_shardings=(repl, repl,
                      NamedSharding(mesh, P(data_axis, None, None)),
                      NamedSharding(mesh, P(data_axis))),
        out_shardings=(NamedSharding(mesh, P(data_axis, None)),
                       NamedSharding(mesh, P(data_axis))))


__all__ = ["make_flash_viterbi_2d", "make_batched_flash_decoder",
           "BATCHED_DECODER_METHODS"]
