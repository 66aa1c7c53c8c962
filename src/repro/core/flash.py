"""FLASH Viterbi — non-recursive divide-and-conquer decoding (paper Sec. V-A/V-B).

Structure (faithful to Algorithm 1 + the P-way initial-partition optimisation):

  * **Initial pass** over the full (padded) sequence tracks, for every DP state, the
    state its best path visited at each of the P-1 interior *division points*
    (the `MidState`/`DivState` array of the paper, generalised from 1 midpoint to
    P-1 boundaries).  Backtracking pins the optimal states at all boundaries plus
    the final step.  Cost: O(K^2 T) time, O(PK) space.

  * **Layer wavefront**: the paper's task queue admits any intra-layer order, so we
    schedule it as a statically known layer-synchronous wavefront.  Layer ell has
    Tp/s contiguous tiles of length s = seg0 / 2^(ell-1); every tile's entry state
    (q*_{m-1}) and exit state (q*_n) were pinned by strictly earlier layers, which
    is exactly the paper's inter-layer ordering invariant.  Each tile resolves one
    state: its midpoint.

  * **Pruning** (paper Sec. V-B, Theorems 1-3): a tile starting at m != 0 seeds its
    DP from only the pinned entry state with score 0:
        OptProb[i] = log A[q*_{m-1}, i] + log B[i, x_m].
    This removes every cross-tile data dependency, so a whole layer is data-parallel.

  * **Parallelism degree P** maps to TPU lanes: tiles of a layer are processed in
    chunks of `lanes` via `vmap` (sequentially over chunks, matching the paper's
    "P subtasks in flight" queue semantics and its O(PK) space bound).  Setting
    `lanes=None` vectorises the whole layer (TPU throughput mode; documented
    deviation — space grows to O(K * tiles_per_layer)).

Sequences are padded to Tp = P * 2^L with tropical-identity steps (stay in place,
add 0), which provably leave every delta, backpointer, division state and the
decoded prefix unchanged.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np



# ---------------------------------------------------------------------------
# Padding
# ---------------------------------------------------------------------------

def plan_padding(T: int, P: int) -> tuple[int, int]:
    """Return (Tp, L): padded length P * 2^L with seg0 = 2^L >= ceil(T / P)."""
    seg0 = max(1, math.ceil(T / P))
    L = max(0, math.ceil(math.log2(seg0)))
    return P * (1 << L), L


def pad_emissions(em: jax.Array, Tp: int) -> tuple[jax.Array, jax.Array]:
    T = em.shape[0]
    em_p = jnp.pad(em, ((0, Tp - T), (0, 0)))
    pad = jnp.arange(Tp) >= T
    return em_p, pad


# ---------------------------------------------------------------------------
# DP steps
# ---------------------------------------------------------------------------

def _dp_step(log_A, delta, em_t, is_pad):
    """One Viterbi DP step; pad steps are tropical-identity (delta frozen)."""
    K = log_A.shape[0]
    scores = delta[:, None] + log_A                  # (K_src, K_dst)
    psi = jnp.argmax(scores, axis=0).astype(jnp.int32)
    new = jnp.max(scores, axis=0) + em_t
    eye = jnp.arange(K, dtype=jnp.int32)
    return jnp.where(is_pad, delta, new), jnp.where(is_pad, eye, psi)


def _initial_pass(log_pi, log_A, em, pad, boundaries: np.ndarray):
    """Full-sequence DP tracking division states at `boundaries` (static indices).

    Returns (q_bounds (nb,), q_last, score): pinned states at each interior
    boundary, the optimal final state, and the optimal path log-likelihood.
    """
    Tp, K = em.shape
    nb = len(boundaries)
    bnd = jnp.asarray(boundaries, dtype=jnp.int32)

    delta0 = log_pi + em[0]
    div0 = jnp.zeros((K, nb), dtype=jnp.int32)

    def step(carry, inp):
        delta, div = carry
        em_t, is_pad, t = inp
        new, psi = _dp_step(log_A, delta, em_t, is_pad)
        just = (t == bnd + 1)            # (nb,) this step crosses boundary i
        gathered = div[psi, :]           # (K, nb) propagate along best edges
        div_new = jnp.where(just[None, :], psi[:, None], gathered)
        return (new, div_new), None

    ts = jnp.arange(1, Tp, dtype=jnp.int32)
    (delta_T, div_T), _ = jax.lax.scan(step, (delta0, div0), (em[1:], pad[1:], ts))
    q_last = jnp.argmax(delta_T).astype(jnp.int32)
    score = delta_T[q_last]
    q_bounds = div_T[q_last, :]
    return q_bounds, q_last, score


def _segment_decode(log_pi, log_A, em_seg, pad_seg, entry, exit_state, is_first):
    """Pruned subtask DP over one tile (static length s); returns q*_{midpoint}.

    `entry` is the pinned optimal state at m-1 (ignored when is_first), and
    `exit_state` the pinned optimal state at n.  Faithful to Algorithm 2 with the
    Sec. V-B pruned re-initialisation.
    """
    s, K = em_seg.shape
    tm = s // 2 - 1  # local midpoint index

    pruned0 = log_A[entry] + em_seg[0]
    first0 = log_pi + em_seg[0]
    delta0 = jnp.where(is_first, first0, pruned0)
    mid0 = jnp.zeros((K,), dtype=jnp.int32)

    def step(carry, inp):
        delta, mid = carry
        em_t, is_pad, tl = inp
        new, psi = _dp_step(log_A, delta, em_t, is_pad)
        mid_new = jnp.where(tl == tm + 1, psi, mid[psi])
        return (new, mid_new), None

    tls = jnp.arange(1, s, dtype=jnp.int32)
    (_, mid_T), _ = jax.lax.scan(step, (delta0, mid0), (em_seg[1:], pad_seg[1:], tls))
    return mid_T[exit_state]


# ---------------------------------------------------------------------------
# Lane-chunked layer execution (the task queue, statically scheduled)
# ---------------------------------------------------------------------------

def chunked_vmap(fn, args: tuple, lanes: int | None):
    """vmap `fn` over the leading axis, `lanes` tasks at a time.

    `lanes` is the paper's parallelism degree P: at most `lanes` subtasks are in
    flight, bounding live memory at O(lanes * K) while leaving intra-chunk
    execution fully parallel.  `lanes=None` runs the whole layer at once.
    """
    n = args[0].shape[0]
    vf = jax.vmap(fn)
    if lanes is None or n <= lanes:
        return vf(*args)
    nfull = (n // lanes) * lanes
    args_c = tuple(a[:nfull].reshape(n // lanes, lanes, *a.shape[1:])
                   for a in args)
    out = jax.lax.map(lambda xs: vf(*xs), args_c)
    out = out.reshape(nfull, *out.shape[2:])
    if nfull != n:  # remainder chunk: fewer than `lanes` tasks in flight
        out = jnp.concatenate([out, vf(*(a[nfull:] for a in args))], axis=0)
    return out


# ---------------------------------------------------------------------------
# Full decoder
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("P", "lanes"))
def _flash_padded(log_pi, log_A, em, pad, P: int, lanes: int | None):
    Tp, K = em.shape
    seg0 = Tp // P

    boundaries = (np.arange(1, P) * seg0 - 1).astype(np.int64)  # e_i, i < P-1
    with jax.named_scope("flash.initial_pass"):
        q_bounds, q_last, score = _initial_pass(log_pi, log_A, em, pad,
                                                boundaries)

    q_star = jnp.zeros((Tp,), dtype=jnp.int32)
    q_star = q_star.at[Tp - 1].set(q_last)
    if P > 1:
        q_star = q_star.at[jnp.asarray(boundaries)].set(q_bounds)

    s = seg0
    with jax.named_scope("flash.wavefront"):
        while s >= 2:  # L = log2(seg0) layers, statically unrolled
            n = Tp // s
            starts = np.arange(n, dtype=np.int64) * s
            ends = starts + s - 1
            mids = starts + s // 2 - 1
            em_tiles = em.reshape(n, s, K)
            pad_tiles = pad.reshape(n, s)
            entries = q_star[jnp.asarray(np.maximum(starts - 1, 0))]
            exits = q_star[jnp.asarray(ends)]
            is_first = jnp.asarray(starts == 0)

            fn = partial(_segment_decode, log_pi, log_A)
            mid_states = chunked_vmap(
                fn, (em_tiles, pad_tiles, entries, exits, is_first), lanes)
            q_star = q_star.at[jnp.asarray(mids)].set(mid_states)
            s //= 2
    return q_star, score


def flash_viterbi(log_pi, log_A, em, parallelism: int = 8,
                  lanes: int | None = -1):
    """FLASH Viterbi decode.

    Args:
      log_pi, log_A, em: HMM in log domain + (T, K) emissions.
      parallelism: the paper's P — width of the initial partition and the default
        number of subtask lanes in flight.
      lanes: subtasks processed concurrently per layer; -1 means "= parallelism"
        (paper semantics), None means vectorise whole layers (TPU throughput mode).

    Returns:
      (path, score): (T,) int32 optimal path and its log-likelihood.
    """
    T, K = em.shape
    P = int(parallelism)
    if lanes == -1:
        lanes = P
    if T == 1:
        q = jnp.argmax(log_pi + em[0]).astype(jnp.int32)
        return q[None], (log_pi + em[0])[q]
    Tp, _ = plan_padding(T, P)
    em_p, pad = pad_emissions(em, Tp)
    q_star, score = _flash_padded(log_pi, log_A, em_p, pad, P, lanes)
    return q_star[:T], score


#: flashprove waivers (see analysis/findings.py for the grammar).
FLASHPROVE_WAIVERS = {
    "PV103:jaxpr:flash:batch": (
        "the vmapped DP step broadcasts (batch, lanes, K, K) scores for one "
        "time step; it is per-step compute working set XLA fuses into the "
        "argmax/max reduction, never a retained table, and it scales with "
        "the lane count the planner already bounds"),
}

__all__ = [
    "flash_viterbi",
    "plan_padding",
    "pad_emissions",
    "chunked_vmap",
]
