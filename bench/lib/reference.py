"""The plain Viterbi reference the benchmark judges every answer against.

Frozen with the benchmark and independent of the program: it imports
nothing from ``src/`` and takes only the HMM and emissions the benchmark
generated.  Three pieces:

* `viterbi_numpy` / `path_score_numpy`: the textbook decoder and path score,
  for the CPU tests at small sizes.
* `viterbi_reference`: the same recurrence in `jax.numpy`, batched with
  ragged lengths and run in blocks of rows so that it fits on one chip at
  the timed sizes.  ``mode`` picks the precision: ``"f32"`` is the
  reference; ``"bf16"`` computes everything in bfloat16 and is the control
  that a sound comparison has to reject.
* `path_score64`: a path's log-likelihood summed in float64 on the host,
  the yardstick for both the reference's and the program's paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

MODES = ("f32", "bf16")


def viterbi_numpy(log_pi: np.ndarray, log_A: np.ndarray, em: np.ndarray):
    """Vanilla Viterbi. Returns (path (T,), score)."""
    T, K = em.shape
    delta = log_pi + em[0]
    psi = np.zeros((T, K), dtype=np.int64)
    for t in range(1, T):
        scores = delta[:, None] + log_A
        psi[t] = np.argmax(scores, axis=0)
        delta = scores[psi[t], np.arange(K)] + em[t]
    path = np.zeros((T,), dtype=np.int64)
    path[-1] = int(np.argmax(delta))
    for t in range(T - 2, -1, -1):
        path[t] = psi[t + 1][path[t + 1]]
    return path, float(np.max(delta))


def path_score_numpy(log_pi, log_A, em, path) -> float:
    s = log_pi[path[0]] + em[0, path[0]]
    for t in range(1, len(path)):
        s += log_A[path[t - 1], path[t]] + em[t, path[t]]
    return float(s)


@functools.partial(jax.jit, static_argnames=("mode",))
def _viterbi_block(log_pi, log_A, em, lengths, *, mode: str):
    if mode == "bf16":
        dt = jnp.bfloat16
        log_pi, log_A, em = (x.astype(dt) for x in (log_pi, log_A, em))
    B, T, K = em.shape
    ident = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32), (B, K))

    def step(delta, inp):
        em_t, t = inp
        scores = delta[:, :, None] + log_A[None]          # (B, src, dst)
        psi = jnp.argmax(scores, axis=1).astype(jnp.int32)
        new = jnp.max(scores, axis=1) + em_t
        live = (t < lengths)[:, None]
        return jnp.where(live, new, delta), jnp.where(live, psi, ident)

    delta0 = log_pi[None, :] + em[:, 0]
    delta_T, psis = jax.lax.scan(
        step, delta0, (jnp.swapaxes(em[:, 1:], 0, 1), jnp.arange(1, T)))
    q_last = jnp.argmax(delta_T, axis=1).astype(jnp.int32)
    rows = jnp.arange(B)

    def back(q, psi_t):
        prev = psi_t[rows, q]
        return prev, prev

    _, prefix = jax.lax.scan(back, q_last, psis, reverse=True)
    paths = jnp.concatenate([prefix.T, q_last[:, None]], axis=1)
    return paths, delta_T[rows, q_last].astype(jnp.float32)


def block_rows(K: int, budget_bytes: int = 1 << 28) -> int:
    """Rows per reference block so one step's (rows, K, K) scores fit."""
    return max(1, budget_bytes // (K * K * 4))


def viterbi_reference(log_pi, log_A, em: np.ndarray, lengths: np.ndarray,
                      mode: str = "f32"):
    """Decode host rows `em` (N, T, K) at `lengths` on the default device.

    Returns (paths (N, T) int32, scores (N,) float32) as host arrays; entries
    past a row's length repeat its last state.  Every block has the same
    shape (the last one is padded with repeats), so it compiles once.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    N, T, K = em.shape
    rows = min(block_rows(K), N)
    paths = np.zeros((N, T), np.int32)
    scores = np.zeros((N,), np.float32)
    for lo in range(0, N, rows):
        idx = np.minimum(np.arange(lo, lo + rows), N - 1)
        p, s = _viterbi_block(log_pi, log_A, jnp.asarray(em[idx]),
                              jnp.asarray(lengths[idx], jnp.int32), mode=mode)
        n = min(rows, N - lo)
        paths[lo:lo + n] = np.asarray(p)[:n]
        scores[lo:lo + n] = np.asarray(s)[:n]
    return paths, scores


def path_score64(log_pi64: np.ndarray, log_A64: np.ndarray, em: np.ndarray,
                 path: np.ndarray) -> float:
    """Log-likelihood of `path` over the first len(path) rows of `em`,
    summed in float64 (the inputs are the float32 values the program got)."""
    path = np.asarray(path, np.int64)
    L = path.shape[0]
    emit = em[np.arange(L), path].astype(np.float64)
    return float(log_pi64[path[0]] + emit.sum()
                 + log_A64[path[:-1], path[1:]].sum())
