"""Left-to-right (Bakis) HMM, the shape of the paper's forced alignment.

FLASH Viterbi (arXiv:2510.19301), Sec. VII-A aligns speech to a chain of
K phone states: each state stays, with probability about `self_loop`, or
advances by 1 to `max_skip` states; the path starts in state 0.  Weights
carry +-20% noise; the emission matrix is Dirichlet.  A frozen copy; a
configuration states every name in `PARAMS`.
"""

import jax
import jax.numpy as jnp

PARAMS = ("num_states", "num_obs", "self_loop", "max_skip")

# Large finite "minus infinity" for absent transitions (float32-safe).
NEG_INF = -1.0e9


def make(key, *, num_states: int, num_obs: int, self_loop: float,
         max_skip: int):
    """(log_pi (K,), log_A (K, K), log_B (K, |O|))."""
    k_emit, k_noise = jax.random.split(key)
    idx = jnp.arange(num_states)
    delta = idx[None, :] - idx[:, None]
    allowed = (delta >= 0) & (delta <= max_skip)
    base = jnp.where(delta == 0, self_loop, (1.0 - self_loop) / max_skip)
    noise = jax.random.uniform(k_noise, (num_states, num_states),
                               minval=0.8, maxval=1.2)
    weights = jnp.where(allowed, base * noise, 0.0)
    probs = weights / jnp.maximum(jnp.sum(weights, axis=1, keepdims=True),
                                  1e-30)
    log_A = jnp.where(allowed, jnp.log(jnp.maximum(probs, 1e-30)), NEG_INF)
    log_pi = jnp.full((num_states,), NEG_INF).at[0].set(0.0)
    emit = jax.random.dirichlet(k_emit, jnp.ones((num_obs,)) * 0.5,
                                (num_states,))
    return log_pi, log_A, jnp.log(jnp.maximum(emit, 1e-30))
