"""Erdős–Rényi HMM, the paper's default synthetic model.

FLASH Viterbi (arXiv:2510.19301), Sec. VII-A: a random transition graph
G(K, p), plus a ring so that every state has an exit, with random weights on
its edges; a Dirichlet initial distribution and emission matrix.  A frozen
copy, so that a change to the program cannot change the model the benchmark
measures it on.  A configuration states every name in `PARAMS`.
"""

import jax
import jax.numpy as jnp

PARAMS = ("num_states", "num_obs", "edge_prob")

# Large finite "minus infinity" for absent transitions (float32-safe).
NEG_INF = -1.0e9


def make(key, *, num_states: int, num_obs: int, edge_prob: float):
    """(log_pi (K,), log_A (K, K), log_B (K, |O|))."""
    k_edges, k_trans, k_pi, k_emit = jax.random.split(key, 4)
    mask = jax.random.bernoulli(k_edges, edge_prob, (num_states, num_states))
    mask = mask | jnp.roll(jnp.eye(num_states, dtype=bool), 1, axis=1)
    raw = jax.random.uniform(k_trans, (num_states, num_states),
                             minval=0.05, maxval=1.0)
    weights = jnp.where(mask, raw, 0.0)
    probs = weights / jnp.sum(weights, axis=1, keepdims=True)
    log_A = jnp.where(mask, jnp.log(jnp.maximum(probs, 1e-30)), NEG_INF)
    pi = jax.random.dirichlet(k_pi, jnp.ones((num_states,)) * 0.8)
    log_pi = jnp.log(jnp.maximum(pi, 1e-30))
    emit = jax.random.dirichlet(k_emit, jnp.ones((num_obs,)) * 0.5,
                                (num_states,))
    return log_pi, log_A, jnp.log(jnp.maximum(emit, 1e-30))
