"""The HMM and the request pool, made on the device from the seed.

A configuration names its HMM family (``"hmm"``), found by name as
``bench/hmms/<kind>.py`` (`lib/registry.py`), and states every parameter
that family takes.  `make_inputs` builds the HMM and a pool of emission
sequences in one jitted call: observations are sampled from the HMM itself
and the emissions are ``log_B[:, o_t]``, the paper's own "same
structure/scale" synthesis (FLASH Viterbi, arXiv:2510.19301, Sec. VII-A),
in the configuration's ``dtype``.  A configuration key that neither the
family nor the harness reads is refused.
"""

from __future__ import annotations

import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from . import registry

#: keys of a configuration that the harness reads or that document it
CONFIG_KEYS = ("name", "source", "hmm", "seq_len", "dtype", "guarantee",
               "reference", "assumed", "limits")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that keeps all 64 bits of a seed (key() alone drops some)."""
    seed = int(seed)
    base = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(base, (seed >> 32) & 0xFFFFFFFF)


def sample_observations(key, log_pi, log_A, log_B, length: int):
    """Ancestral sampling of (hidden states, observations)."""
    k0, key = jax.random.split(key)
    s0 = jax.random.categorical(k0, log_pi)

    def step(s, k):
        ka, kb = jax.random.split(k)
        return (jax.random.categorical(ka, log_A[s]),
                (s, jax.random.categorical(kb, log_B[s])))

    _, (states, obs) = jax.lax.scan(step, s0, jax.random.split(key, length))
    return states, obs


def family(config: dict, base: Path = registry.BENCH):
    """(the family's ``make``, its parameters as stated) for a config."""
    gen = registry.load_module("hmms", config["hmm"], base)
    registry.check_keys(f"configuration {config.get('name')!r}", config,
                        CONFIG_KEYS + tuple(gen.PARAMS))
    return gen.make, tuple((k, config[k]) for k in gen.PARAMS)


@functools.partial(jax.jit, static_argnames=("make", "params", "dtype", "n",
                                              "t_max"))
def _make_inputs(key, *, make, params, dtype, n, t_max):
    k_hmm, k_obs = jax.random.split(key)
    log_pi, log_A, log_B = make(k_hmm, **dict(params))
    sample = functools.partial(sample_observations, log_pi=log_pi,
                               log_A=log_A, log_B=log_B, length=t_max)
    _, obs = jax.vmap(lambda k: sample(k))(jax.random.split(k_obs, n))
    em = jnp.take(log_B.T, obs, axis=0)              # (n, t_max, K)
    return log_pi.astype(dtype), log_A.astype(dtype), em.astype(dtype)


def make_inputs(config: dict, seed: int, n: int, t_max: int,
                base: Path = registry.BENCH):
    """(log_pi, log_A) on the device and the (n, t_max, K) emission pool on
    the host, all from `seed`; the same seed gives the same arrays."""
    if t_max > int(config["seq_len"]):
        raise ValueError(f"sequences of {t_max} frames exceed the "
                         f"configuration's seq_len {config['seq_len']}")
    make, params = family(config, base)
    log_pi, log_A, em = _make_inputs(
        seed_key(seed), make=make, params=params, dtype=config["dtype"],
        n=n, t_max=t_max)
    pool = np.asarray(jax.device_get(em))
    del em
    return log_pi, log_A, pool
