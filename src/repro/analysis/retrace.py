"""Recompilation detector for `ViterbiDecoder`'s spec-keyed jit caches.

`core/decoder.py` holds its jit callables in module-level tables keyed by the
spec itself — that is the whole point of specs being frozen and hashable.
Two failure modes silently destroy the design and show up only as latency:

  * a spec field stops participating in equality/hash (or a decoder grows a
    closure over per-instance state again), so two decoders built from equal
    specs stop sharing a compilation;
  * ragged `lengths` leak into a traced shape, so every new length mix inside
    one (B, T, K) bucket triggers a fresh compile.

This module turns both into hard failures.  `RetraceGuard` snapshots
`jit._cache_size()` for the callables behind a set of specs, runs the guarded
block, and raises `RetraceError` if the caches grew more than the declared
`allow_compiles`.  `check_retrace()` is the CLI battery: equal-spec reuse
across decoder instances, ragged-length reuse within a bucket, and a shape
change as the positive control (it *must* compile — a guard that never fires
guards nothing).
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import decoder as _decoder
from repro.core.decoder import ViterbiDecoder
from repro.core.spec import DecodeSpec, FlashSpec, FusedSpec, VanillaSpec

__all__ = ["RetraceError", "RetraceGuard", "check_retrace",
           "check_inflight_retrace", "supported"]


class RetraceError(AssertionError):
    """A jit cache compiled when the contract says it must not have."""


def _cache_size(fn) -> int | None:
    meth = getattr(fn, "_cache_size", None)
    if callable(meth):
        return int(meth())
    return None


def supported() -> bool:
    """Whether this jax exposes `jit._cache_size()` (0.9.0 does)."""
    return _cache_size(_decoder._jit_decode(VanillaSpec())) is not None


class RetraceGuard:
    """Context manager: fail if the jit caches behind `specs` compile.

        with RetraceGuard([spec]):
            decoder_a.decode(em)
            decoder_b.decode(em2)      # equal spec, same shape: no compile

    `allow_compiles` declares an expected number of *new* cache entries
    (e.g. 1 when the guarded block intentionally introduces a new shape
    bucket); anything beyond that raises `RetraceError`.
    """

    def __init__(self, specs, *, allow_compiles: int = 0):
        self.specs = tuple(specs)
        self.allow_compiles = int(allow_compiles)
        self._before: dict[str, int] = {}

    def _sizes(self) -> dict[str, int]:
        sizes: dict[str, int] = {}
        for spec in self.specs:
            if spec.jittable:
                n = _cache_size(_decoder._jit_decode(spec))
                sizes[f"decode[{spec!r}]"] = -1 if n is None else n
            if spec.batch_method is not None:
                n = _cache_size(_decoder._jit_decode_batch(spec))
                sizes[f"decode_batch[{spec!r}]"] = -1 if n is None else n
        return sizes

    def __enter__(self) -> "RetraceGuard":
        self._before = self._sizes()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            return False
        after = self._sizes()
        grown = {k: after[k] - self._before.get(k, 0)
                 for k in after
                 if after[k] >= 0 and after[k] > self._before.get(k, 0)}
        total = sum(grown.values())
        if total > self.allow_compiles:
            detail = ", ".join(f"{k}: +{v}" for k, v in sorted(grown.items()))
            raise RetraceError(
                f"{total} unexpected recompilation(s) "
                f"(allowed {self.allow_compiles}): {detail}")
        return False

    @property
    def compiles(self) -> dict[str, int]:
        """Cache growth observed so far (for the positive-control tests)."""
        return {k: v - self._before.get(k, 0)
                for k, v in self._sizes().items()
                if v >= 0 and v > self._before.get(k, 0)}


def _tiny_hmm(K: int, seed: int):
    rng = np.random.default_rng(seed)
    log_pi = jnp.asarray(rng.standard_normal(K), jnp.float32)
    log_A = jnp.asarray(rng.standard_normal((K, K)), jnp.float32)
    return log_pi, log_A


def check_retrace(specs: tuple[DecodeSpec, ...] = (VanillaSpec(),
                                                   FlashSpec(parallelism=4),
                                                   FusedSpec()),
                  K: int = 12, T: int = 24) -> list[str]:
    """Run the no-retrace battery; returns passed-scenario descriptions.

    Raises `RetraceError` on any unexpected compile.  Returns a single
    "skipped" note if this jax does not expose jit cache sizes.
    """
    if not supported():
        return ["skipped: jax.jit has no _cache_size() on this version"]
    passed: list[str] = []
    rng = np.random.default_rng(0)
    for spec in specs:
        log_pi, log_A = _tiny_hmm(K, seed=1)
        em = jnp.asarray(rng.standard_normal((T, K)), jnp.float32)
        dec = ViterbiDecoder(spec, log_pi, log_A)
        dec.decode(em)                       # warm the (K, T) bucket
        with RetraceGuard([spec]):
            dec.decode(em)                   # same decoder, same shape
            em2 = jnp.asarray(rng.standard_normal((T, K)), jnp.float32)
            dec.decode(em2)                  # same shape, new values
            log_pi2, log_A2 = _tiny_hmm(K, seed=2)
            dec2 = ViterbiDecoder(spec, log_pi2, log_A2)
            dec2.decode(em2)                 # equal spec, new instance + HMM
        passed.append(f"equal-spec no-retrace [{spec.method}]")

        if spec.batch_method is None:
            continue
        B = 3
        ems = jnp.asarray(rng.standard_normal((B, T, K)), jnp.float32)
        dec.decode_batch(ems, lengths=np.asarray([T, T // 3, T // 2]))
        with RetraceGuard([spec]):
            # new ragged mix inside the same (B, T, K) bucket
            dec.decode_batch(ems, lengths=np.asarray([2, T, T - 1]))
            dec2 = ViterbiDecoder(spec, *_tiny_hmm(K, seed=3))
            dec2.decode_batch(ems, lengths=np.asarray([T, 1, 5]))
        passed.append(f"ragged-bucket no-retrace [{spec.method}]")

    # positive control: a genuinely new shape bucket MUST compile, proving
    # the cache-size probe actually observes compilation.
    spec = specs[0]
    log_pi, log_A = _tiny_hmm(K, seed=1)
    dec = ViterbiDecoder(spec, log_pi, log_A)
    em_new = jnp.asarray(rng.standard_normal((T + 7, K)), jnp.float32)
    guard = RetraceGuard([spec], allow_compiles=1)
    with guard:
        dec.decode(em_new)
        if not guard.compiles:
            raise RetraceError(
                "positive control failed: a new (T, K) shape bucket did not "
                "register as a compile — the cache-size probe is broken")
    passed.append("positive control: new shape bucket compiles")
    return passed


def check_inflight_retrace(K: int = 12, block: int = 8,
                           slots: int = 3) -> list[str]:
    """Session churn on a live `InflightScheduler` must never recompile.

    The continuous-batching contract: the slot pool's jitted step has one
    fixed shape `(S, block, K)`, and sessions joining/leaving/forcing a
    flush only ever change array *contents*.  This battery warms a scheduler
    (including a forced flush, so the score-masking path is traced), then
    churns rounds of ragged joins/leaves — exact and bounded-lag mixed —
    under the cache-size probe.  A second scheduler with a different pool
    shape is the positive control.
    """
    if not supported():
        return ["skipped: jax.jit has no _cache_size() on this version"]
    from repro.serving.inflight import InflightScheduler, inflight_jit_fns

    rng = np.random.default_rng(0)
    log_pi, log_A = _tiny_hmm(K, seed=1)
    sched = InflightScheduler(log_pi, log_A, max_slots=slots, block=block)

    def em(T, scale=1.0):
        return (rng.standard_normal((T, K)) * scale).astype(np.float32)

    def churn_round(scale: float, lag: int | None) -> None:
        sids = [sched.submit(max_lag=(lag if i % 2 else None))
                for i in range(slots)]
        for i, sid in enumerate(sids):
            sched.feed(sid, em(2 * block + i, scale=scale))
            sched.pump()
        for sid in sids:
            sched.finish(sid)

    # warm-up: max_lag=1 on near-flat emissions all but guarantees forced
    # flushes, so _mask_slot is traced before the guard window opens
    churn_round(scale=0.01, lag=1)
    fns = inflight_jit_fns()
    if _cache_size(fns["mask_slot"]) == 0:
        raise RetraceError(
            "inflight warm-up never forced a flush; the battery would not "
            "cover the score-masking path")
    before = {k: _cache_size(f) for k, f in fns.items()}
    churn_round(scale=0.01, lag=1)
    churn_round(scale=1.0, lag=block)
    churn_round(scale=1.0, lag=None)
    after = {k: _cache_size(f) for k, f in fns.items()}
    grown = {k: after[k] - before[k] for k in after if after[k] > before[k]}
    if grown:
        detail = ", ".join(f"{k}: +{v}" for k, v in sorted(grown.items()))
        raise RetraceError(
            f"inflight session churn recompiled the slot-pool step: {detail}")
    passed = [f"inflight join/leave churn no-retrace "
              f"(S={slots}, block={block}, K={K})"]

    # positive control: a different pool shape MUST compile
    sched2 = InflightScheduler(log_pi, log_A, max_slots=slots + 1,
                               block=block)
    sid = sched2.submit()
    sched2.feed(sid, em(block + 1))
    sched2.pump()
    sched2.finish(sid)
    if _cache_size(fns["inflight_step"]) <= after["inflight_step"]:
        raise RetraceError(
            "positive control failed: a new (S, block, K) pool shape did "
            "not register as a compile — the cache-size probe is broken")
    passed.append("positive control: new pool shape compiles")
    return passed
