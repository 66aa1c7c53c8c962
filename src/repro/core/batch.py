"""First-class batched decoding — `viterbi_decode_batch` over (B, T, K).

Batch-axis parallelism is where decoding throughput comes from on wide
hardware (cf. the GPU Viterbi literature): one launch amortises the
transition-matrix load and the dispatch overhead over the whole request
bucket.  This module is the single entry point serving goes through.

Ragged batches are the normal case in serving, so `lengths` is part of the
contract rather than an afterthought: sequence i is decoded *exactly* at
length `lengths[i]`, with the tail realised as tropical-identity pad steps
(stay in place, add 0 — the masking machinery shared with `flash.pad_emissions`
/ `flash._dp_step`, which provably leaves deltas, backpointers, and scores
unchanged).  Scores therefore contain no pad-transition mass and per-sequence
results are bit-identical to looped `viterbi_decode` calls for the exact
methods; `tests/test_batch.py` pins this.

Methods:
  * ``fused``    — batch-grid Pallas kernel (`kernels.ops.viterbi_decode_fused_batch`):
                   grid (B, T/bt), log_A resident in VMEM for the whole bucket.
  * ``vanilla``  — vmapped masked lax.scan (exact oracle).
  * ``flash``    — vmapped FLASH wavefront; ragged masks ride the same pad
                   machinery the algorithm already uses for its P·2^L padding.
  * ``flash_bs`` — vmapped FLASH-BS dynamic beam (exact when beam_width >= K).

Path entries at padded steps repeat the sequence's final decoded state
(identity backpointers); slice row i to [:lengths[i]] for the true path.

Multi-device: pass ``mesh=``/``data_axis=`` to shard the request bucket over
a mesh axis (`shard_map`, HMM tensors replicated, zero collectives — the
sequences are independent).  Per-sequence results are bit-identical to the
single-device decode; `tests/test_distributed.py` pins this on 8 virtual
devices.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..runtime.jaxcompat import shard_map
from ..runtime.spans import span
from .vanilla import viterbi_vanilla_masked
from .flash import plan_padding, _flash_padded
from .flash_bs import pad_state_space, _flash_bs_padded

BATCH_METHODS = ("vanilla", "flash", "flash_bs", "fused")


def _pad_mask(T: int, lengths: jax.Array) -> jax.Array:
    return jnp.arange(T)[None, :] >= lengths[:, None]    # (B, T) True == pad


def _validate_lengths(lengths: jax.Array, T: int) -> None:
    """Eagerly reject out-of-range lengths instead of silently clipping.

    Clipping (`jnp.clip(lengths, 1, T)`) used to paper over caller bugs — a
    0 or T+overrun length silently decoded the wrong frame span.  Concrete
    lengths are checked here; traced lengths (inside jit / shard_map) cannot
    be inspected, so out-of-range traced values are a caller contract
    violation with undefined results.
    """
    try:
        # flashlint: disable=FL002(eager pre-jit validation of host-side lengths metadata)
        conc = np.asarray(lengths)
    except (jax.errors.TracerArrayConversionError,
            jax.errors.ConcretizationTypeError):
        return
    if conc.size and (conc.min() < 1 or conc.max() > T):
        raise ValueError(
            f"lengths must lie in [1, T={T}]; got range "
            f"[{int(conc.min())}, {int(conc.max())}]")


def _vanilla_batch(log_pi, log_A, em, pad):
    return jax.vmap(
        lambda e, p: viterbi_vanilla_masked(log_pi, log_A, e, p))(em, pad)


def _flash_batch(log_pi, log_A, em, pad, P: int, lanes):
    B, T, K = em.shape
    Tp, _ = plan_padding(T, P)
    em_p = jnp.pad(em, ((0, 0), (0, Tp - T), (0, 0)))
    pad_p = jnp.pad(pad, ((0, 0), (0, Tp - T)), constant_values=True)
    q, s = jax.vmap(
        lambda e, p: _flash_padded(log_pi, log_A, e, p, P, lanes))(em_p, pad_p)
    return q[:, :T], s


def _flash_bs_batch(log_pi, log_A, em, pad, beam_width: int, P: int, lanes,
                    chunk: int):
    B, T, K = em.shape
    Bw = int(min(beam_width, K))
    chunk = int(min(chunk, K))
    log_pi, log_A, em, _ = pad_state_space(log_pi, log_A, em, chunk)
    Tp, _ = plan_padding(T, P)
    em_p = jnp.pad(em, ((0, 0), (0, Tp - T), (0, 0)))
    pad_p = jnp.pad(pad, ((0, 0), (0, Tp - T)), constant_values=True)
    q, s = jax.vmap(
        lambda e, p: _flash_bs_padded(log_pi, log_A, e, p, P, lanes, Bw,
                                      chunk))(em_p, pad_p)
    return q[:, :T], s


def viterbi_decode_batch(
    emissions: jax.Array,
    log_pi: jax.Array,
    log_A: jax.Array,
    lengths: jax.Array | None = None,
    method: str = "fused",
    *,
    parallelism: int = 8,
    lanes: int | None = -1,
    beam_width: int = 128,
    chunk: int = 128,
    bt: int = 8,
    mesh=None,
    data_axis: str = "data",
    constraint=None,
) -> tuple[jax.Array, jax.Array]:
    """Decode a (possibly ragged) batch of emission sequences.

    Args:
      emissions: (B, T, K) emission log-likelihoods, row i real for the first
        lengths[i] steps (pad frames may hold anything — they are masked).
      log_pi, log_A: shared HMM in log domain.
      lengths: optional (B,) int true lengths; None means every sequence is
        full-length.  Lengths are used *as given* — there is no clipping.
        Every concrete value must lie in [1, T] or a ValueError is raised
        eagerly; traced lengths (inside jit) cannot be checked and
        out-of-range values there are a contract violation with undefined
        results.
      method: one of ``BATCH_METHODS``.  ``vanilla``/``fused`` are exact;
        ``flash`` is exact; ``flash_bs`` is exact when beam_width >= K.
      parallelism, lanes, beam_width, chunk: as in `viterbi_decode`.
      bt: fused-kernel time-block size.
      mesh: optional `jax.sharding.Mesh`; when given, the batch axis is
        sharded over ``data_axis`` with `shard_map` (the axis size must
        divide B) and each device decodes its bucket slice with the exact
        same per-sequence compute — results stay bit-identical to the
        single-device call.  The HMM tensors are replicated.
      data_axis: mesh axis name the batch shards over.
      constraint: optional `core.constraints.ConstraintSpec`, shared by the
        whole bucket (per-step schedules index *absolute* step t, so ragged
        tails just never reach the later rows).  The fused method keeps
        the inputs dense and fuses the penalty adds into the kernel; every
        other method pre-masks the inputs with `constrain_inputs` — both are
        bit-identical to decoding the pre-masked model.  With ``mesh=`` each
        device applies it to its own slice, as the single-device call does.

    Returns:
      (paths (B, T) int32, scores (B,)): paths[i, :lengths[i]] is the decode
      of emissions[i, :lengths[i]] (bit-identical to the unbatched call for
      exact methods); entries past the length repeat the final decoded state.
    """
    if method not in BATCH_METHODS:
        raise ValueError(
            f"unknown batch method {method!r}; choose from {BATCH_METHODS}")
    B, T, K = emissions.shape
    if mesh is not None:
        return _sharded_batch(emissions, log_pi, log_A, lengths, method,
                              mesh=mesh, data_axis=data_axis,
                              constraint=constraint, parallelism=parallelism,
                              lanes=lanes, beam_width=beam_width, chunk=chunk,
                              bt=bt)

    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    _validate_lengths(lengths, T)

    if constraint is not None:
        from .constraints import compiled_penalties, constrain_inputs
        if method == "fused" and T > 1:
            from repro.kernels.ops import viterbi_decode_fused_batch_masked
            t_pen, pi_pen, s_pen = compiled_penalties(constraint, K, T)
            return viterbi_decode_fused_batch_masked(
                log_pi, log_A, emissions, lengths,
                t_pen=t_pen, pi_pen=pi_pen, s_pen=s_pen, bt=bt)
        log_pi, log_A, emissions = constrain_inputs(
            constraint, log_pi, log_A, emissions)

    if T == 1:
        d0 = log_pi[None, :] + emissions[:, 0, :]
        q = jnp.argmax(d0, axis=1).astype(jnp.int32)
        return q[:, None], jnp.max(d0, axis=1)

    if method == "fused":
        from repro.kernels.ops import viterbi_decode_fused_batch
        return viterbi_decode_fused_batch(log_pi, log_A, emissions, lengths,
                                          bt=bt)

    pad = _pad_mask(T, lengths)
    if method == "vanilla":
        return _vanilla_batch(log_pi, log_A, emissions, pad)

    P = int(parallelism)
    if lanes == -1:
        lanes = P
    if method == "flash":
        return _flash_batch(log_pi, log_A, emissions, pad, P, lanes)
    return _flash_bs_batch(log_pi, log_A, emissions, pad, beam_width, P,
                           lanes, chunk)


@lru_cache(maxsize=64)
def _sharded_decoder(mesh, data_axis, method, kw_items):
    """Build (and cache) the jitted shard_map-ed decoder for one config.

    Cached + jitted so repeated eager `mesh=` calls reuse one compiled
    callable — jit's cache keys on callable identity, and a fresh shard_map
    closure per call would retrace (and recompile) every time.
    """
    kw = dict(kw_items)
    Ps = PartitionSpec

    # the device trace names the program after this function
    # (``jit__decode_shard``), and the benchmark finds it by that name
    def _decode_shard(lp, la, em, ln):
        return viterbi_decode_batch(em, lp, la, ln, method=method, **kw)

    return jax.jit(shard_map(
        _decode_shard, mesh=mesh,
        in_specs=(Ps(), Ps(), Ps(data_axis, None, None), Ps(data_axis)),
        out_specs=(Ps(data_axis, None), Ps(data_axis)),
        check_replication=False))


def _sharded_batch(emissions, log_pi, log_A, lengths, method, *, mesh,
                   data_axis, **kw):
    """Shard the request bucket over `data_axis` and decode per device.

    Sequences are independent, so the shard_map body is just the
    single-device `viterbi_decode_batch` on the local (B/dp, T, K) slice —
    no collectives, and per-sequence results are bit-identical to the
    unsharded call (vmap lanes never interact).  log_pi/log_A replicate.

    The batch and its lengths are put straight into the mesh (span
    ``decode.put``), each device sent its own rows: a host batch leaves the
    host once, its lengths checked there first; a device batch is resharded
    on the devices; under jit the put is a sharding constraint.
    """
    dp = mesh.shape[data_axis]
    B, T = emissions.shape[0], emissions.shape[1]
    if B % dp:
        raise ValueError(
            f"mesh axis {data_axis!r}={dp} must divide batch size {B}; pad "
            f"the bucket with length-1 dummies (serving.alignment does this)")
    if lengths is None:
        lengths = np.full((B,), T, np.int32)
    elif isinstance(lengths, jax.Array):
        lengths = jnp.asarray(lengths, jnp.int32)
    else:
        # flashlint: disable=FL002(host lengths stay on the host until the put)
        lengths = np.asarray(lengths, np.int32)
    _validate_lengths(lengths, T)
    with span("decode.put", rows=B):
        emissions = jax.device_put(emissions, NamedSharding(
            mesh, PartitionSpec(data_axis, None, None)))
        lengths = jax.device_put(lengths, NamedSharding(
            mesh, PartitionSpec(data_axis)))
    sharded = _sharded_decoder(mesh, data_axis, method,
                               tuple(sorted(kw.items())))
    return sharded(log_pi, log_A, emissions, lengths)


__all__ = ["viterbi_decode_batch", "BATCH_METHODS"]
