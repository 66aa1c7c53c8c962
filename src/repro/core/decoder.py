"""`ViterbiDecoder` — one resource-adaptive decoder object for every call site.

Binds a typed `DecodeSpec` to an HMM (log_pi, log_A) and exposes the three
execution shapes the system serves through, uniformly:

    dec = ViterbiDecoder(FlashSpec(parallelism=8), log_pi, log_A)
    path,  score  = dec.decode(em)                      # one (T, K) sequence
    paths, scores = dec.decode_batch(ems, lengths=ln)   # ragged (B, T, K)
    paths, scores = dec.decode_sharded(ems, lengths=ln, mesh=mesh)

A `DataPlacement` pairs a batchable spec with the data mesh it is served on;
it passes wherever a spec does (`to_spec`), and `make_alignment_head` takes
its mesh, so one object names both what to run and where.

Compilation is cached per (spec, shape-bucket) in *module-level* jit tables
keyed by the spec itself (specs are frozen and hashable precisely so they can
be cache keys): two `ViterbiDecoder`s built from equal specs — e.g. one per
serving head — share a single compilation, with the HMM tensors passed as
traced arguments.  jit's own cache then keys on shapes, one compile per
length bucket; `analysis/retrace.py` fails CI if an equal spec or a ragged
batch within one bucket ever retraces.  The sharded path reuses
`core.batch`'s per-(mesh, method, tunables) compiled-decoder cache, which
puts a host batch straight into the mesh, each device sent its own rows.  The
streaming specs (`OnlineSpec`/`OnlineBeamSpec`) are stateful Python loops, so
they run eagerly and reject the batched entry points.

Results are bit-identical to the legacy `viterbi_decode(method=..., **kw)`
shim built from the same tunables — both run the same `spec.run`;
`tests/test_api.py` pins this for every method.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..runtime.jaxcompat import is_traced
from .spec import DecodeSpec, as_decode_spec

__all__ = ["ViterbiDecoder", "DataPlacement"]


def _run_spec(spec: DecodeSpec, log_pi, log_A, em):
    return spec.run(log_pi, log_A, em)


def _run_spec_batch(spec: DecodeSpec, em, log_pi, log_A, lengths):
    from .batch import viterbi_decode_batch
    return viterbi_decode_batch(em, log_pi, log_A, lengths,
                                method=spec.batch_method,
                                constraint=spec.constraint,
                                **spec.batch_tunables())


@functools.lru_cache(maxsize=None)
def _jit_decode(spec: DecodeSpec):
    """Shared single-sequence jit entry for `spec` (spec is the cache key)."""
    return jax.jit(functools.partial(_run_spec, spec))


@functools.lru_cache(maxsize=None)
def _jit_decode_batch(spec: DecodeSpec):
    """Shared ragged-batch jit entry for `spec`."""
    return jax.jit(functools.partial(_run_spec_batch, spec))


class ViterbiDecoder:
    """A `DecodeSpec` bound to one HMM, with jit-compile caching."""

    def __init__(self, spec: DecodeSpec, log_pi, log_A):
        self.spec = as_decode_spec(spec)
        self.log_pi = jnp.asarray(log_pi)
        self.log_A = jnp.asarray(log_A)
        # sharded_batches: `decode_sharded` calls run eagerly (not traced);
        # dummy_rows: length-1 rows added to make them divide the data axis
        self.stats = {"sharded_batches": 0, "dummy_rows": 0}
        self._replicas: dict = {}     # mesh -> (log_pi, log_A) on it

    def __repr__(self):
        return (f"ViterbiDecoder({self.spec!r}, "
                f"K={int(self.log_A.shape[0])})")

    # -- single sequence ----------------------------------------------------
    def decode(self, emissions) -> tuple[jax.Array, jax.Array]:
        """Decode one (T, K) sequence -> (path (T,) int32, score)."""
        em = jnp.asarray(emissions)
        if self.spec.jittable:
            return _jit_decode(self.spec)(self.log_pi, self.log_A, em)
        return self.spec.run(self.log_pi, self.log_A, em)

    # -- ragged batch -------------------------------------------------------
    def _require_batchable(self, entry: str) -> str:
        if self.spec.batch_method is None:
            raise ValueError(
                f"{type(self.spec).__name__} has no batched path; {entry} "
                f"needs a spec whose method is in core.batch.BATCH_METHODS")
        return self.spec.batch_method

    def _lengths(self, emissions, lengths) -> jax.Array:
        from .batch import _validate_lengths
        B, T = emissions.shape[0], emissions.shape[1]
        if lengths is None:
            return jnp.full((B,), T, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        _validate_lengths(lengths, T)   # eager, before entering jit
        return lengths

    def decode_batch(self, emissions, lengths=None
                     ) -> tuple[jax.Array, jax.Array]:
        """Decode a (B, T, K) batch; `lengths` (B,) makes rows ragged.

        Inherits the `viterbi_decode_batch` contract: pad frames run as
        tropical-identity steps, so `paths[i, :lengths[i]]` is bit-identical
        to `decode(emissions[i, :lengths[i]])` for exact methods.
        """
        self._require_batchable("decode_batch")
        emissions = jnp.asarray(emissions)
        lengths = self._lengths(emissions, lengths)
        return _jit_decode_batch(self.spec)(emissions, self.log_pi,
                                            self.log_A, lengths)

    # -- mesh-sharded batch -------------------------------------------------
    def _replicated(self, mesh: Mesh) -> tuple[jax.Array, jax.Array]:
        """log_pi and log_A on every device of `mesh`, put there once."""
        hmm = self._replicas.get(mesh)
        if hmm is None:
            repl = NamedSharding(mesh, PartitionSpec())
            hmm = (jax.device_put(self.log_pi, repl),
                   jax.device_put(self.log_A, repl))
            if not is_traced(hmm[0]):   # under jit the put is only staged
                self._replicas[mesh] = hmm
        return hmm

    def decode_sharded(self, emissions, lengths=None, *, mesh,
                       data_axis: str = "data"
                       ) -> tuple[jax.Array, jax.Array]:
        """Decode a (B, T, K) batch sharded over `mesh`'s `data_axis`.

        Buckets whose size does not divide the axis are padded up with
        length-1 dummy rows and sliced back (sequences are independent, so
        dummies change nothing).  Per-sequence results stay bit-identical to
        `decode_batch` — the shard body is the same per-device decode.
        Dummies are added where the batch lives (numpy for a host batch),
        and the HMM is replicated over the mesh once per decoder.
        """
        method = self._require_batchable("decode_sharded")
        from .batch import viterbi_decode_batch
        xp = np if isinstance(emissions, np.ndarray) else jnp
        emissions = xp.asarray(emissions)
        B, T = emissions.shape[0], emissions.shape[1]
        pad_b = -B % mesh.shape[data_axis]
        if pad_b:
            emissions = xp.concatenate(
                [emissions,
                 xp.zeros((pad_b,) + emissions.shape[1:], emissions.dtype)])
            lx = jnp if isinstance(lengths, jax.Array) else np
            lengths = lx.concatenate(
                [lx.full((B,), T, lx.int32) if lengths is None
                 else lx.asarray(lengths, lx.int32),
                 lx.ones((pad_b,), lx.int32)])
        log_pi, log_A = self._replicated(mesh)
        paths, scores = viterbi_decode_batch(
            emissions, log_pi, log_A, lengths, method=method, mesh=mesh,
            data_axis=data_axis, constraint=self.spec.constraint,
            **self.spec.batch_tunables())
        if not is_traced(paths):
            self.stats["sharded_batches"] += 1
            self.stats["dummy_rows"] += pad_b
        if pad_b:
            paths, scores = paths[:B], scores[:B]
        return paths, scores

    # -- streaming ----------------------------------------------------------
    def make_streaming(self):
        """Stateful incremental decoder for the streaming specs."""
        mk = getattr(self.spec, "make_streaming", None)
        if mk is None:
            raise ValueError(
                f"{type(self.spec).__name__} is not a streaming spec; use "
                f"OnlineSpec / OnlineBeamSpec")
        return mk(self.log_pi, self.log_A)


@dataclasses.dataclass(frozen=True)
class DataPlacement:
    """A batchable `DecodeSpec` served data-parallel over a mesh axis.

    Each device of `mesh`'s `data_axis` holds a replica of the HMM and
    decodes its share of every batch (`ViterbiDecoder.decode_sharded`).
    `to_spec()` makes it pass wherever a spec does (`as_decode_spec`), and
    `serving.alignment.make_alignment_head` shards over its mesh when given
    no ``mesh=`` of its own.
    """
    spec: DecodeSpec
    mesh: Mesh
    data_axis: str = "data"

    def __post_init__(self):
        spec = as_decode_spec(self.spec)
        if spec.batch_method is None:
            raise ValueError(f"{type(spec).__name__} has no batched path; a "
                             f"data placement needs a batchable spec")
        if self.data_axis not in self.mesh.shape:
            raise ValueError(f"mesh {dict(self.mesh.shape)} has no axis "
                             f"{self.data_axis!r}")
        object.__setattr__(self, "spec", spec)

    def to_spec(self) -> DecodeSpec:
        return self.spec

    @classmethod
    def over_devices(cls, spec: DecodeSpec, n: int,
                     data_axis: str = "data") -> "DataPlacement":
        """`spec` on a one-axis `data_axis` mesh of the first `n` local
        devices; refuses more devices than jax sees."""
        from ..runtime.jaxcompat import make_mesh
        devices = jax.local_devices()
        if not 1 <= n <= len(devices):
            raise ValueError(f"a data mesh of {n} devices needs 1 to "
                             f"{len(devices)}; jax sees {len(devices)} "
                             f"({devices[0].platform})")
        return cls(spec, make_mesh((n,), (data_axis,), devices=devices[:n]),
                   data_axis)
