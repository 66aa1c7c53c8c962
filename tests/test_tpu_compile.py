"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with jax and compiles for a chip that is only
described, not attached: these tests catch what interpret mode cannot (block
shapes Mosaic refuses, unsupported ops in a kernel body) at real widths,
without a chip.  Nothing runs, so they say nothing about results or times.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and a worker that could not would then
collect a different set of tests.  Kept in one file for the same reason.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache, so keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(chip, fn, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("K,B,T", [(512, 8, 512), (1152, 1, 256)])
def test_batched_forward_compiles(chip, K, B, T):
    # K=1152 is the largest K `ops._kernel_fits` admits at the default bt
    def fwd(log_A, em, delta0, lengths):
        return ops.viterbi_forward_batch(log_A, em, delta0, lengths,
                                         interpret=False)

    text = _compiled_text(chip, fwd, ((K, K), F32), ((B, T, K), F32),
                          ((B, K), F32), ((B,), I32))
    assert "tpu_custom_call" in text


def test_masked_forward_compiles(chip):
    K, B, T = 512, 4, 256

    def fwd(log_A, em, delta0, lengths, tmask, smask):
        return ops.viterbi_forward_batch_masked(
            log_A, em, delta0, lengths, tmask=tmask, smask=smask,
            interpret=False)

    text = _compiled_text(chip, fwd, ((K, K), F32), ((B, T, K), F32),
                          ((B, K), F32), ((B,), I32), ((K, K), F32),
                          ((T, K), F32))
    assert "tpu_custom_call" in text


def test_slot_step_compiles(chip):
    S, block, K = 64, 16, 512

    def step(log_A, em, delta, nfeed):
        return ops.viterbi_slot_step(log_A, em, delta, nfeed, interpret=False)

    text = _compiled_text(chip, step, ((K, K), F32), ((S, block, K), F32),
                          ((S, K), F32), ((S,), I32))
    assert "tpu_custom_call" in text


def test_fused_batch_decode_compiles(chip):
    K, B, T = 512, 8, 512

    def decode(log_pi, log_A, em, lengths):
        return ops.viterbi_decode_fused_batch(log_pi, log_A, em, lengths,
                                              interpret=False)

    text = _compiled_text(chip, decode, ((K,), F32), ((K, K), F32),
                          ((B, T, K), F32), ((B,), I32))
    assert "tpu_custom_call" in text


def test_sharded_fused_batch_decode_compiles(topo, monkeypatch):
    # the program's sharded decode (`core.batch._sharded_decoder`, the
    # shard_map `ViterbiDecoder.decode_sharded` runs) over the four
    # described chips, 64 rows each; `ops` sees the CPU backend, so the test
    # steers its kernels to compile
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import FusedSpec
    from repro.core.batch import _sharded_decoder
    from repro.runtime.jaxcompat import make_mesh

    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    K, B, T = 512, 256, 512
    mesh = make_mesh((4,), ("data",), devices=topo.devices[:4])
    spec = FusedSpec()
    decode = _sharded_decoder(mesh, "data", spec.batch_method, tuple(sorted(
        dict(spec.batch_tunables(), constraint=None).items())))
    repl, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    args = [jax.ShapeDtypeStruct((K,), F32, sharding=repl),
            jax.ShapeDtypeStruct((K, K), F32, sharding=repl),
            jax.ShapeDtypeStruct((B, T, K), F32, sharding=rows),
            jax.ShapeDtypeStruct((B,), I32, sharding=rows)]
    text = decode.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
