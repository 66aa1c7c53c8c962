"""The typed DecodeSpec / planner / ViterbiDecoder API.

Pins the PR-4 redesign contract:
  * specs validate eagerly and are hashable (jit-cache keys);
  * the planner reproduces the adaptive_edge degradation ladder and never
    picks a larger-footprint plan for a smaller budget;
  * the legacy `viterbi_decode(method=..., **kw)` shim is bit-identical to
    `ViterbiDecoder` built from the equivalent spec — for every method, and
    through the batched/ragged and mesh-sharded entry points;
  * ignored legacy tunables warn instead of being silently dropped.
"""

import dataclasses
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    erdos_renyi_hmm, random_emissions, viterbi_decode, viterbi_decode_batch,
    ViterbiDecoder, DataPlacement, DecodePlan, plan, ResourceBudget,
    decoder_state_bytes, spec_state_bytes, spec_from_tunables,
    SPEC_BY_METHOD, METHODS, BATCH_METHODS,
    VanillaSpec, CheckpointSpec, FlashSpec, FlashBSSpec, BeamStaticSpec,
    BeamStaticMPSpec, AssocSpec, FusedSpec, OnlineSpec, OnlineBeamSpec,
)
from repro.runtime.jaxcompat import make_mesh


@pytest.fixture(scope="module")
def problem():
    key = jax.random.key(42)
    k1, k2 = jax.random.split(key)
    hmm = erdos_renyi_hmm(k1, 48, edge_prob=0.3)
    em = random_emissions(k2, 96, 48)
    return hmm, em


# ---------------------------------------------------------------------------
# Spec construction: validation, hashability, registry
# ---------------------------------------------------------------------------

def test_every_method_has_a_spec():
    assert set(SPEC_BY_METHOD) == set(METHODS)
    for method, cls in SPEC_BY_METHOD.items():
        assert cls.method == method
        assert dataclasses.is_dataclass(cls)


@pytest.mark.parametrize("bad", [
    lambda: FlashSpec(parallelism=0),
    lambda: FlashSpec(parallelism=-2),
    lambda: FlashSpec(lanes=0),
    lambda: FlashBSSpec(beam_width=0),
    lambda: FlashBSSpec(chunk=0),
    lambda: BeamStaticSpec(beam_width=-1),
    lambda: BeamStaticMPSpec(parallelism=0),
    lambda: CheckpointSpec(seg_len=0),
    lambda: FusedSpec(bt=0),
    lambda: OnlineSpec(stream_chunk=0),
    lambda: OnlineBeamSpec(max_lag=0),
    lambda: ResourceBudget(memory_bytes=0),
    lambda: ResourceBudget(latency_hint="speed"),
])
def test_nonsense_rejected_eagerly(bad):
    with pytest.raises(ValueError):
        bad()


def test_unknown_tunables_fail_loudly():
    # the legacy dispatch silently dropped these; the spec cannot express them
    with pytest.raises(TypeError):
        VanillaSpec(beam_width=4)
    with pytest.raises(TypeError):
        FlashSpec(beam_width=4)
    with pytest.raises(TypeError):
        FlashBSSpec(seg_len=3)


def test_specs_hashable_and_frozen():
    a = FlashBSSpec(parallelism=4, beam_width=64)
    b = FlashBSSpec(parallelism=4, beam_width=64)
    c = FlashBSSpec(parallelism=4, beam_width=32)
    assert a == b and hash(a) == hash(b)
    assert a != c
    assert {a: 1, c: 2}[b] == 1          # usable as a cache key
    with pytest.raises(dataclasses.FrozenInstanceError):
        a.beam_width = 16


def test_spec_from_tunables_routes_and_reports_ignored():
    spec, ignored = spec_from_tunables(
        "flash", {"parallelism": 4, "beam_width": 9, "seg_len": 2})
    assert spec == FlashSpec(parallelism=4)
    assert set(ignored) == {"beam_width", "seg_len"}
    with pytest.raises(ValueError):
        spec_from_tunables("nope", {})


# ---------------------------------------------------------------------------
# Legacy shim: deprecation warning on ignored tunables
# ---------------------------------------------------------------------------

def test_legacy_ignored_tunable_warns(problem):
    hmm, em = problem
    with pytest.warns(DeprecationWarning, match="beam_width"):
        viterbi_decode(em, hmm.log_pi, hmm.log_A, method="vanilla",
                       beam_width=8)
    with pytest.warns(DeprecationWarning, match="seg_len"):
        viterbi_decode(em, hmm.log_pi, hmm.log_A, method="flash",
                       parallelism=4, seg_len=10)


def test_legacy_consumed_tunables_do_not_warn(problem):
    hmm, em = problem
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        viterbi_decode(em, hmm.log_pi, hmm.log_A, method="flash_bs",
                       parallelism=4, beam_width=16, chunk=16)


# ---------------------------------------------------------------------------
# Planner: cost model, ladder, monotonicity
# ---------------------------------------------------------------------------

def test_cost_model_matches_spec_view():
    assert (spec_state_bytes(FlashSpec(parallelism=4), 512, 512)
            == decoder_state_bytes("flash", 512, 512, P=4))
    assert (spec_state_bytes(FlashBSSpec(parallelism=2, beam_width=64),
                             512, 512)
            == decoder_state_bytes("flash_bs", 512, 512, P=2, B=64))


def test_benchmarks_reexport_cost_model():
    # benchmarks/examples import the cost model FROM core, never the reverse
    from benchmarks.common import decoder_state_bytes as bench_view
    assert bench_view is decoder_state_bytes


def test_plan_reproduces_adaptive_edge_ladder():
    # the exact decisions the old examples/adaptive_edge.choose_config made
    p64 = plan(512, 512, ResourceBudget(memory_bytes=64 * 1024))
    assert p64.spec == FlashSpec(parallelism=8)
    assert "exact, P=8" in p64.why

    p8 = plan(512, 512, ResourceBudget(memory_bytes=8 * 1024))
    assert p8.spec == FlashSpec(parallelism=1)
    assert "exact, P=1" in p8.why

    # below the exact floor the beam ladder fires, then the floor config
    pbeam = plan(512, 512, 1024)
    assert isinstance(pbeam.spec, FlashBSSpec)
    assert pbeam.state_bytes <= 1024
    pfloor = plan(512, 512, 1)
    assert pfloor.spec == FlashBSSpec(parallelism=1, beam_width=16)
    assert pfloor.why.startswith("floor")
    assert "exceeds budget" in pfloor.why     # the why never claims a false fit


def test_plan_rejects_nonpositive_batch():
    with pytest.raises(ValueError, match="batch"):
        plan(512, 512, 1024, batch=0)
    with pytest.raises(ValueError, match="batch"):
        plan(512, 512, 1024, batch=-3)


def test_plan_respects_budget_cost_model():
    for kb in (512, 64, 8, 2, 1):
        budget = kb * 1024
        p = plan(512, 512, budget)
        assert isinstance(p, DecodePlan)
        assert p.state_bytes == spec_state_bytes(p.spec, 512, 512)
        if not p.why.startswith("floor"):
            assert p.state_bytes <= budget


def test_plan_monotone_in_budget():
    # a smaller budget never yields a larger-footprint plan
    budgets = [2 ** b for b in range(8, 22)]
    footprints = [plan(512, 512, b).state_bytes for b in budgets]
    assert footprints == sorted(footprints)


def test_plan_batch_scales_footprint():
    single = plan(512, 512, 64 * 1024)
    batched = plan(512, 512, 64 * 1024, batch=8)
    assert batched.state_bytes == 8 * spec_state_bytes(batched.spec, 512, 512)
    # the batched plan had to degrade further down the ladder
    assert batched.state_bytes <= 64 * 1024
    assert (spec_state_bytes(batched.spec, 512, 512)
            <= spec_state_bytes(single.spec, 512, 512))
    # planned-for-batch specs must be batch-executable
    assert batched.spec.batch_method in BATCH_METHODS


def test_plan_memory_hint_prefers_smallest_exact():
    p = plan(512, 512, ResourceBudget(memory_bytes=1 << 20,
                                      latency_hint="memory"))
    assert p.spec == FlashSpec(parallelism=1)
    p_lat = plan(512, 512, ResourceBudget(memory_bytes=1 << 20))
    assert p_lat.spec == FlashSpec(parallelism=16)


def test_plan_unlimited_budget_is_latency_optimal():
    assert plan(512, 512).spec == FlashSpec(parallelism=16)


# ---------------------------------------------------------------------------
# Bit-identity: legacy viterbi_decode vs ViterbiDecoder, every method
# ---------------------------------------------------------------------------

# modest tunables so beams/streaming take their real code paths at K=48
_TUNABLES = {
    "vanilla": {}, "checkpoint": {"seg_len": 12},
    "flash": {"parallelism": 4},
    "flash_bs": {"parallelism": 4, "beam_width": 16, "chunk": 16},
    "beam_static": {"beam_width": 16},
    "beam_static_mp": {"beam_width": 16, "parallelism": 4},
    "assoc": {}, "fused": {},
    "online": {"stream_chunk": 32},
    "online_beam": {"beam_width": 16, "chunk": 16, "stream_chunk": 32},
}


@pytest.mark.parametrize("method", METHODS)
def test_decoder_bit_identical_to_legacy(problem, method):
    hmm, em = problem
    kw = _TUNABLES[method]
    p_legacy, s_legacy = viterbi_decode(em, hmm.log_pi, hmm.log_A,
                                        method=method, **kw)
    spec, ignored = spec_from_tunables(method, kw)
    assert not ignored
    dec = ViterbiDecoder(spec, hmm.log_pi, hmm.log_A)
    p_spec, s_spec = dec.decode(em)
    assert np.array_equal(np.asarray(p_legacy), np.asarray(p_spec))
    assert np.asarray(s_legacy) == np.asarray(s_spec)   # bit-identical


@pytest.mark.parametrize("method", BATCH_METHODS)
def test_decode_batch_bit_identical_to_legacy_batch(problem, method):
    hmm, em = problem
    T, K = em.shape
    ems = jnp.stack([em, em[::-1], em * 0.5])
    lengths = jnp.asarray([T, T // 2, T // 3], jnp.int32)
    kw = {k: v for k, v in _TUNABLES[method].items()}
    p_legacy, s_legacy = viterbi_decode_batch(ems, hmm.log_pi, hmm.log_A,
                                              lengths, method=method, **kw)
    spec, _ = spec_from_tunables(method, kw)
    dec = ViterbiDecoder(spec, hmm.log_pi, hmm.log_A)
    p_spec, s_spec = dec.decode_batch(ems, lengths)
    assert np.array_equal(np.asarray(p_legacy), np.asarray(p_spec))
    assert np.array_equal(np.asarray(s_legacy), np.asarray(s_spec))


@pytest.mark.parametrize("method", ("vanilla", "flash", "fused"))
def test_decode_sharded_bit_identical(problem, method):
    hmm, em = problem
    T, K = em.shape
    mesh = make_mesh((1,), ("data",))
    ems = jnp.stack([em, em[::-1], em * 0.5])       # B=3: exercises dummy pad
    lengths = jnp.asarray([T, T // 2, T // 3], jnp.int32)
    spec, _ = spec_from_tunables(method, _TUNABLES[method])
    dec = ViterbiDecoder(spec, hmm.log_pi, hmm.log_A)
    p_ref, s_ref = dec.decode_batch(ems, lengths)
    p_sh, s_sh = dec.decode_sharded(ems, lengths, mesh=mesh)
    assert p_sh.shape == p_ref.shape                 # dummies sliced back off
    assert np.array_equal(np.asarray(p_ref), np.asarray(p_sh))
    assert np.array_equal(np.asarray(s_ref), np.asarray(s_sh))


@pytest.mark.parametrize("xp", [np, jnp], ids=["host", "device"])
def test_data_placement_serves_through_decode_sharded(problem, xp):
    """A placement passes as a spec; the head built from it shards over its
    mesh (here one device) and matches `decode_batch` bit for bit, from a
    host batch and from one already on the device."""
    from repro.core import as_decode_spec
    from repro.serving.alignment import make_alignment_head
    hmm, em = problem
    T = em.shape[0]
    placement = DataPlacement.over_devices(FusedSpec(), 1)
    assert as_decode_spec(placement) == FusedSpec()
    head = make_alignment_head(hmm.log_pi, hmm.log_A, placement)
    ems = xp.stack([xp.asarray(em), xp.asarray(em)[::-1]])
    lengths = xp.asarray([T, T // 3], xp.int32)
    p_sh, s_sh = head(ems, lengths)
    p_ref, s_ref = head.decoder.decode_batch(ems, lengths)
    assert p_sh.sharding.mesh == placement.mesh
    assert np.array_equal(np.asarray(p_ref), np.asarray(p_sh))
    assert np.array_equal(np.asarray(s_ref), np.asarray(s_sh))
    assert head.decoder.stats == {"sharded_batches": 1, "dummy_rows": 0}


def test_data_placement_under_jit_counts_no_traces(problem):
    """Jitting the placed head traces `decode_sharded` (the put becomes a
    sharding constraint) and matches the eager call; only concrete calls
    count as batches."""
    from repro.serving.alignment import make_alignment_head
    hmm, em = problem
    T = em.shape[0]
    head = make_alignment_head(hmm.log_pi, hmm.log_A,
                               DataPlacement.over_devices(FusedSpec(), 1))
    ems = jnp.stack([em, em * 0.5])
    lengths = jnp.asarray([T, T // 2], jnp.int32)
    p_jit, s_jit = jax.jit(head)(ems, lengths)
    assert head.decoder.stats["sharded_batches"] == 0
    p_eager, s_eager = head(ems, lengths)
    assert np.array_equal(np.asarray(p_jit), np.asarray(p_eager))
    assert np.array_equal(np.asarray(s_jit), np.asarray(s_eager))
    assert head.decoder.stats["sharded_batches"] == 1


def _head_with_placement_and_mesh():
    from repro.serving.alignment import make_alignment_head
    K = 128
    make_alignment_head(jnp.zeros((K,)), jnp.zeros((K, K)),
                        DataPlacement.over_devices(FusedSpec(), 1),
                        mesh=make_mesh((1,), ("data",)))


@pytest.mark.parametrize("bad", [
    lambda: DataPlacement.over_devices(OnlineSpec(), 1),
    lambda: DataPlacement.over_devices(FusedSpec(), 0),
    lambda: DataPlacement.over_devices(FusedSpec(), len(jax.devices()) + 1),
    lambda: DataPlacement(FusedSpec(), make_mesh((1,), ("data",)), "model"),
    _head_with_placement_and_mesh,
], ids=["not_batchable", "no_devices", "more_than_jax_sees", "no_such_axis",
        "placement_and_mesh"])
def test_data_placement_refuses(bad):
    with pytest.raises(ValueError):
        bad()


def test_decode_batch_ragged_matches_single(problem):
    hmm, em = problem
    T, K = em.shape
    spec = FlashSpec(parallelism=4)
    dec = ViterbiDecoder(spec, hmm.log_pi, hmm.log_A)
    ems = jnp.stack([em, em])
    lengths = jnp.asarray([T, T // 2], jnp.int32)
    paths, scores = dec.decode_batch(ems, lengths)
    for i, L in enumerate([T, T // 2]):
        p1, s1 = dec.decode(em[:L])
        assert np.array_equal(np.asarray(paths[i, :L]), np.asarray(p1))
        assert np.isclose(float(scores[i]), float(s1), rtol=1e-6)


def test_decode_batch_rejects_unbatchable_spec(problem):
    hmm, em = problem
    dec = ViterbiDecoder(AssocSpec(), hmm.log_pi, hmm.log_A)
    with pytest.raises(ValueError, match="no batched path"):
        dec.decode_batch(jnp.stack([em]))


def test_decode_batch_validates_lengths_eagerly(problem):
    hmm, em = problem
    dec = ViterbiDecoder(VanillaSpec(), hmm.log_pi, hmm.log_A)
    with pytest.raises(ValueError, match="lengths"):
        dec.decode_batch(jnp.stack([em]), lengths=jnp.asarray([0]))


def test_streaming_spec_roundtrip(problem):
    hmm, em = problem
    dec = ViterbiDecoder(OnlineSpec(), hmm.log_pi, hmm.log_A)
    sdec = dec.make_streaming()
    sdec.feed(np.asarray(em))
    sdec.flush()
    p_ref, _ = viterbi_decode(em, hmm.log_pi, hmm.log_A, method="vanilla")
    assert np.array_equal(np.asarray(sdec.path), np.asarray(p_ref))
    with pytest.raises(ValueError, match="not a streaming spec"):
        ViterbiDecoder(VanillaSpec(), hmm.log_pi, hmm.log_A).make_streaming()
