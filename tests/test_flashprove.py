"""Tests for the flashprove tier (`repro.analysis` tier 2): the planner-model
vs jaxpr-liveness property over every registered spec, injected-defect
negatives (an f64 promotion, an oversized Pallas tile config), the collective
walk's positive control, and the waiver grammar."""

from __future__ import annotations

import sys
import types

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.collective_check import (check_collectives,
                                             collectives_in)
from repro.analysis.findings import (Finding, ProveReport, apply_waivers,
                                     collect_waivers)
from repro.analysis.jaxpr_check import (analyze_jaxpr, batch_entry_jaxpr,
                                        dp_state_bytes, entry_jaxpr,
                                        jaxpr_flops, jaxpr_peak_temp_bytes)
from repro.analysis.pallas_check import (DEFAULT_VMEM_BUDGET, BlockInfo,
                                         _alignment_findings, _check_entry,
                                         harvest_pallas_calls)
from repro.core.planner import crosscheck_state_bytes
from repro.core.spec import SPEC_BY_METHOD

# small grid: the property is checked exhaustively (deep grids, K=128 Pallas
# points) by `make analysis-deep`; tier-1 keeps the trace cost bounded.
GRID = ((16, 32), (24, 64))
BATCH_GRID = ((16, 32, 3),)


# ---------------------------------------------------------------------------
# The PV104 property: planner model upper-bounds IR-derived DP state
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", sorted(SPEC_BY_METHOD))
def test_model_upper_bounds_ir_state(method):
    spec = SPEC_BY_METHOD[method]()
    for K, T in GRID:
        # zero is legitimate for a streaming surrogate whose only stateful
        # output is the jaxpr boundary itself (e.g. `online`'s chunk step).
        ir = jaxpr_peak_temp_bytes(spec, K, T)
        msg = crosscheck_state_bytes(spec, K, T, ir)
        assert msg is None, msg


@pytest.mark.parametrize("method", sorted(
    m for m, cls in SPEC_BY_METHOD.items() if cls.batch_method is not None))
def test_model_upper_bounds_ir_state_batched(method):
    spec = SPEC_BY_METHOD[method]()
    for K, T, B in BATCH_GRID:
        ir = dp_state_bytes(batch_entry_jaxpr(spec, K, T, B))
        msg = crosscheck_state_bytes(spec, K, T, ir, batch=B)
        assert msg is None, msg


def test_ir_flops_scale_with_sequence_length():
    spec = SPEC_BY_METHOD["vanilla"]()
    f1, f2 = jaxpr_flops(spec, 16, 32), jaxpr_flops(spec, 16, 128)
    assert 0 < f1 < f2


def test_crosscheck_rejects_an_ir_blowup():
    # a decoder whose IR retains far more than the model says is a finding,
    # not a tolerance: the message names the method and both sides.
    spec = SPEC_BY_METHOD["vanilla"]()
    msg = crosscheck_state_bytes(spec, 16, 32, ir_bytes=1 << 30)
    assert msg is not None and "vanilla" in msg


# ---------------------------------------------------------------------------
# Injected defects the jaxpr pass must flag
# ---------------------------------------------------------------------------

def test_injected_f64_promotion_is_flagged():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2.0)(jnp.ones((8,), jnp.float32))
        _, findings = analyze_jaxpr(closed, "jaxpr:injected", 1 << 20)
    assert "PV101" in {f.code for f in findings}


def test_injected_bf16_widening_is_flagged():
    closed = jax.make_jaxpr(
        lambda x: x.astype(jnp.float32) + 1.0)(jnp.ones((8,), jnp.bfloat16))
    _, findings = analyze_jaxpr(closed, "jaxpr:injected", 1 << 20)
    assert "PV101" in {f.code for f in findings}


def test_narrowing_is_not_a_widening():
    closed = jax.make_jaxpr(
        lambda x: x.astype(jnp.bfloat16))(jnp.ones((8,), jnp.float32))
    _, findings = analyze_jaxpr(closed, "jaxpr:injected", 1 << 20)
    assert not findings


def test_host_callback_is_flagged():
    def f(x):
        jax.debug.print("x={x}", x=x)
        return x * 2.0

    closed = jax.make_jaxpr(f)(jnp.ones((4,), jnp.float32))
    _, findings = analyze_jaxpr(closed, "jaxpr:injected", 1 << 20)
    assert "PV102" in {f.code for f in findings}


def test_oversized_intermediate_is_flagged():
    closed = jax.make_jaxpr(
        lambda a, b: (a[:, None, :] + b[None, :, :]).sum()
    )(jnp.ones((256, 256), jnp.float32), jnp.ones((256, 256), jnp.float32))
    # (256, 256, 256) f32 broadcast = 64 MiB, far above a 1 KiB model.
    _, findings = analyze_jaxpr(closed, "jaxpr:injected", 1024)
    assert "PV103" in {f.code for f in findings}


# ---------------------------------------------------------------------------
# Pallas pass: tile alignment + the oversized-config rejection
# ---------------------------------------------------------------------------

def test_oversized_tile_config_is_rejected():
    # the raw kernel bypasses `ops._kernel_fits`' runtime fallback, so the
    # static pass is the only guard: K=2048 makes the resident transition
    # block (K, K) f32 = 16 MiB > the 12 MiB budget.
    from repro.kernels import viterbi_dp

    K, bt, B = 2048, 8, 2
    A = jnp.zeros((K, K), jnp.float32)
    em = jnp.zeros((B, 4 * bt, K), jnp.float32)
    d0 = jnp.zeros((B, K), jnp.float32)
    report = ProveReport()
    _check_entry(
        "pallas:test.oversized",
        lambda: viterbi_dp.viterbi_forward_batch(A, em, d0, bt=bt,
                                                 interpret=True),
        DEFAULT_VMEM_BUDGET, report)
    assert "PV202" in {f.code for f in report.findings}


def test_harvest_reads_declared_blocks_back():
    from repro.kernels import viterbi_dp

    K, bt, B = 128, 8, 2
    A = jnp.zeros((K, K), jnp.float32)
    em = jnp.zeros((B, 4 * bt, K), jnp.float32)
    d0 = jnp.zeros((B, K), jnp.float32)
    closed = jax.make_jaxpr(
        lambda: viterbi_dp.viterbi_forward_batch(A, em, d0, bt=bt,
                                                 interpret=True))()
    (summary,) = harvest_pallas_calls(closed)
    assert summary.grid
    shapes = {b.block_shape for b in summary.blocks}
    assert (K, K) in shapes              # resident transition block
    assert summary.vmem_bytes <= DEFAULT_VMEM_BUDGET


def test_alignment_rule_and_its_exemptions():
    def block(bs, arr):
        return BlockInfo(label="in[0]", block_shape=bs, array_shape=arr,
                         dtype="float32", streamed=False)

    # off-grid lane dim that is not the full axis -> PV201
    assert [f.code for f in _alignment_findings(
        "pallas:t", block((8, 72), (64, 1024)))] == ["PV201"]
    # full-axis lane dim is the data's own shape, not the blocking's
    assert _alignment_findings("pallas:t", block((8, 72), (64, 72))) == []
    # sublane 1 is the squeeze/batch-axis idiom
    assert _alignment_findings("pallas:t", block((1, 128), (64, 1024))) == []
    # aligned tiles are silent
    assert _alignment_findings("pallas:t", block((8, 128), (64, 1024))) == []


# ---------------------------------------------------------------------------
# Collective walk: negative on the tree, positive control for the detector
# ---------------------------------------------------------------------------

def test_sharded_decode_has_no_collectives():
    report = check_collectives(quick=True)
    assert report.ok, [str(f) for f in report.findings]
    assert report.checks


def test_collective_detector_positive_control():
    # psum binds the same equation on a 1-device axis, so the detector must
    # see a deliberately-inserted collective even on the CPU lint host.
    from jax.sharding import PartitionSpec as P

    from repro.runtime.jaxcompat import make_mesh, shard_map

    mesh = make_mesh((1,), ("data",))
    f = shard_map(lambda v: jax.lax.psum(v, "data"), mesh=mesh,
                  in_specs=P("data"), out_specs=P())
    closed = jax.make_jaxpr(f)(jnp.ones((4,), jnp.float32))
    assert any(name.startswith("psum") for name in collectives_in(closed))


# ---------------------------------------------------------------------------
# Waiver grammar
# ---------------------------------------------------------------------------

def test_waiver_prefix_matching_and_unused_detection():
    f = Finding("PV103", "jaxpr:flash:batch[K=16,T=32,B=3]", "big broadcast")
    active, waived = apply_waivers([f], {"PV103:jaxpr:flash": "modeled cost"})
    assert active == [] and waived == [(f, "modeled cost")]

    # wrong code does not match; the unused waiver itself becomes PV000
    active, waived = apply_waivers([f], {"PV101:jaxpr:flash": "nope"})
    assert [g.code for g in active] == ["PV103", "PV000"] and not waived

    # narrowed runs must not flag deep-only waivers
    active, _ = apply_waivers([f], {"PV101:jaxpr:flash": "nope"},
                              require_used=False)
    assert [g.code for g in active] == ["PV103"]


def test_malformed_waivers_are_pv000():
    mod = types.ModuleType("fake_waiver_mod")
    mod.FLASHPROVE_WAIVERS = {
        "PV999:x": "unknown code",
        "PV103:y": "   ",          # empty reason
        "PV000:z": "cannot waive the waiver rule",
    }
    sys.modules["fake_waiver_mod"] = mod
    try:
        waivers, malformed = collect_waivers(("fake_waiver_mod",))
    finally:
        del sys.modules["fake_waiver_mod"]
    assert waivers == {}
    assert [m.code for m in malformed] == ["PV000"] * 3


def test_tree_waivers_are_well_formed():
    # every in-code triage declaration parses; zero malformed at merge
    waivers, malformed = collect_waivers()
    assert malformed == []
    assert waivers, "the triaged findings declare their waivers in-code"


def test_entry_jaxpr_covers_streaming_specs():
    # the streaming specs trace their chunk-advance surrogates — the pass
    # never silently skips a registered method.
    for method in ("online", "online_beam"):
        closed = entry_jaxpr(SPEC_BY_METHOD[method](), 16, 64)
        assert closed.jaxpr.eqns
