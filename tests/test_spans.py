"""Named spans and scopes inside the serving path and the decode programs.

The schedulers open `repro.runtime.spans.span` at each boundary of their
work (one of each per batch or step, tagged with its number); the decode
programs carry `jax.named_scope` phases in their ops' op_name metadata.
Spans record only under a profiler session, so these tests record a CPU
trace and read it back; the scopes are read from the compiled HLO.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import erdos_renyi_hmm, random_emissions
from repro.core.decoder import _jit_decode_batch
from repro.core.spec import FlashSpec, FusedSpec
from repro.runtime.spans import span
from repro.serving.inflight import InflightScheduler
from repro.serving.scheduler import BatchScheduler

BATCH_SPANS = ("repro.batch.pad", "repro.batch.dispatch", "repro.batch.wait",
               "repro.batch.unpad")
STEP_SPANS = ("repro.inflight.stage", "repro.inflight.dispatch",
              "repro.inflight.psi_copy", "repro.inflight.commit")


def _recorded(tmp_path, fn):
    """Run `fn` under a profiler session; its ``repro.*`` host events as
    (name, metadata, start_ns, end_ns), in order of start."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, {k: v for k, v in ev.stats},
                                ev.start_ns, ev.end_ns))
    return sorted(out, key=lambda x: x[2])


@pytest.fixture(scope="module")
def hmm():
    return erdos_renyi_hmm(jax.random.key(3), 16, edge_prob=0.5)


def _ems(hmm, lengths, seed=0):
    keys = jax.random.split(jax.random.key(seed), len(lengths))
    return [np.asarray(random_emissions(k, T, hmm.log_pi.shape[0]))
            for k, T in zip(keys, lengths)]


@jax.jit
def _decode(padded, lens):
    return jnp.argmax(padded, -1).astype(jnp.int32), padded.sum((1, 2))


def test_span_without_a_profiler_records_nothing_and_raises_nothing(
        tmp_path):
    with span("batch.pad", batch=0):
        pass
    events = _recorded(tmp_path, lambda: None)
    assert events == []
    with span("inflight.commit", step=1) as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)


def test_batch_scheduler_opens_four_spans_per_batch_in_order(tmp_path):
    sched = BatchScheduler(_decode, max_batch=2, buckets=(8, 16))
    for T in (5, 7, 12, 3):
        sched.submit(np.ones((T, 4), np.float32))
    _decode(np.zeros((2, 8, 4), np.float32), np.zeros(2, np.int32))
    _decode(np.zeros((2, 16, 4), np.float32), np.zeros(2, np.int32))
    events = _recorded(tmp_path, sched.drain)
    assert sched.stats["batches"] == 3
    for n in range(3):
        names = [e[0] for e in events if e[1].get("batch") == n]
        assert names == list(BATCH_SPANS), (n, names)
    assert len(events) == 4 * 3


def test_lookahead_batch_pads_and_dispatches_inside_its_ahead_span(
        tmp_path):
    # six rows of one bucket, batch 2: batch 1 is dispatched while batch 0
    # is in flight, batch 2 after both are back
    sched = BatchScheduler(_decode, max_batch=2, buckets=(8,))
    for T in (5, 7, 3, 8, 1, 6):
        sched.submit(np.ones((T, 4), np.float32))
    _decode(np.zeros((2, 8, 4), np.float32), np.zeros(2, np.int32))
    events = _recorded(tmp_path, sched.drain)
    assert sched.stats["lookahead"] == 1
    for n in range(3):
        names = [e[0] for e in events
                 if e[1].get("batch") == n and e[0] in BATCH_SPANS]
        assert names == list(BATCH_SPANS), (n, names)
    (ahead,) = [e for e in events if e[0] == "repro.batch.ahead"]
    assert ahead[1]["batch"] == 1
    inside = [e[0] for e in events
              if ahead[2] <= e[2] and e[3] <= ahead[3] and e is not ahead]
    assert inside == ["repro.batch.pad", "repro.batch.dispatch"]
    wait0 = next(e for e in events
                 if e[0] == "repro.batch.wait" and e[1]["batch"] == 0)
    assert ahead[3] <= wait0[2]


def test_inflight_step_spans_once_per_step_and_flush_per_finish(
        tmp_path, hmm):
    ems = _ems(hmm, [40, 23])
    sched = InflightScheduler(hmm.log_pi, hmm.log_A, max_slots=2, block=8)
    warm = sched.submit()                 # compile outside the trace
    sched.feed(warm, ems[0][:9])
    sched.pump()
    sched.finish(warm)
    steps0 = sched.stats["steps"]

    def serve():
        sids = [sched.submit() for _ in ems]
        for sid, em in zip(sids, ems):
            sched.feed(sid, em)
        sched.pump()
        for sid in sids:
            sched.finish(sid)

    events = _recorded(tmp_path, serve)
    steps = range(steps0, sched.stats["steps"])
    assert len(steps) > 2
    for n in steps:
        names = [e[0] for e in events if e[1].get("step") == n]
        assert names == list(STEP_SPANS), (n, names)
    flushes = [e for e in events if e[0] == "repro.inflight.flush"]
    assert len(flushes) == 2
    assert len(events) == len(STEP_SPANS) * len(steps) + 2


def test_finish_steps_counts_exactly_the_steps_finish_runs(hmm):
    em = _ems(hmm, [45])[0]
    sched = InflightScheduler(hmm.log_pi, hmm.log_A, max_slots=2, block=8)
    sid = sched.submit()
    sched.feed(sid, em[:33])
    assert sched.pump() > 0               # pump's steps are not counted
    assert sched.stats["finish_steps"] == 0
    sched.feed(sid, em[33:])
    before = sched.stats["steps"]
    sched.finish(sid)
    ran = sched.stats["steps"] - before
    assert ran > 0
    assert sched.stats["finish_steps"] == ran
    sched.finish(sid)                     # idempotent: no further steps
    assert sched.stats["finish_steps"] == ran


def test_batch_scheduler_counts_frames_and_pad_frames():
    def fake_decode(batch, lengths):
        B, T, _ = batch.shape
        return np.zeros((B, T), np.int32), np.zeros(B, np.float32)

    sched = BatchScheduler(fake_decode, max_batch=4, buckets=(16, 32))
    assert sched.pad_frac() == 0.0
    for T in (10, 16, 20):
        sched.submit(np.zeros((T, 3), np.float32))
    sched.drain()
    # bucket 16 holds 10 + 16 of 32 frames; bucket 32 holds 20 of 32
    assert sched.stats["frames"] == 46
    assert sched.stats["padded_frames"] == 64 - 46
    assert sched.pad_frac() == pytest.approx(18 / 64)


def _compiled(spec, K=16, B=2, T=32):
    em = jnp.zeros((B, T, K))
    return _jit_decode_batch(spec).lower(
        em, jnp.zeros(K), jnp.zeros((K, K)),
        jnp.full((B,), T, jnp.int32)).compile().as_text()


@pytest.mark.parametrize("spec, scopes", [
    (FusedSpec(), ("viterbi.backtrack",)),
    (FlashSpec(parallelism=4), ("flash.initial_pass", "flash.wavefront")),
], ids=["fused", "flash"])
def test_batch_decode_ops_carry_the_named_scopes(spec, scopes):
    text = _compiled(spec)
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for scope in scopes:
        under = [n for n in op_names if f"/{scope}/" in n]
        assert any("/while" in n for n in under), (scope, under[:5])


def test_single_sequence_fused_decode_scopes_its_backtrack():
    from repro.kernels.ops import viterbi_decode_fused
    K = 16
    text = jax.jit(viterbi_decode_fused).lower(
        jnp.zeros(K), jnp.zeros((K, K)), jnp.zeros((20, K))).compile(
        ).as_text()
    assert re.search(r'op_name="[^"]*/viterbi\.backtrack/while', text)


@pytest.mark.parametrize("spec", [FusedSpec(), FlashSpec(parallelism=4)],
                         ids=["fused", "flash"])
def test_offline_decode_program_keeps_its_name(spec):
    # the benchmark's rooflines match the decode program by this exact name
    assert re.match(r"HloModule jit__unknown,", _compiled(spec))


def test_batch_pad_span_says_whether_the_staging_buffer_was_reused(
        tmp_path):
    sched = BatchScheduler(_decode, max_batch=2, buckets=(8, 16))
    for T in (5, 7, 12, 3, 8, 16):
        sched.submit(np.ones((T, 4), np.float32))
    _decode(np.zeros((2, 8, 4), np.float32), np.zeros(2, np.int32))
    _decode(np.zeros((2, 16, 4), np.float32), np.zeros(2, np.int32))
    events = _recorded(tmp_path, sched.drain)
    pads = [e[1] for e in events if e[0] == "repro.batch.pad"]
    # batches of buckets 8, 16, 8: the first batch of each allocates
    assert [(p["batch"], p["reused"]) for p in pads] == [
        (0, 0), (1, 0), (2, 1)]
    assert (sched.stats["staging_allocs"], sched.stats["staging_reuses"]) \
        == (2, 1)
