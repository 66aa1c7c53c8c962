"""How `BatchScheduler.step` fans a batch's outputs back out to requests.

Each batch brings its paths and scores to the host in one copy of each
output and slices the rows there: no per-row indexing of the outputs (on
a device array every index is a small device program, and a slice of a
new length a new compile), and each request owns its row.
"""

import jax
import jax.numpy as jnp
import jax.monitoring as mon
import numpy as np
import pytest

from repro.core import (ViterbiDecoder, VanillaSpec, erdos_renyi_hmm,
                        random_emissions)
from repro.serving.scheduler import BatchScheduler

COMPILE = "/jax/core/compile/backend_compile_duration"


class _Counted:
    """An output that counts host conversions and indexing."""

    def __init__(self, value):
        self.value = value
        self.arrays = 0
        self.items = 0

    def __array__(self, dtype=None, copy=None):
        self.arrays += 1
        return np.asarray(self.value, dtype)

    def __getitem__(self, key):
        self.items += 1
        return self.value[key]


def _ragged(lengths):
    return [np.full((T, 3), i + 1, np.float32) for i, T in enumerate(lengths)]


def test_one_host_copy_per_output_per_batch_and_no_row_indexing():
    outputs = []

    def decode(padded, lens):
        B, Tb, _ = padded.shape
        tags = padded[:, 0, 0].astype(np.int32)
        out = (_Counted(np.repeat(tags[:, None], Tb, 1)),
               _Counted(tags.astype(np.float32)))
        outputs.append(out)
        return out

    lengths = (1, 16, 5, 16, 9, 1, 32, 17)        # T=1 and T=bucket
    sched = BatchScheduler(decode, max_batch=3, buckets=(16, 32))
    reqs = [sched.submit(p) for p in _ragged(lengths)]
    sched.drain()
    assert len(outputs) == sched.stats["batches"] == 3
    for paths, scores in outputs:
        assert (paths.arrays, scores.arrays) == (1, 1)
        assert (paths.items, scores.items) == (0, 0)
    for i, (r, T) in enumerate(zip(reqs, lengths)):
        path, score = r.result
        assert r.done and path.shape == (T,)
        assert np.all(path == i + 1) and score == i + 1


@pytest.fixture
def compiles():
    """Durations of the backend compiles made while the test runs."""
    seen = []

    def on_duration(event, duration, **_):
        if event == COMPILE:
            seen.append(duration)
    mon.register_event_duration_secs_listener(on_duration)
    yield seen
    mon.unregister_event_duration_listener(on_duration)


@jax.jit
def _decode(padded, lens):
    return jnp.argmax(padded, -1).astype(jnp.int32), padded.sum((1, 2))


def test_new_lengths_in_a_warm_bucket_compile_nothing(compiles):
    rng = np.random.default_rng(0)
    sched = BatchScheduler(_decode, max_batch=4, buckets=(16, 32))

    def serve(lengths):
        reqs = [sched.submit(rng.standard_normal((T, 4)).astype(np.float32))
                for T in lengths]
        sched.drain()
        return reqs

    serve((20, 32, 17, 25))                       # warm bucket 32, batch 4
    compiles.clear()
    lengths = [18, 21, 30, 31, 22, 27, 19, 29]    # none seen before
    reqs = serve(lengths)
    assert compiles == []
    assert [len(r.result[0]) for r in reqs] == lengths


@pytest.fixture(scope="module")
def decoder():
    hmm = erdos_renyi_hmm(jax.random.key(5), 16, edge_prob=0.5)
    return ViterbiDecoder(VanillaSpec(), hmm.log_pi, hmm.log_A)


def test_results_equal_the_batched_call_rows_and_own_their_memory(decoder):
    lengths = np.array([24, 1, 7, 24, 13], np.int32)
    keys = jax.random.split(jax.random.key(9), len(lengths))
    ems = [np.asarray(random_emissions(k, int(T), 16))
           for k, T in zip(keys, lengths)]
    sched = BatchScheduler(decoder, max_batch=len(lengths), buckets=(24,))
    reqs = [sched.submit(em) for em in ems]
    assert sched.step() == reqs

    padded = np.zeros((len(lengths), 24, 16), np.float32)
    for i, em in enumerate(ems):
        padded[i, :lengths[i]] = em
    paths, scores = decoder.decode_batch(padded, lengths)
    paths, scores = np.asarray(paths), np.asarray(scores)
    for i, r in enumerate(reqs):
        path, score = r.result
        assert path.dtype == np.int32 and type(score) is float
        assert np.array_equal(path, paths[i, :lengths[i]])
        assert score == float(scores[i])
        assert path.flags.owndata
    for i, a in enumerate(reqs):
        for b in reqs[i + 1:]:
            assert not np.shares_memory(a.result[0], b.result[0])


# -- staging buffers --------------------------------------------------------
#
# Each (bucket, K) is padded into a kept (max_batch, bucket, K) buffer (a
# second one for a batch dispatched while the first is in flight); only
# the stale part of each slot's tail is zeroed between batches.


class _Staged:
    """A decode_batch_fn that enforces the padding contract on every call
    (rows intact, every pad frame exactly 0.0) and records each call's
    shape and the address of the array it was handed, keeping no
    reference to the array."""

    def __init__(self):
        self.calls = []                       # (shape, data address)

    def __call__(self, padded, lengths):
        B, Tb, _ = padded.shape
        lengths = np.asarray(lengths)
        tags = padded[:, 0, 0].astype(np.int32)
        for i in range(B):
            assert np.all(padded[i, :lengths[i]] == tags[i])
            assert np.all(padded[i, lengths[i]:] == 0.0), (i, lengths[i])
        self.calls.append((padded.shape, padded.ctypes.data))
        return np.repeat(tags[:, None], Tb, 1), tags.astype(np.float32)


def _serve(sched, rows):
    """Submit (T, K, tag) rows, drain, and check each result's tag."""
    reqs = [sched.submit(np.full((T, K), tag, np.float32))
            for T, K, tag in rows]
    sched.drain()
    for r, (T, _, tag) in zip(reqs, rows):
        assert r.result[0].shape == (T,) and np.all(r.result[0] == tag)
    return reqs


def test_a_short_row_after_a_long_one_in_a_slot_sees_a_zero_tail():
    dec = _Staged()
    sched = BatchScheduler(dec, max_batch=2, buckets=(32,))
    _serve(sched, [(32, 3, 1), (5, 3, 2)])        # slot 0 long, slot 1 short
    _serve(sched, [(7, 3, 3), (30, 3, 4)])        # and the other way round
    _serve(sched, [(1, 3, 5), (1, 3, 6)])
    assert [shape for shape, _ in dec.calls] == [(2, 32, 3)] * 3
    assert len({addr for _, addr in dec.calls}) == 1


def test_a_partial_batch_hands_the_decoder_its_rows_only():
    dec = _Staged()
    sched = BatchScheduler(dec, max_batch=4, buckets=(16,))
    _serve(sched, [(16, 2, t) for t in range(1, 7)])
    _serve(sched, [(3, 2, 7)])
    assert [shape for shape, _ in dec.calls] == [
        (4, 16, 2), (2, 16, 2), (1, 16, 2)]
    assert len({addr for _, addr in dec.calls}) == 1


def test_buckets_and_state_counts_never_share_a_buffer():
    dec = _Staged()
    sched = BatchScheduler(dec, max_batch=2, buckets=(16, 32))
    rows = [(16, 3, 1), (16, 3, 2), (32, 3, 3), (9, 5, 4)]
    for _ in range(2):
        _serve(sched, rows)
    # calls alternate (16, K=3), (32, K=3), (16, K=5) twice over
    addrs = [addr for _, addr in dec.calls]
    assert len(addrs) == 6 and addrs[:3] == addrs[3:]
    assert len(set(addrs)) == 3
    spans = sorted((a, a + 2 * Tb * K * 4) for (_, Tb, K), a in dec.calls[:3])
    assert all(hi <= lo for (_, hi), (lo, _) in zip(spans, spans[1:]))


def test_staging_counters_count_one_alloc_per_bucket_and_state_count():
    sched = BatchScheduler(_Staged(), max_batch=2, buckets=(16, 32))
    assert sched.stats["staging_allocs"] == sched.stats["staging_reuses"] == 0
    _serve(sched, [(10, 3, 1), (12, 3, 2), (20, 3, 3), (12, 4, 4)])
    assert (sched.stats["staging_allocs"], sched.stats["staging_reuses"]) \
        == (3, 0)
    # the second batch of bucket 16 is dispatched while the first is in
    # flight (a full batch and two rows queued behind it), so (16, K=3)
    # gets its second buffer; the third batch of 16 reuses the first
    _serve(sched, [(16, 3, t) for t in range(1, 6)] + [(31, 3, 6)])
    assert sched.stats["batches"] == 7
    assert sched.stats["lookahead"] == 1
    assert (sched.stats["staging_allocs"], sched.stats["staging_reuses"]) \
        == (4, 3)


def test_reused_buffers_decode_bit_identically_to_fresh_padding(decoder):
    lengths = [24, 20, 3, 24, 1, 17, 11, 24, 2]
    keys = jax.random.split(jax.random.key(11), len(lengths))
    ems = [np.asarray(random_emissions(k, T, 16))
           for k, T in zip(keys, lengths)]
    sched = BatchScheduler(decoder, max_batch=3, buckets=(24,))
    reqs = [sched.submit(em) for em in ems]
    sched.drain()
    # the second batch is dispatched while the first is in flight, into a
    # second buffer; the third reuses the first
    assert sched.stats["lookahead"] == 1
    assert (sched.stats["staging_allocs"], sched.stats["staging_reuses"]) \
        == (2, 1)
    for b in range(0, len(ems), 3):
        padded = np.zeros((3, 24, 16), np.float32)
        for i, em in enumerate(ems[b:b + 3]):
            padded[i, :len(em)] = em
        paths, scores = decoder.decode_batch(padded, lengths[b:b + 3])
        for i, r in enumerate(reqs[b:b + 3]):
            assert np.array_equal(r.result[0],
                                  np.asarray(paths)[i, :lengths[b + i]])
            assert r.result[1] == float(scores[i])


# -- look-ahead -------------------------------------------------------------
#
# `step()` dispatches the next batch before it waits on the oldest, when
# the queue's head bucket holds a full batch and `max_batch` requests stay
# queued behind it.


class _Out:
    """A paths output: waiting on it and copying it to the host each run
    `check` first."""

    def __init__(self, value, check):
        self.value, self.check = value, check

    def block_until_ready(self):
        self.check("wait")
        return self

    def __array__(self, dtype=None, copy=None):
        self.check("unpad")
        return np.asarray(self.value, dtype)


class _Recorder:
    """A decode_batch_fn that records its inputs.  Each call keeps a copy
    of the padded batch, logs ``("dispatch", i)`` and returns rows tagged
    by their first frame; waiting on call i's paths logs ``("wait", i)``.
    Both the wait and the copy to the host check that the view the call
    was handed still holds what it held then, so a staging buffer written
    before its batch was waited on fails the step that writes it."""

    def __init__(self):
        self.inputs: list[np.ndarray] = []
        self.log: list[tuple[str, int]] = []

    def __call__(self, padded, lengths):
        i = len(self.inputs)
        kept = padded.copy()
        self.inputs.append(kept)
        self.log.append(("dispatch", i))

        def check(event):
            assert np.array_equal(padded, kept), (event, i)
            if event == "wait":
                self.log.append((event, i))

        B, Tb, _ = padded.shape
        tags = padded[:, 0, 0].astype(np.int32)
        return (_Out(np.repeat(tags[:, None], Tb, 1), check),
                tags.astype(np.float32))

    def in_flight(self) -> int:
        return sum(1 if e == "dispatch" else -1 for e, _ in self.log)

    def early(self) -> int:
        """Calls made while another call's batch was not yet waited on."""
        n = live = 0
        for e, _ in self.log:
            n += e == "dispatch" and live > 0
            live += 1 if e == "dispatch" else -1
        return n


def _tagged(sched, lengths, K=3, first_tag=1):
    return [sched.submit(np.full((T, K), first_tag + i, np.float32))
            for i, T in enumerate(lengths)]


@pytest.mark.parametrize("lengths, max_batch, log", [
    # a full batch with a full batch queued behind it: dispatched early
    ([4] * 6, 2, ["d0", "d1", "w0", "w1", "d2", "w2"]),
    # four queued behind the first batch, but the head bucket's is partial
    ([4, 4, 12, 5, 5, 6], 2,
     ["d0", "w0", "d1", "w1", "d2", "w2", "d3", "w3"]),
    # a full next batch, but the queue would fall under max_batch
    ([4] * 5, 2, ["d0", "w0", "d1", "w1", "d2", "w2"]),
    # early on every step while the queue holds two batches
    ([3] * 12, 3, ["d0", "d1", "w0", "d2", "w1", "w2", "d3", "w3"]),
], ids=["full_behind", "partial_next", "short_queue", "every_step"])
def test_lookahead_runs_only_with_a_full_batch_and_a_queue_behind_it(
        lengths, max_batch, log):
    dec = _Recorder()
    sched = BatchScheduler(dec, max_batch=max_batch, buckets=(8, 16))
    reqs = _tagged(sched, lengths)
    done = sched.drain()
    assert [f"{e[0]}{i}" for e, i in dec.log] == log
    assert sched.stats["lookahead"] == dec.early()
    assert done == reqs and all(r.done for r in reqs)


def test_step_returns_with_a_batch_in_flight_only_over_a_full_queue():
    rng = np.random.default_rng(4)
    dec = _Recorder()
    sched = BatchScheduler(dec, max_batch=3, buckets=(8, 16))
    submitted = 0
    for _ in range(60):
        lengths = rng.integers(1, 17, rng.integers(0, 7))
        _tagged(sched, lengths, first_tag=submitted + 1)
        submitted += len(lengths)
        sched.step()
        assert dec.in_flight() in (0, 1)
        assert not dec.in_flight() or len(sched.queue) >= 3
    sched.drain()
    assert dec.in_flight() == 0 and sched.stats["requests"] == submitted
    assert sched.stats["lookahead"] == dec.early() > 0


@pytest.mark.parametrize("loop", ["while_queue", "full_batches_then_drain"])
def test_every_request_comes_back_once_in_dispatch_order(loop):
    rng = np.random.default_rng(7)
    dec = _Recorder()
    sched = BatchScheduler(dec, max_batch=4, buckets=(8, 16, 32))
    reqs, returned = [], []
    for wave in range(12):
        reqs += _tagged(sched, rng.integers(1, 33, rng.integers(1, 14)),
                        first_tag=len(reqs) + 1)
        if loop == "while_queue":
            while sched.queue:
                returned += sched.step()
        else:
            while len(sched.queue) >= sched.max_batch:
                returned += sched.step()
    returned += sched.drain()
    assert sched.stats["lookahead"] > 0
    assert sorted(r.rid for r in returned) == [r.rid for r in reqs]
    # the batches come back as they were dispatched, row for row
    dispatched = [int(t) for x in dec.inputs for t in x[:, 0, 0]]
    assert [r.rid + 1 for r in returned] == dispatched
    for r in reqs:
        assert np.all(r.result[0] == r.rid + 1)
        assert r.result[0].shape == (len(r.payload),)


def test_a_buffer_is_not_written_until_its_batch_is_waited_on():
    # ragged rows in two buckets (each its own state count), queued deep
    # enough that most steps dispatch ahead; `_Recorder` checks each
    # batch's input at its wait and its unpad
    rng = np.random.default_rng(3)
    dec = _Recorder()
    sched = BatchScheduler(dec, max_batch=4, buckets=(16, 32))
    reqs = [sched.submit(np.full((T, 3 if T <= 16 else 5), i + 1,
                                 np.float32))
            for i, T in enumerate(rng.integers(1, 33, 80))]
    sched.drain()
    assert all(r.done and np.all(r.result[0] == r.rid + 1) for r in reqs)
    assert sched.stats["lookahead"] >= sched.stats["batches"] // 2
    # no key ever needs more than two buffers
    assert all(len(kept) <= 2 for kept in sched._staging.values())


def test_flush_hands_back_the_batch_in_flight():
    dec = _Recorder()
    sched = BatchScheduler(dec, max_batch=2, buckets=(8,))
    reqs = _tagged(sched, [4] * 7)
    assert sched.step() == reqs[:2]
    assert dec.in_flight() == 1 and len(sched.queue) == 3
    assert sched.flush() == reqs[2:4]
    assert dec.in_flight() == 0 and list(sched.queue) == reqs[4:]
    assert sched.flush() == []


def test_lookahead_decodes_bit_identically_to_unbatched_decodes(decoder):
    lengths = [24, 5, 17, 9, 24, 1, 13, 30, 22, 11, 7, 28, 2, 19, 16, 3]
    keys = jax.random.split(jax.random.key(13), len(lengths))
    ems = [np.asarray(random_emissions(k, T, 16))
           for k, T in zip(keys, lengths)]
    sched = BatchScheduler(decoder, max_batch=3, buckets=(16, 32))
    reqs = [sched.submit(em) for em in ems]
    sched.drain()
    assert sched.stats["lookahead"] >= 2
    for r, em in zip(reqs, ems):
        path, score = decoder.decode(em)
        assert np.array_equal(r.result[0], np.asarray(path))
        assert np.float32(r.result[1]).tobytes() == \
            np.float32(score).tobytes()
