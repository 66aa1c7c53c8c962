"""Rates and tails cover the whole window; open-loop times follow the
wall-clock schedule.  The clients run against stand-in schedulers on a fake
clock, so a stall can be placed exactly."""

import itertools

import numpy as np
import pytest

from lib.offline import OfflineClient
from lib.spans import Spans
from lib.stream import StreamClient, percentile
from lib import registry
from lib.traffic import (chunk_schedule, closed_order, open_schedule,
                         pool_lengths)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, d):
        self.t += d


class Marks:
    def settle(self):
        pass

    def window_start(self):
        pass

    def window_end(self):
        pass


class _Req:
    def __init__(self, rid, payload):
        self.rid, self.payload, self.result = rid, payload, None


class FakeBatchScheduler:
    """Serves `max_batch` requests per step; each step takes 1 s, and the
    step numbered `stall_at` takes `stall` s more."""

    def __init__(self, clock, max_batch, stall_at=None, stall=0.0):
        self.clock, self.mb = clock, max_batch
        self.queue, self.ids = [], itertools.count()
        self.n, self.stall_at, self.stall = 0, stall_at, stall

    def submit(self, payload):
        r = _Req(next(self.ids), payload)
        self.queue.append(r)
        return r

    def step(self):
        done, self.queue = self.queue[:self.mb], self.queue[self.mb:]
        self.clock.t += 1.0 + (self.stall if self.n == self.stall_at else 0)
        self.n += 1
        for r in done:
            r.result = (np.zeros(len(r.payload), np.int32), 0.0)
        return done


MIX = {"kind": "closed", "lengths": [4, 8], "pool_per_length": 2,
       "buckets": [8], "max_batch": 2, "queue_batches": 2}


def _offline(stall_at=None, stall=0.0):
    clock = FakeClock()
    lengths = pool_lengths(MIX)
    pool = np.zeros((len(lengths), 8, 3), np.float32)
    client = OfflineClient(MIX, None, None, pool, lengths, 1, Spans(False),
                           clock=clock)
    client.sched = FakeBatchScheduler(clock, 2, stall_at, stall)
    for _ in range(4):
        client._submit(next(client._order))
    return client, client.run(10.0, Marks())


def test_offline_rate_is_all_frames_over_the_whole_window():
    client, out = _offline()
    c = client.counters
    assert c["window_s"] == 10.0 and c["batches"] == 10
    assert out["frames_per_s"] == c["frames"] / 10.0
    assert c["frames"] == sum(len(p) for _, p, _ in client.answers)


def test_offline_stall_inside_the_window_moves_the_rate():
    _, calm = _offline()
    _, stalled = _offline(stall_at=3, stall=5.0)
    assert stalled["frames_per_s"] < 0.75 * calm["frames_per_s"]


def test_closed_order_and_pool_are_the_same_set_for_every_seed():
    a, b = closed_order(12, 1), closed_order(12, 2)
    assert sorted(a) == sorted(b) == list(range(12))
    assert list(a) != list(b)
    assert list(pool_lengths({"length_range": [128, 256], "pool": 3})) == [
        128, 192, 256]


def test_poisson_gaps_are_one_set_in_another_order():
    poisson = registry.load_module("arrivals", "poisson")
    g1 = poisson.gaps(100, 4.0, np.random.default_rng(1))
    g2 = poisson.gaps(100, 4.0, np.random.default_rng(2))
    assert np.allclose(np.sort(g1), np.sort(g2))
    assert abs(g1.mean() - 0.25) < 0.01


class FakeInflight:
    """Consumes everything buffered on each pump; `stall` s once, at the
    first pump at or after `stall_t` on the clock."""

    def __init__(self, clock, stall_t=None, stall=0.0):
        self.clock, self.stall_t, self.stall = clock, stall_t, stall
        self.ids = itertools.count()
        self.buf, self.fed, self.pending = {}, {}, {}
        self.stats = {"steps": 0, "frames": 0}
        self.max_slots, self.block = 4, 16

    def submit(self, max_lag=None):
        sid = next(self.ids)
        self.buf[sid], self.fed[sid], self.pending[sid] = 0, 0, []
        return sid

    def feed(self, sid, frames):
        self.buf[sid] += len(frames)
        self.fed[sid] += len(frames)
        return {"buffered": self.buf[sid]}

    def _step(self):
        if self.stall_t is not None and self.clock.t >= self.stall_t:
            self.clock.t += self.stall
            self.stall_t = None
        self.clock.t += 0.001
        self.stats["steps"] += 1
        for sid, b in self.buf.items():
            if b:
                self.pending[sid].append(np.zeros(b, np.int32))
                self.stats["frames"] += b
                self.buf[sid] = 0

    def pump(self):
        if any(self.buf.values()):
            self._step()

    def collect(self, sid):
        out = (np.concatenate(self.pending[sid]) if self.pending[sid]
               else np.zeros(0, np.int32))
        self.pending[sid] = []
        return out

    def finish(self, sid):
        if self.buf[sid]:
            self._step()
        return np.zeros(self.fed[sid], np.int32), -1.0

    def queued_sessions(self):
        return []


STREAM = {"kind": "open", "lengths": [20, 40], "pool_per_length": 1,
          "rate_per_s": 4.0, "arrivals": {"process": "poisson"},
          "chunk_frames": 10, "frames_per_s": 100,
          "max_slots": 4, "block": 16, "warmup_s": 1.0, "tail_limit_s": 5.0}


def _stream(stall_t=None, stall=0.0):
    clock = FakeClock()
    lengths = pool_lengths(STREAM)
    pool = np.zeros((len(lengths), 40, 3), np.float32)
    client = StreamClient(STREAM, None, None, pool, lengths, 3, Spans(False),
                          clock=clock, sleep=clock.sleep)
    client.sched = FakeInflight(clock, stall_t, stall)
    return client, client.run(5.0, Marks())


def test_stream_tails_cover_every_chunk_and_session_due_in_the_window():
    client, out = _stream()
    sessions = open_schedule(STREAM, 3, 1.0 + 5.0 + 5.0)
    chunks = chunk_schedule(STREAM, sessions)
    due = [c for c in chunks if 1.0 <= c.due < 6.0]
    ends = [s for s in sessions if 1.0 <= s.last_due(100) < 6.0]
    c = client.counters
    assert c["chunks"] == len(due) and c["sessions"] == len(ends)
    assert c["chunks_missing"] == 0 and client.failed == 0
    assert len(client.answers) == len(ends) and client.prefix_bad == 0
    assert out["chunk_p95_ms"] < 10.0 and client.info["lateness_ms"]["max"] < 5


def test_stream_stall_moves_the_tail_and_the_lateness():
    _, calm = _stream()
    client, stalled = _stream(stall_t=3.0, stall=0.5)
    assert stalled["chunk_p95_ms"] > calm["chunk_p95_ms"] + 100
    # chunks that fell due during the stall were fed late, by the schedule
    assert client.info["lateness_ms"]["max"] > 400


def test_percentile_of_nothing_is_nan():
    assert np.isnan(percentile([], 95))
    assert percentile([1.0, 2.0, 3.0], 50) == pytest.approx(2.0)
