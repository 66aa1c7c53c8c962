"""Open-loop streaming client: live sessions in front of `InflightScheduler`.

The system under test is `serving.inflight.InflightScheduler`: `submit`,
`feed`, `pump`, `collect`, `finish`.  Sessions arrive on the wall clock and
send their frames in chunks as the schedule makes them due, whatever the
system is doing; each session finishes as soon as its last chunk is fed.

The run starts the schedule at 0, warms up for ``warmup_s`` (set-up), and
measures the window [warmup_s, warmup_s + seconds):

* a chunk due in the window is timed from its due time to the return of the
  `collect` that follows the step that consumed its last frame;
* a session whose last chunk is due in the window is timed from that due
  time to the return of `finish`;
* the generator's lateness is how long after its due time a chunk was fed.

After the window closes the schedule runs on until every chunk and session
it measures has completed (at most ``tail_limit_s``); what has not completed
then never came, and counts as failed.
"""

from __future__ import annotations

import time

import numpy as np

from . import registry
from .traffic import POOL_KEYS, chunk_schedule, open_schedule


def percentile(xs, q: float) -> float:
    if len(xs) == 0:
        return float("nan")
    return float(np.percentile(np.asarray(xs, np.float64), q))


class _Live:
    __slots__ = ("sess", "sid", "fed", "waiting", "segments", "final",
                 "measured", "last_due")

    def __init__(self, sess, measured: bool, last_due: float):
        self.sess = sess
        self.sid = None
        self.fed = 0
        self.waiting: list[tuple[float, int, bool]] = []  # (due, hi, measured)
        self.segments: list[np.ndarray] = []
        self.final = None
        self.measured = measured
        self.last_due = last_due


class StreamClient:
    #: ``knee`` records how ``rate_per_s`` was chosen; nothing reads it
    KEYS = POOL_KEYS + ("kind", "arrivals", "rate_per_s", "chunk_frames",
                        "frames_per_s", "max_slots", "block", "max_lag",
                        "warmup_s", "tail_limit_s", "knee")

    def __init__(self, mix: dict, log_pi, log_A, pool: np.ndarray,
                 lengths: np.ndarray, seed: int, spans,
                 clock=time.perf_counter, sleep=time.sleep,
                 rate: float | None = None):
        registry.check_keys("open-loop traffic mix", mix, self.KEYS)
        self.mix = mix
        self.log_pi, self.log_A = log_pi, log_A
        self.pool, self.lengths = pool, lengths
        self.seed = seed
        self.spans = spans
        self.clock, self.sleep = clock, sleep
        self.rate = float(mix["rate_per_s"] if rate is None else rate)
        self.warmup_s = float(mix["warmup_s"])
        self.tail_limit_s = float(mix.get("tail_limit_s", 60.0))
        self.answers: list[tuple[int, np.ndarray, float]] = []
        self.prefix_bad = 0
        self.counters: dict = {}
        self.info: dict = {}
        self.K = int(pool.shape[-1])
        self._empty = np.zeros((0, self.K), np.float32)

    def setup(self) -> None:
        from repro.serving.inflight import InflightScheduler
        self.sched = InflightScheduler(
            self.log_pi, self.log_A, max_slots=int(self.mix["max_slots"]),
            block=int(self.mix["block"]), horizon=int(self.lengths.max()))
        self.info.update(max_slots=self.sched.max_slots,
                         block=self.sched.block, rate_per_s=self.rate,
                         max_lag=self.mix.get("max_lag"))
        # one session end to end compiles every program the loop can call
        sid = self.sched.submit(max_lag=self.mix.get("max_lag"))
        self.sched.feed(sid, self.pool[0, :3 * self.sched.block])
        self.sched.pump()
        self.sched.finish(sid)
        self.sched.collect(sid)

    def _buffered(self, live: _Live) -> int:
        return self.sched.feed(live.sid, self._empty)["buffered"]

    def backlog(self, lives) -> dict:
        """Queued sessions and frames fed but not yet consumed."""
        buffered = sum(self._buffered(v) for v in lives
                       if v.sid is not None and v.final is None)
        return {"queued": len(self.sched.queued_sessions()),
                "buffered_frames": int(buffered)}

    def run(self, seconds: float, marks) -> dict:
        mix, sched, spans, clock = self.mix, self.sched, self.spans, self.clock
        fps = float(mix["frames_per_s"])
        w0, w1 = self.warmup_s, self.warmup_s + float(seconds)
        with spans("traffic"):
            sessions = open_schedule(mix, self.seed, w1 + self.tail_limit_s,
                                     rate=self.rate)
            chunks = chunk_schedule(mix, sessions)
        lives = [_Live(s, w0 <= s.last_due(fps) < w1, s.last_due(fps))
                 for s in sessions]
        chunk_ms: list[float] = []
        finish_ms: list[float] = []
        late_ms: list[float] = []
        open_chunks = sum(w0 <= c.due < w1 for c in chunks)
        open_sessions = sum(v.measured for v in lives)
        active: dict[int, _Live] = {}
        busy_s = 0.0          # inside pump() and finish(), in the window
        in_window = False
        ci, n = 0, len(chunks)
        stats0 = backlog0 = None
        marks.settle()
        t0 = clock()
        while True:
            now = clock() - t0
            if not in_window and now >= w0 and stats0 is None:
                marks.window_start()
                in_window = True
                stats0 = dict(sched.stats)
                backlog0 = self.backlog(active.values())
            if in_window and now >= w1:
                marks.window_end()
                in_window = False
                stats1 = dict(sched.stats)
                backlog1 = self.backlog(active.values())
            if stats0 is not None and not in_window and (
                    (open_chunks == 0 and open_sessions == 0)
                    or now >= w1 + self.tail_limit_s):
                break
            due: list = []
            with spans("traffic"):
                while ci < n and chunks[ci].due <= now:
                    due.append(chunks[ci])
                    ci += 1
            if not due:
                wait = (chunks[ci].due - now) if ci < n else 0.001
                with spans("wait"):
                    self.sleep(max(0.0, min(wait, 0.002)))
                continue
            finishing = []
            for c in due:
                live = lives[c.session]
                if c.first:
                    with spans("submit"):
                        live.sid = sched.submit(max_lag=mix.get("max_lag"))
                    active[c.session] = live
                s = live.sess
                with spans("feed"):
                    sched.feed(live.sid, self.pool[s.pool_index, c.lo:c.hi])
                if w0 <= c.due < w1:
                    late_ms.append(1e3 * (clock() - t0 - c.due))
                live.fed = c.hi
                live.waiting.append((c.due, c.hi, w0 <= c.due < w1))
                if c.last:
                    finishing.append(live)
            steps = sched.stats["steps"]
            t = clock()
            with spans("pump"):
                sched.pump()
            for live in finishing:
                with spans("finish"):
                    live.final = sched.finish(live.sid)
                t_fin = clock() - t0
                if live.measured:
                    finish_ms.append(1e3 * (t_fin - live.last_due))
                    open_sessions -= 1
            if in_window:
                busy_s += clock() - t
            if sched.stats["steps"] == steps and not finishing:
                continue
            # a step ran: hand each session what became final and time the
            # chunks whose last frame it consumed
            for key in list(active):
                live = active[key]
                consumed = (live.fed if live.final is not None
                            else live.fed - self._buffered(live))
                if not live.waiting or live.waiting[0][1] > consumed:
                    continue
                with spans("collect"):
                    seg = sched.collect(live.sid)
                t_c = clock() - t0
                live.segments.append(seg)
                while live.waiting and live.waiting[0][1] <= consumed:
                    c_due, _, c_measured = live.waiting.pop(0)
                    if c_measured:
                        chunk_ms.append(1e3 * (t_c - c_due))
                        open_chunks -= 1
                if live.final is not None:
                    del active[key]
                    if live.measured:
                        self._record(live)
        self.counters.update(
            window_s=w1 - w0, steps=stats1["steps"] - stats0["steps"],
            frames=stats1["frames"] - stats0["frames"],
            max_slots=sched.max_slots, block=sched.block, busy_s=busy_s,
            chunks=len(chunk_ms), sessions=len(finish_ms),
            chunks_missing=open_chunks, sessions_missing=open_sessions)
        self.info.update(
            lateness_ms={"p50": percentile(late_ms, 50),
                         "p99": percentile(late_ms, 99),
                         "max": max(late_ms, default=float("nan"))},
            backlog_start=backlog0, backlog_end=backlog1,
            tail_s=clock() - t0 - w1)
        self.failed = open_chunks + open_sessions
        return {"chunk_p95_ms": percentile(chunk_ms, 95),
                "finish_p95_ms": percentile(finish_ms, 95)}

    def _record(self, live: _Live) -> None:
        path, score = live.final
        joined = (np.concatenate(live.segments) if live.segments
                  else np.zeros((0,), np.int32))
        if not np.array_equal(joined, path):
            self.prefix_bad += 1
        self.answers.append((live.sess.pool_index, np.asarray(path),
                             float(score)))

    def close(self) -> None:
        self.__dict__.pop("sched", None)
