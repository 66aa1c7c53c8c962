"""Closed-loop offline client in front of `BatchScheduler`.

The system under test is the program's serving path for offline alignment:
`serving.scheduler.BatchScheduler` over
`serving.alignment.make_alignment_head(log_pi, log_A, spec)`, which calls
`ViterbiDecoder.decode_batch`.  The spec comes from the builder the mix
names (``spec.builder``, ``bench/specs/<builder>.py``).  The client keeps
``queue_batches * max_batch`` requests queued and submits one new request
for each one handed back.  The window runs
`sched.step()` until `seconds` have passed and closes at the end of the step
that crosses that mark, so the rate is all the frames delivered over all the
time taken.
"""

from __future__ import annotations

import itertools
import time

import numpy as np

from . import registry
from .traffic import POOL_KEYS, closed_order


def resolve_spec(spec_cfg: dict, *, K: int, T: int, batch: int, log_pi,
                 log_A, base=registry.BENCH):
    """(`DecodeSpec`, why) from the builder a mix names, given its
    parameters, the cell's K, longest bucket T and batch, and the HMM."""
    builder = registry.load_module("specs", spec_cfg["builder"], base)
    registry.check_keys(f"spec builder {spec_cfg['builder']!r}", spec_cfg,
                        ("builder",) + tuple(builder.PARAMS))
    return builder.build(spec_cfg, K=K, T=T, batch=batch, log_pi=log_pi,
                         log_A=log_A)


class OfflineClient:
    KEYS = POOL_KEYS + ("kind", "buckets", "max_batch", "queue_batches",
                        "spec")

    def __init__(self, mix: dict, log_pi, log_A, pool: np.ndarray,
                 lengths: np.ndarray, seed: int, spans,
                 clock=time.perf_counter):
        registry.check_keys("closed-loop traffic mix", mix, self.KEYS)
        self.mix = mix
        self.log_pi, self.log_A = log_pi, log_A
        self.pool, self.lengths = pool, lengths
        self.spans = spans
        self.clock = clock
        self.buckets = sorted(int(b) for b in mix["buckets"])
        self.max_batch = int(mix["max_batch"])
        self._order = itertools.cycle(closed_order(len(lengths), seed))
        self._pool_of: dict[int, int] = {}
        self.answers: list[tuple[int, np.ndarray, float]] = []
        self.counters: dict = {}
        self.info: dict = {}

    def bucket_of(self, L: int) -> int:
        return next((b for b in self.buckets if L <= b), self.buckets[-1])

    def _submit(self, i: int) -> None:
        req = self.sched.submit(self.pool[i, :self.lengths[i]])
        self._pool_of[req.rid] = i

    def setup(self) -> None:
        from repro.serving.alignment import make_alignment_head
        from repro.serving.scheduler import BatchScheduler
        spec, why = resolve_spec(
            self.mix["spec"], K=int(self.pool.shape[-1]),
            T=self.buckets[-1], batch=self.max_batch, log_pi=self.log_pi,
            log_A=self.log_A)
        self.head = make_alignment_head(self.log_pi, self.log_A, spec)
        self.sched = BatchScheduler(self.head, max_batch=self.max_batch,
                                    buckets=tuple(self.buckets))
        self.info.update(spec=repr(spec), spec_why=why)
        # warm every (bucket, max_batch) shape the window can produce with
        # full batches that hold every pool entry of the bucket (unpadding
        # compiles a slice per length), then run one more batch of each
        by_bucket: dict[int, list[int]] = {}
        for i, L in enumerate(self.lengths):
            by_bucket.setdefault(self.bucket_of(int(L)), []).append(i)
        mb = self.max_batch
        for rounds in (None, 1):
            for idx in by_bucket.values():
                n = mb * (rounds or -(-len(idx) // mb))
                for i in itertools.islice(itertools.cycle(idx), n):
                    self._submit(i)
                self.sched.drain()
        self._pool_of.clear()
        for _ in range(int(self.mix["queue_batches"]) * self.max_batch):
            self._submit(next(self._order))

    def run(self, seconds: float, marks) -> dict:
        sched, spans, clock = self.sched, self.spans, self.clock
        frames = run_frames = batches = 0
        marks.settle()
        marks.window_start()
        t0 = clock()
        while True:
            with spans("sched.step"):
                done = sched.step()
            t = clock()
            for r in done:
                L = len(r.payload)
                frames += L
                self.answers.append((self._pool_of.pop(r.rid), r.result[0],
                                     r.result[1]))
            if done:
                run_frames += self.bucket_of(len(done[0].payload)) * len(done)
                batches += 1
            if t - t0 >= seconds:
                break
            with spans("submit"):
                for _ in done:
                    self._submit(next(self._order))
        marks.window_end()
        window = t - t0
        self.counters.update(window_s=window, frames=frames,
                             run_frames=run_frames, batches=batches,
                             requests=len(self.answers))
        return {"frames_per_s": frames / window}

    def close(self) -> None:
        for name in ("sched", "head"):
            self.__dict__.pop(name, None)
