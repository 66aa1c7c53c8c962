"""Batched request scheduler for the serving examples.

Continuous-batching-lite: requests queue up, the scheduler packs up to
`max_batch` compatible requests (same HMM / model), pads sequences to the
bucket boundary, runs one batched decode, and fans results back out.  Buckets
keep jit cache hits high (one compile per bucket, not per length).

The decode function receives the true lengths alongside the padded batch:
``decode_batch_fn(padded (B, Tb, K), lengths (B,) int32) -> (paths, scores)``.
Length-aware decoders (``core.viterbi_decode_batch``) mask pad frames as
tropical-identity steps, so every request's path and score are bit-identical
to an unbatched decode of its unpadded payload — padding is a pure throughput
trick, never an approximation.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Any, Callable

import jax
import numpy as np

from repro.runtime.spans import span


@dataclasses.dataclass
class Request:
    rid: int
    payload: Any                    # (T, K) emissions or token prompt
    arrival: float = 0.0
    result: Any = None
    done: bool = False


@dataclasses.dataclass
class _Batch:
    """A batch dispatched and not yet handed back."""
    n: int                          # dispatch number, the spans' ``batch=``
    reqs: list[Request]
    lens: np.ndarray
    bucket: int
    staged: tuple[np.ndarray, np.ndarray]   # the staging buffer it reads
    out: Any                        # (paths, scores) as the decode returned


class BatchScheduler:
    """Packs requests into padded buckets and runs one batched decode.

    `decode_batch_fn` is either the raw callable contract above, or a
    `core.ViterbiDecoder` — the scheduler then drives its `decode_batch`
    (the decoder owns jit caching per bucket shape and the lengths contract).

    Each `step()` hands back one batch, in dispatch order.  Where the queue
    holds a full batch behind the one it picks and ``max_batch`` requests
    after that, it dispatches that next batch too before it waits, so the
    host pads and dispatches batch n+1 while the device decodes batch n
    (dispatch is asynchronous).  At most two batches are in flight, and
    `step()` returns with one in flight only while ``max_batch`` or more
    requests are queued: a loop that steps while the queue is non-empty,
    or while it holds a full batch and then drains, collects every result.
    ``stats["lookahead"]`` counts the batches dispatched so.

    Batches are padded into host staging buffers kept for each
    ``(bucket, K)`` served, ``(max_batch, bucket, K)`` float32: a batch
    takes the first of its key's buffers that no batch in flight reads,
    and a second is allocated only when a look-ahead batch finds the first
    in flight.  The scheduler holds up to ``2 * max_batch * bucket * K * 4``
    bytes of host memory for each bucket it has served (224 MiB over
    buckets 128/256/512 at batch 64 and K=512; 896 MiB at batch 256).  The
    padded batch handed to `decode_batch_fn` is a leading view of a buffer,
    not written again until that batch's outputs are ready; a callable
    that keeps it past them must copy it.  ``stats["staging_allocs"]``
    counts the buffers allocated, ``stats["staging_reuses"]`` the batches
    padded into one that existed.
    """

    def __init__(self, decode_batch_fn, max_batch: int = 8,
                 buckets: tuple[int, ...] = (128, 256, 512, 1024, 2048)):
        from repro.core import ViterbiDecoder
        if isinstance(decode_batch_fn, ViterbiDecoder):
            decode_batch_fn = decode_batch_fn.decode_batch
        self.fn: Callable = decode_batch_fn
        self.max_batch = max_batch
        self.buckets = sorted(buckets)
        self.queue: deque[Request] = deque()
        self._next_id = itertools.count()
        self._next_batch = itertools.count()
        # (bucket, K) -> up to two (staging buffer, frames last written
        # into each slot)
        self._staging: dict[tuple, list[tuple[np.ndarray, np.ndarray]]] = {}
        self._inflight: deque[_Batch] = deque()     # oldest first
        # frames: real frames decoded; padded_frames: pad frames added to
        # fill buckets (both summed over every batch handed back)
        self.stats = {"batches": 0, "requests": 0, "frames": 0,
                      "padded_frames": 0, "staging_allocs": 0,
                      "staging_reuses": 0, "lookahead": 0}

    def submit(self, payload) -> Request:
        req = Request(rid=next(self._next_id), payload=payload,
                      arrival=time.monotonic())
        self.queue.append(req)
        return req

    def _bucket(self, length: int) -> int:
        for b in self.buckets:
            if length <= b:
                return b
        return self.buckets[-1]

    def step(self) -> list[Request]:
        """Hand back the oldest batch in flight, dispatching one first if
        none is; returns its completed requests.

        Spans (`runtime.spans`, ``batch=`` the batch's dispatch number)
        name the work of each batch in order: ``batch.pad`` (pick and pad;
        ``reused=`` 1 when the staging buffer already existed, else 0),
        ``batch.dispatch`` (enqueue the decode), ``batch.wait`` (until the
        device is done) and ``batch.unpad`` (one copy of paths and scores
        to the host, then per-row host slices).  A look-ahead batch's pad
        and dispatch sit inside one ``batch.ahead``, before the wait of the
        batch in flight.  Each request gets its own copy of its row, so a
        result does not keep the batch's buffer alive.

        Each row is written into its slot of a staging buffer, and only the
        frames a longer earlier row left past its length are zeroed, so
        every pad frame the decoder sees is 0.0.
        """
        if not self._inflight:
            if not self.queue:
                return []
            self._dispatch(next(self._next_batch))
        if self._full_batch_behind():
            n = next(self._next_batch)
            with span("batch.ahead", batch=n):
                self._dispatch(n)
            self.stats["lookahead"] += 1
        return self._collect(self._inflight.popleft())

    def _full_batch_behind(self) -> bool:
        """Whether the queue's head bucket holds a full batch and at least
        ``max_batch`` requests stay queued once it is taken."""
        mb = self.max_batch
        if len(self.queue) < 2 * mb:
            return False
        bucket = self._bucket(len(self.queue[0].payload))
        same = 0
        for r in self.queue:
            same += self._bucket(len(r.payload)) == bucket
            if same == mb:
                return True
        return False

    def _dispatch(self, n: int) -> None:
        """Pick batch `n` from the queue, pad it into a staging buffer no
        batch in flight reads, and enqueue its decode."""
        first = self.queue[0]
        bucket = self._bucket(len(first.payload))
        key = (bucket, first.payload.shape[-1])
        kept = self._staging.setdefault(key, [])
        staged = next((s for s in kept
                       if all(s is not b.staged for b in self._inflight)),
                      None)
        with span("batch.pad", batch=n, reused=int(staged is not None)):
            batch: list[Request] = []
            rest: deque[Request] = deque()
            while self.queue and len(batch) < self.max_batch:
                r = self.queue.popleft()
                if self._bucket(len(r.payload)) == bucket:
                    batch.append(r)
                else:
                    rest.append(r)
            self.queue.extendleft(reversed(rest))

            lens = np.asarray([len(r.payload) for r in batch], np.int32)
            if staged is None:
                staged = (np.zeros((self.max_batch,) + key, np.float32),
                          np.zeros(self.max_batch, np.int32))
                kept.append(staged)
                self.stats["staging_allocs"] += 1
            else:
                self.stats["staging_reuses"] += 1
            buf, filled = staged
            for i, r in enumerate(batch):
                L = lens[i]
                buf[i, :L] = r.payload  # tail masked by the decoder
                if filled[i] > L:
                    buf[i, L:filled[i]] = 0.0
                filled[i] = L
            padded = buf[:len(batch)]
        with span("batch.dispatch", batch=n):
            out = self.fn(padded, lens)
        self._inflight.append(_Batch(n, batch, lens, bucket, staged, out))

    def _collect(self, b: _Batch) -> list[Request]:
        """Wait on batch `b` and fan its outputs out to its requests."""
        with span("batch.wait", batch=b.n):
            jax.block_until_ready(b.out)
        with span("batch.unpad", batch=b.n):
            paths, scores = (np.asarray(x) for x in b.out)
            for i, r in enumerate(b.reqs):
                r.result = (paths[i, :b.lens[i]].copy(), float(scores[i]))
                r.done = True
        frames = int(b.lens.sum())
        self.stats["batches"] += 1
        self.stats["requests"] += len(b.reqs)
        self.stats["frames"] += frames
        self.stats["padded_frames"] += len(b.reqs) * b.bucket - frames
        return b.reqs

    def pad_frac(self) -> float:
        """Pad frames over all frames decoded, frame-weighted over every
        batch so far (0.0 before the first)."""
        total = self.stats["frames"] + self.stats["padded_frames"]
        return self.stats["padded_frames"] / total if total else 0.0

    def flush(self) -> list[Request]:
        """Hand back every batch in flight, oldest first, dispatching
        nothing: for a caller that stops stepping before the queue is
        empty (and then moves the queue elsewhere)."""
        done = []
        while self._inflight:
            done.extend(self._collect(self._inflight.popleft()))
        return done

    def drain(self) -> list[Request]:
        done = []
        while self.queue or self._inflight:
            done.extend(self.step())
        return done


__all__ = ["Request", "BatchScheduler"]
