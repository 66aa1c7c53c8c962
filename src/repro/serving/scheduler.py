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


class BatchScheduler:
    """Packs requests into padded buckets and runs one batched decode.

    `decode_batch_fn` is either the raw callable contract above, or a
    `core.ViterbiDecoder` — the scheduler then drives its `decode_batch`
    (the decoder owns jit caching per bucket shape and the lengths contract).

    Batches are padded into a host staging buffer kept for each
    ``(bucket, K)`` served, ``(max_batch, bucket, K)`` float32, allocated the
    first time that bucket is padded and reused by every later batch of it:
    the scheduler holds up to ``max_batch * bucket * K * 4`` bytes of host
    memory for each bucket it has served.  The padded batch handed to
    `decode_batch_fn` is a leading view of that buffer and is valid only for
    the duration of the call; a callable that keeps it must copy it.
    ``stats["staging_allocs"]`` counts the buffers allocated,
    ``stats["staging_reuses"]`` the batches padded into one that existed.
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
        # (bucket, K) -> (staging buffer, frames last written into each slot)
        self._staging: dict[tuple, tuple[np.ndarray, np.ndarray]] = {}
        # frames: real frames decoded; padded_frames: pad frames added to
        # fill buckets (both summed over every batch)
        self.stats = {"batches": 0, "requests": 0, "frames": 0,
                      "padded_frames": 0, "staging_allocs": 0,
                      "staging_reuses": 0}

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
        """Run one batch; returns completed requests.

        Four spans (`runtime.spans`, ``batch=`` the batch number) name the
        work in order: ``batch.pad`` (pick and pad; ``reused=`` 1 when the
        bucket's staging buffer already existed, else 0), ``batch.dispatch``
        (enqueue the decode), ``batch.wait`` (until the device is done)
        and ``batch.unpad`` (one copy of paths and scores to the host,
        then per-row host slices).  Each request gets its own copy of its
        row, so a result does not keep the batch's buffer alive.

        Each row is written into its slot of the bucket's staging buffer,
        and only the frames a longer earlier row left past its length are
        zeroed, so every pad frame the decoder sees is 0.0.  The buffer is
        written again only after ``batch.wait``, when the outputs, which
        depend on it, are ready.
        """
        if not self.queue:
            return []
        n = self.stats["batches"]
        first = self.queue[0]
        bucket = self._bucket(len(first.payload))
        key = (bucket, first.payload.shape[-1])
        staged = self._staging.get(key)
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
                staged = self._staging[key] = (
                    np.zeros((self.max_batch,) + key, np.float32),
                    np.zeros(self.max_batch, np.int32))
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
            paths, scores = self.fn(padded, lens)
        with span("batch.wait", batch=n):
            jax.block_until_ready((paths, scores))
        with span("batch.unpad", batch=n):
            paths, scores = np.asarray(paths), np.asarray(scores)
            for i, r in enumerate(batch):
                r.result = (paths[i, :lens[i]].copy(), float(scores[i]))
                r.done = True
        frames = int(lens.sum())
        self.stats["batches"] += 1
        self.stats["requests"] += len(batch)
        self.stats["frames"] += frames
        self.stats["padded_frames"] += padded.shape[0] * bucket - frames
        return batch

    def pad_frac(self) -> float:
        """Pad frames over all frames decoded, frame-weighted over every
        batch so far (0.0 before the first)."""
        total = self.stats["frames"] + self.stats["padded_frames"]
        return self.stats["padded_frames"] / total if total else 0.0

    def drain(self) -> list[Request]:
        done = []
        while self.queue:
            done.extend(self.step())
        return done


__all__ = ["Request", "BatchScheduler"]
