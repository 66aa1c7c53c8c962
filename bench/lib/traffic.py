"""The one traffic generator: reads a mix's parameters, returns its schedule.

A traffic mix is a JSON file under ``bench/traffic/``.  Every seed gets the
same set of sequence lengths and the same set of inter-arrival gaps, in a
different order, so the work in a window does not change with the seed.

Pool: ``lengths`` (each value ``pool_per_length`` times) or ``length_range``
[lo, hi] (``pool`` values spread evenly over it).  Pool entry i has length
``pool_lengths(mix)[i]``; the generator hands out pool indices.

* ``"kind": "closed"`` — a batch client that keeps ``queue_batches *
  max_batch`` requests queued and submits one new request per completed one,
  cycling through the pool in a seed-permuted order.
* ``"kind": "open"`` — sessions arrive on the wall clock at a mean of
  ``rate_per_s``, with the gaps of the arrival process that
  ``arrivals.process`` names (``bench/arrivals/<process>.py``, its
  parameters beside the name); each streams its frames in chunks of
  ``chunk_frames`` at ``frames_per_s``; a chunk is due when its last frame
  has been captured.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from . import registry


#: the keys of a mix that set its pool
POOL_KEYS = ("lengths", "pool_per_length", "length_range", "pool")


def pool_lengths(mix: dict) -> np.ndarray:
    if "lengths" in mix:
        return np.repeat(np.asarray(mix["lengths"], np.int64),
                         int(mix["pool_per_length"]))
    lo, hi = mix["length_range"]
    return np.rint(np.linspace(lo, hi, int(mix["pool"]))).astype(np.int64)


def closed_order(n_pool: int, seed: int) -> np.ndarray:
    """The pool indices a closed-loop client submits, cycled forever."""
    return np.random.default_rng(seed).permutation(n_pool)


@dataclasses.dataclass(frozen=True)
class Chunk:
    due: float          # seconds on the schedule clock
    session: int
    lo: int             # frames [lo, hi) of the session's sequence
    hi: int
    first: bool
    last: bool


@dataclasses.dataclass(frozen=True)
class Session:
    arrival: float
    pool_index: int
    length: int

    def last_due(self, frames_per_s: float) -> float:
        return self.arrival + self.length / frames_per_s


def arrival_gaps(arrivals: dict, n: int, rate: float, rng,
                 base=registry.BENCH) -> np.ndarray:
    """n gaps of the arrival process a mix names, at mean rate `rate`."""
    proc = registry.load_module("arrivals", arrivals["process"], base)
    registry.check_keys(f"arrival process {arrivals['process']!r}",
                        arrivals, ("process",) + tuple(proc.PARAMS))
    return proc.gaps(n, rate, rng, **{k: arrivals[k] for k in proc.PARAMS})


def open_schedule(mix: dict, seed: int, duration_s: float,
                  rate: float | None = None,
                  base=registry.BENCH) -> list[Session]:
    """Sessions arriving in [0, duration_s) at `rate` (default the mix's)."""
    rate = float(mix["rate_per_s"] if rate is None else rate)
    lengths = pool_lengths(mix)
    n = max(1, int(math.ceil(rate * duration_s)))
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(arrival_gaps(mix["arrivals"], n, rate, rng, base))
    # every length equally often, in a seed-permuted order; pool entries of
    # one length are used in turn
    picks = rng.permutation(np.resize(np.arange(lengths.size), n))
    return [Session(float(a), int(p), int(lengths[p]))
            for a, p in zip(arrivals, picks) if a < duration_s]


def chunk_schedule(mix: dict, sessions: list[Session]) -> list[Chunk]:
    """Every chunk of every session, in due order."""
    c = int(mix["chunk_frames"])
    fps = float(mix["frames_per_s"])
    out = []
    for i, s in enumerate(sessions):
        for lo in range(0, s.length, c):
            hi = min(lo + c, s.length)
            out.append(Chunk(s.arrival + hi / fps, i, lo, hi, lo == 0,
                             hi == s.length))
    out.sort(key=lambda ch: (ch.due, ch.session))
    return out
