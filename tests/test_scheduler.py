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
