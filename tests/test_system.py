"""End-to-end behaviour tests: the paper's serving pipeline."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (left_to_right_hmm, erdos_renyi_hmm,
                        viterbi_vanilla, relative_error)
from repro.serving.alignment import AlignmentConfig, make_alignment_head
from repro.serving.scheduler import BatchScheduler


def test_alignment_serving_end_to_end():
    """Encoder-emissions -> FLASH-BS alignment through the batch scheduler,
    validated against exact Viterbi (paper Fig. 9 style).  Ragged lengths are
    masked by the batched decoder, so the only error source is the beam."""
    key = jax.random.key(0)
    k1, k2 = jax.random.split(key)
    hmm = left_to_right_hmm(k1, 64, 16)
    head = make_alignment_head(hmm.log_pi, hmm.log_A,
                               AlignmentConfig(method="flash_bs",
                                               beam_width=48, parallelism=4))
    sched = BatchScheduler(head, max_batch=4, buckets=(64,))
    rng = np.random.default_rng(0)
    lens = [64, 40, 64, 25, 64, 52]
    reqs = [sched.submit(rng.standard_normal((t, 64)).astype(np.float32))
            for t in lens]
    done = sched.drain()
    assert len(done) == 6
    errs = []
    for r in done:
        em = jnp.asarray(r.payload)
        assert r.result[0].shape == (len(r.payload),)
        _, opt = viterbi_vanilla(hmm.log_pi, hmm.log_A, em)
        errs.append(float(relative_error(opt, r.result[1])))
    assert np.mean(errs) < 0.05  # B=48/64 beam on random emissions


@pytest.mark.parametrize("buckets", [(48,), (16, 32, 48)],
                         ids=["one_bucket", "three_buckets"])
@pytest.mark.parametrize("method", ["vanilla", "flash", "fused"])
def test_scheduler_bit_identical_to_unbatched(method, buckets):
    """Regression for the padded-batch corruption bug: with an exact method,
    every scheduled request's path AND score must be bit-identical to an
    unbatched decode of its unpadded payload — bucket pad frames run as
    tropical-identity steps, never as real DP transitions.  In either
    bucket layout a later batch puts shorter rows into slots that longer
    rows filled before, so the decoder must treat the stale tails of the
    reused staging buffer as pad."""
    key = jax.random.key(2)
    k1, _ = jax.random.split(key)
    hmm = erdos_renyi_hmm(k1, 32, edge_prob=0.4)
    head = make_alignment_head(hmm.log_pi, hmm.log_A,
                               AlignmentConfig(method=method))
    sched = BatchScheduler(head, max_batch=4, buckets=buckets)
    rng = np.random.default_rng(1)
    lens = [48, 47, 16, 48, 15, 40, 33, 9, 35, 1, 3, 20]
    reqs = [sched.submit(rng.standard_normal((t, 32)).astype(np.float32))
            for t in lens]
    done = sched.drain()
    assert len(done) == len(lens) and all(r.done for r in reqs)
    assert sched.stats["staging_reuses"] > 0
    for r in done:
        em = jnp.asarray(r.payload)
        opt_path, opt_score = viterbi_vanilla(hmm.log_pi, hmm.log_A, em)
        assert np.array_equal(r.result[0], np.asarray(opt_path))
        assert np.float32(r.result[1]).tobytes() == \
            np.float32(opt_score).tobytes()
