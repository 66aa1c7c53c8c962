"""The alignment heads behind `BatchScheduler`, checked request by request.

Two paths users call and no other test drives end to end:

* `make_lexicon_align_head`: a lexicon-constrained head for each batchable
  spec.  Exact specs must give every request the path and score, bit for
  bit, of an unbatched decode of the `constrain_inputs`-masked HMM by the
  numpy reference (`core/reference.py`); the FLASH-BS beam must stay within
  the score bound of `test_system.test_alignment_serving_end_to_end`.
* `plan(K, T, budget, batch=)` -> `make_alignment_head` -> `BatchScheduler`:
  budgets that reach five rungs of the planner's ladder, on both of the
  paper's Sec. VII-A HMM families, against the numpy reference, in one
  bucket.

The lexicon head runs over one bucket and over ragged lengths in three
buckets; in both, a later batch puts a shorter row into a staging slot that
a longer row filled.
"""

import jax
import numpy as np
import pytest

from repro.core import (FlashBSSpec, FlashSpec, FusedSpec, LexiconConstraint,
                        ResourceBudget, VanillaSpec, constrain_inputs,
                        erdos_renyi_hmm, left_to_right_hmm, plan,
                        random_emissions)
from repro.core import reference as ref
from repro.serving.alignment import make_alignment_head, make_lexicon_align_head
from repro.serving.scheduler import BatchScheduler

T_MAX = 64
MAX_BATCH = 4
#: ragged lengths, eight requests: two full batches in one bucket of 64, or
#: one to two batches in each of 16, 32 and 64
LENGTHS = (64, 41, 64, 57, 9, 23, 48, 30)
LAYOUTS = {"one_bucket": (64,), "three_buckets": (16, 32, 64)}
#: the mean relative score error a beam may show
#: (`test_system.test_alignment_serving_end_to_end`)
BEAM_BOUND = 0.05


def _bits(x):
    return np.float32(x).tobytes()


def _serve(head, payloads, buckets):
    sched = BatchScheduler(head, max_batch=MAX_BATCH, buckets=buckets)
    reqs = [sched.submit(p) for p in payloads]
    done = sched.drain()
    assert len(done) == len(payloads) and all(r.done for r in reqs)
    for r in reqs:
        assert r.result[0].shape == (len(r.payload),)
    return reqs


def _reference(log_pi, log_A, em):
    """The numpy reference decode of one unpadded request."""
    return ref.viterbi_numpy(np.asarray(log_pi), np.asarray(log_A),
                             np.asarray(em))


def _payloads(K, seed):
    em = np.asarray(random_emissions(jax.random.key(seed),
                                     len(LENGTHS) * T_MAX, K))
    em = em.reshape(len(LENGTHS), T_MAX, K)
    return [em[i, :L] for i, L in enumerate(LENGTHS)]


# ---------------------------------------------------------------------------
# lexicon-constrained forced alignment
# ---------------------------------------------------------------------------

K_LEX = 32
#: three words over disjoint states, one with two pronunciations
WORDS = (((0, 1, 2), (0, 3, 2)), ((4, 5, 6, 7),), ((9, 10),))


@pytest.fixture(scope="module")
def lexicon_problem():
    # edge_prob=1.0: every lexicon arc is a finite transition of the HMM
    hmm = erdos_renyi_hmm(jax.random.key(31), K_LEX, edge_prob=1.0)
    return hmm, _payloads(K_LEX, 32)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("spec", [VanillaSpec(), FlashSpec(parallelism=4),
                                  FusedSpec()],
                         ids=["vanilla", "flash", "fused"])
def test_lexicon_head_bit_identical_to_masked_unbatched(lexicon_problem,
                                                        spec, layout):
    hmm, payloads = lexicon_problem
    head = make_lexicon_align_head(hmm.log_pi, hmm.log_A, WORDS, cfg=spec)
    assert head.constraint == LexiconConstraint(WORDS)
    reqs = _serve(head, payloads, LAYOUTS[layout])
    lex_states = {s for word in WORDS for pron in word for s in pron}
    for r in reqs:
        want_path, want_score = _reference(*constrain_inputs(
            head.constraint, hmm.log_pi, hmm.log_A, r.payload))
        assert np.array_equal(r.result[0], want_path)
        assert _bits(r.result[1]) == _bits(want_score)
        assert set(r.result[0].tolist()) <= lex_states


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_lexicon_head_beam_within_bound(lexicon_problem, layout):
    hmm, payloads = lexicon_problem
    spec = FlashBSSpec(parallelism=4, beam_width=4)
    head = make_lexicon_align_head(hmm.log_pi, hmm.log_A, WORDS, cfg=spec)
    reqs = _serve(head, payloads, LAYOUTS[layout])
    errs = []
    for r in reqs:
        _, opt = _reference(*constrain_inputs(head.constraint, hmm.log_pi,
                                              hmm.log_A, r.payload))
        errs.append(abs(opt - r.result[1]) / abs(opt))
    assert np.mean(errs) < BEAM_BOUND


# ---------------------------------------------------------------------------
# budget -> plan -> head -> scheduler, against the numpy reference
# ---------------------------------------------------------------------------

K_PLAN = 64
#: K=64, T=64, batch 4: exact P=16 needs 63 KiB, P=4 15 KiB, P=1 3 KiB;
#: the B=32 beam 1.5 KiB; the floor (B=16) 0.75 KiB
RUNGS = {
    "exact_P16": (None, FlashSpec(parallelism=16)),
    "exact_P4": (16_000, FlashSpec(parallelism=4)),
    "exact_P1": (4_000, FlashSpec(parallelism=1)),
    "beam_B32": (2_000, FlashBSSpec(parallelism=1, beam_width=32)),
    "floor": (100, FlashBSSpec(parallelism=1, beam_width=16)),
}


@pytest.fixture(scope="module", params=["erdos_renyi", "left_to_right"])
def plan_problem(request):
    key = jax.random.key(41)
    if request.param == "erdos_renyi":
        hmm = erdos_renyi_hmm(key, K_PLAN, num_obs=16)
    else:
        hmm = left_to_right_hmm(key, K_PLAN, 16)
    return hmm, _payloads(K_PLAN, 42)


@pytest.mark.parametrize("rung", sorted(RUNGS))
def test_planned_head_matches_reference(plan_problem, rung):
    hmm, payloads = plan_problem
    budget, want_spec = RUNGS[rung]
    decode_plan = plan(K_PLAN, T_MAX, ResourceBudget(memory_bytes=budget),
                       batch=MAX_BATCH)
    assert decode_plan.spec == want_spec
    head = make_alignment_head(hmm.log_pi, hmm.log_A, decode_plan.spec)
    reqs = _serve(head, payloads, LAYOUTS["one_bucket"])
    errs = []
    for r in reqs:
        ref_path, ref_score = _reference(hmm.log_pi, hmm.log_A, r.payload)
        if isinstance(want_spec, FlashSpec):
            assert np.array_equal(r.result[0], ref_path)
            assert _bits(r.result[1]) == _bits(ref_score)
        errs.append(abs(ref_score - r.result[1]) / abs(ref_score))
    assert np.mean(errs) < BEAM_BOUND
