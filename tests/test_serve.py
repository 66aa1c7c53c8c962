"""The serving driver (`launch/serve.py`) run in process, end to end.

Each case runs `serve.main(argv)`: a left-to-right HMM, the alignment head,
`BatchScheduler` over buckets 128/256/512, seeded requests, then the drain.
The spec comes either from ``--method`` or from the planner through
``--budget-kb``; the budgets below reach four rungs of its ladder.  Every
request must come back whole, and exact specs must decode every request to
the path and score of the numpy reference (`core/reference.py`), bit for
bit.
"""

import jax
import numpy as np
import pytest

from repro.core import FlashBSSpec, FlashSpec
from repro.core import reference as ref
from repro.launch import serve
from repro.runtime import compile_cache as cc

STATES = 64
#: lengths 384, 384 and 96 at seed 3: buckets 512 and 128
REQUESTS = 3
MAX_BATCH = 4


@pytest.fixture
def quiet_cache(monkeypatch, tmp_path):
    """`main` enables the compile cache; keep it out of the checkout and
    leave jax's cache settings as they were."""
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])


def _serve(*args):
    return serve.main(["--states", str(STATES), "--requests", str(REQUESTS),
                       "--max-batch", str(MAX_BATCH), "--seed", "3", *args])


def _check_served(res):
    assert len(res.done) == REQUESTS and all(r.done for r in res.done)
    assert res.stats["requests"] == REQUESTS
    for r in res.done:
        path, score = r.result
        assert path.shape == (len(r.payload),)
        assert ((path >= 0) & (path < STATES)).all()
        assert np.isfinite(score)


def _check_exact(res):
    lp, la = np.asarray(res.hmm.log_pi), np.asarray(res.hmm.log_A)
    for r in res.done:
        want_path, want_score = ref.viterbi_numpy(lp, la, r.payload)
        assert np.array_equal(r.result[0], want_path)
        assert np.float32(r.result[1]).tobytes() == \
            np.float32(want_score).tobytes()


@pytest.mark.parametrize("method", ["vanilla", "flash", "fused"])
def test_serve_exact_method_decodes_every_request_exactly(method,
                                                          quiet_cache):
    res = _serve("--method", method)
    assert res.spec.method == method
    _check_served(res)
    _check_exact(res)


# K=64, batch 4, T=512: exact P=16 needs 63 KiB, exact P=2 7 KiB, exact
# P=1 3 KiB, the B=32 beam 1.5 KiB and the floor (B=16) 0.75 KiB
@pytest.mark.parametrize("budget_kb, rung", [
    ("64", FlashSpec(parallelism=16)),
    ("8", FlashSpec(parallelism=2)),
    ("2", FlashBSSpec(parallelism=1, beam_width=32)),
    ("0.5", FlashBSSpec(parallelism=1, beam_width=16)),
], ids=["exact_P16", "exact_P2", "beam_B32", "floor"])
def test_serve_budget_picks_its_rung_and_serves_every_request(
        budget_kb, rung, quiet_cache):
    res = _serve("--budget-kb", budget_kb)
    assert res.spec == rung
    _check_served(res)
    if isinstance(rung, FlashSpec):
        _check_exact(res)
