"""The comparison that decides `correct`: the reference, and its control.

The control is the reference computed in bfloat16 (the precision below the
float32 the configurations state) put in the program's place; with each
configuration's own limits it has to come out as not correct.  Run here at
the configurations' own K with a few short sequences.
"""

import numpy as np
import pytest

from lib import registry
from lib.check import judge, reference_for, verdict
from lib.hmm import make_inputs
from lib.reference import path_score_numpy, viterbi_numpy


FAMILY = {"erdos_renyi": {"edge_prob": 0.3},
          "left_to_right": {"self_loop": 0.6, "max_skip": 2}}


def _tiny(kind="erdos_renyi", K=24, seed=3, n=4, T=20):
    cfg = {"hmm": kind, "num_states": K, "num_obs": 6, "seq_len": T,
           "dtype": "float32", **FAMILY[kind]}
    log_pi, log_A, pool = make_inputs(cfg, seed, n, T)
    lengths = np.asarray([T, T - 3, 5, 1][:n])
    return log_pi, log_A, pool, lengths


@pytest.mark.parametrize("kind", ["erdos_renyi", "left_to_right"])
def test_device_reference_matches_numpy(kind):
    log_pi, log_A, pool, lengths = _tiny(kind)
    ref = reference_for(log_pi, log_A, pool, lengths, range(len(lengths)))
    lp, la = np.asarray(log_pi), np.asarray(log_A)
    for i, L in enumerate(lengths):
        path, score = viterbi_numpy(lp, la, pool[i, :L])
        assert np.array_equal(ref[i][0], path)
        assert ref[i][1] == pytest.approx(score, rel=1e-6)
        assert path_score_numpy(lp, la, pool[i, :L], path) == pytest.approx(
            score, rel=1e-6)


def test_judge_passes_the_reference_and_catches_altered_answers():
    log_pi, log_A, pool, lengths = _tiny()
    ref = reference_for(log_pi, log_A, pool, lengths, range(len(lengths)))
    limits = {"path_gap": 1e-5, "score_err": 1e-5, "malformed": 0}
    exact = [(i, p, s) for i, (p, s) in ref.items()]
    ok, checks = verdict(judge(exact, ref, log_pi, log_A, pool, lengths),
                         limits)
    assert ok and checks["path_gap"]["value"] == 0
    path = ref[0][0].copy()
    path[5] = (path[5] + 1) % 24
    bad_path = [(0, path, ref[0][1])] + exact[1:]
    assert not verdict(judge(bad_path, ref, log_pi, log_A, pool, lengths),
                       limits)[0]
    bad_score = [(0, ref[0][0], ref[0][1] + 1.0)] + exact[1:]
    r = judge(bad_score, ref, log_pi, log_A, pool, lengths)
    assert r["score_err"] > 1e-5 and not verdict(r, limits)[0]
    short = [(0, ref[0][0][:-1], ref[0][1])] + exact[1:]
    r = judge(short, ref, log_pi, log_A, pool, lengths)
    assert r["malformed"] == 1 and not verdict(r, limits)[0]
    assert not verdict(judge([], ref, log_pi, log_A, pool, lengths),
                       limits)[0]


@pytest.mark.parametrize("config", ["default_k512", "align_k3965"])
def test_bf16_control_is_not_correct_under_the_config_limits(config):
    cfg = registry.load_config(config)
    n, T = (4, 64) if cfg["num_states"] <= 512 else (2, 48)
    log_pi, log_A, pool = make_inputs(cfg, 2147483999, n, T)
    lengths = np.full((n,), T)
    ref = reference_for(log_pi, log_A, pool, lengths, range(n))
    limits = dict(cfg["limits"], malformed=0)
    exact = [(i, p, s) for i, (p, s) in ref.items()]
    assert verdict(judge(exact, ref, log_pi, log_A, pool, lengths),
                   limits)[0]
    ctl = reference_for(log_pi, log_A, pool, lengths, range(n), mode="bf16")
    answers = [(i, p, s) for i, (p, s) in ctl.items()]
    ok, checks = verdict(judge(answers, ref, log_pi, log_A, pool, lengths),
                         limits)
    assert not ok, checks
