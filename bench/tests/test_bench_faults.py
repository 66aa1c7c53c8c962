"""A whole run with the timed path broken underneath comes out not correct.

Each test drives `run.run_cell` on the CPU at a tiny size (the look for a
chip skipped) and plants one fault in the program after set-up:

* offline: a path altered where the decode produces it; half of each
  batch left out (those rows' paths and scores never filled in);
* stream: the slot step returning its state unchanged; a path altered
  where `finish` produces it.

The sharded offline cell has no exchange between chips to leave out: its
rows are decoded independently on each chip (no collective runs).
"""

import time

import numpy as np
import pytest

import run
from lib import registry


def _run(data_dir, config, mix, wrap=None, cell="default_k512.offline"):
    bench = registry.load_benchmark()
    return run.run_cell(
        {"name": cell, "chips": 1},
        registry.load_config(config, data_dir),
        registry.load_traffic(mix, data_dir),
        registry.metrics_for(bench, cell, "end_to_end"), [],
        seed=2147483650, seconds=1.0, trace=False, require_tpu=False,
        t_start=time.perf_counter(), wrap_program=wrap)[0]


def _wrap_decode(broken):
    def wrap(client):
        fn = client.sched.fn

        def decode(padded, lens):
            paths, scores = fn(padded, lens)
            paths, scores = np.array(paths), np.array(scores)
            broken(paths, scores, lens)
            return paths, scores

        client.sched.fn = decode
    return wrap


def _alter_one(paths, scores, lens):
    t = int(lens[0]) // 2
    paths[0, t] = (paths[0, t] + 1) % 128


def _drop_half(paths, scores, lens):
    half = len(lens) // 2
    paths[half:] = 0
    scores[half:] = 0.0


def test_sound_offline_run_is_correct(data_dir):
    assert _run(data_dir, "tiny_er", "tiny_offline")["correct"]


@pytest.mark.parametrize("fault", [_alter_one, _drop_half],
                         ids=["answer_altered", "half_batch_left_out"])
def test_offline_fault_is_not_correct(data_dir, fault):
    res = _run(data_dir, "tiny_er", "tiny_offline", _wrap_decode(fault))
    assert not res["correct"], res["checks"]


def test_stream_step_returning_its_state_unchanged_is_not_correct(
        data_dir, monkeypatch):
    import jax.numpy as jnp
    from repro.serving import inflight

    def stuck(log_pi, log_A, em0, fresh, em, delta, nfeed, *, bt=8):
        S, B, K = em.shape
        ident = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32), (S, B, K))
        return ident, delta

    def wrap(client):
        monkeypatch.setattr(inflight, "_inflight_step", stuck)

    res = _run(data_dir, "tiny_er", "tiny_stream", wrap,
               cell="default_k512.stream")
    assert not res["correct"], res["checks"]


def test_stream_answer_altered_is_not_correct(data_dir):
    def wrap(client):
        finish = client.sched.finish

        def altered(sid):
            path, score = finish(sid)
            path = path.copy()
            path[len(path) // 2] = (path[len(path) // 2] + 1) % 128
            return path, score

        client.sched.finish = altered

    res = _run(data_dir, "tiny_er", "tiny_stream", wrap,
               cell="default_k512.stream")
    assert not res["correct"], res["checks"]
