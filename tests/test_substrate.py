"""Substrate tests: optimizer, compression, checkpointing, fault tolerance,
elastic planning, data pipeline determinism, serving scheduler."""


import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.optim import (AdamWConfig, init_state, update, schedule,
                         zero1_specs, dequantize, ef_accumulate)
from repro.checkpointing.manager import CheckpointManager
from repro.checkpointing.elastic import plan_rescale, abstract_target_mesh
from repro.runtime.fault import (HeartbeatMonitor, StragglerDetector,
                                 SupervisedLoop)
from repro.data.pipeline import SyntheticTokenPipeline, TokenPipelineConfig


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_reduces_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1,
                      total_steps=100)
    params = {"w": jnp.array([3.0, -2.0])}
    state = init_state(params)
    for _ in range(50):
        grads = {"w": 2 * params["w"]}       # d/dw (w^2)
        params, state, m = update(cfg, grads, state, params)
    assert float(jnp.sum(jnp.square(params["w"]))) < 0.2


def test_schedule_warmup_and_decay():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    assert float(schedule(cfg, 5)) == pytest.approx(0.5)
    assert float(schedule(cfg, 10)) == pytest.approx(1.0)
    assert float(schedule(cfg, 100)) == pytest.approx(0.1, rel=1e-3)


def test_zero1_specs_shard_largest_free_axis():
    specs = {"w": P(None, "model"), "b": P()}
    shapes = {"w": jax.ShapeDtypeStruct((64, 128), jnp.float32),
              "b": jax.ShapeDtypeStruct((7,), jnp.float32)}
    out = zero1_specs(specs, shapes, ("data",), data_size=16)
    assert out["w"] == P("data", "model")
    assert tuple(out["b"]) in ((), (None,))   # 7 not divisible: replicated


def test_compression_error_feedback_converges():
    """Accumulating N identical grads through int8+EF loses < 1% of the sum."""
    g = jax.random.normal(jax.random.key(0), (256,)) * 1e-3
    q = jnp.zeros((256,), jnp.int8)
    scale = jnp.zeros(())
    res = jnp.zeros((256,))
    for _ in range(16):
        q, scale, res = ef_accumulate(q, scale, res, g)
    acc = dequantize(q, scale) + res
    np.testing.assert_allclose(np.asarray(acc), np.asarray(16 * g), rtol=1e-2,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# checkpointing + fault tolerance
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
             "step": jnp.asarray(3)}
    for s in (10, 20, 30):
        mgr.save(s, state, blocking=True)
    assert mgr.all_steps() == [20, 30]        # keep=2 GC'd step 10
    restored = mgr.restore(30, state)
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(state["params"]["w"]))


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.zeros((2, 2))}, blocking=True)
    with pytest.raises(ValueError):
        mgr.restore(1, {"w": jnp.zeros((3, 3))})


def test_supervised_loop_restarts_from_checkpoint(tmp_path):
    """Inject a failure mid-run; the loop restores and replays identically."""
    mgr = CheckpointManager(str(tmp_path), keep=5)
    calls = {"n": 0}

    def step_fn(state, batch):
        return {"x": state["x"] + batch}, {"loss": state["x"]}

    def chaos(step):
        calls["n"] += 1
        if step == 7 and not calls.get("failed"):   # fail once at step 7
            calls["failed"] = True
            raise RuntimeError("injected node failure")

    loop = SupervisedLoop(step_fn, {"x": jnp.asarray(0.0)}, mgr,
                          batch_fn=lambda s: jnp.asarray(1.0),
                          ckpt_every=5, chaos=chaos)
    state, log = loop.run(0, 10)
    assert loop.restarts == 1
    assert float(state["x"]) == 10.0          # exact replay after restore


def test_heartbeat_and_straggler():
    clock = {"t": 0.0}
    hb = HeartbeatMonitor(3, timeout_s=5.0, clock=lambda: clock["t"])
    clock["t"] = 3.0
    hb.beat(0), hb.beat(1)
    clock["t"] = 7.0
    assert hb.dead_workers() == [2]

    sd = StragglerDetector(num_workers=4, factor=3.0)
    for w in range(4):
        for _ in range(4):
            sd.record(w, 1.0)
    sd.record(2, 9.0)
    assert sd.stragglers() == [2]


def test_elastic_plan_rescale():
    # abstract target mesh: plan_rescale only reads shapes (1-device test
    # host); constructed through the jaxcompat shim, the one call site for
    # AbstractMesh's constructor
    mesh_ok = abstract_target_mesh((2, 2), ("data", "model"))
    shapes = {"w": jax.ShapeDtypeStruct((64, 128), jnp.float32)}
    specs = {"w": P("data", "model")}
    assert plan_rescale(shapes, specs, mesh_ok) == []
    shapes_bad = {"w": jax.ShapeDtypeStruct((63, 128), jnp.float32)}
    assert len(plan_rescale(shapes_bad, specs, mesh_ok)) == 1


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_pipeline_deterministic_and_resumable():
    cfg = TokenPipelineConfig(vocab=100, seq_len=16, global_batch=4, seed=3)
    p1, p2 = SyntheticTokenPipeline(cfg), SyntheticTokenPipeline(cfg)
    for step in (0, 5, 17):
        b1, b2 = p1.batch(step), p2.batch(step)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(p1.batch(1)["tokens"], p1.batch(2)["tokens"])


def test_pipeline_labels_are_shifted_tokens():
    cfg = TokenPipelineConfig(vocab=50, seq_len=8, global_batch=2, seed=0)
    b = SyntheticTokenPipeline(cfg).batch(0)
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])
    assert (b["mask"][:, -1] == 0).all()


# ---------------------------------------------------------------------------
# serving scheduler
# ---------------------------------------------------------------------------

def test_batch_scheduler_buckets_and_results():
    from repro.serving.scheduler import BatchScheduler

    def fake_decode(batch, lengths):          # (B, T, K), (B,) -> paths, scores
        B, T, K = batch.shape
        assert lengths.shape == (B,)
        return np.zeros((B, T), np.int32), np.arange(B, dtype=np.float32)

    sched = BatchScheduler(fake_decode, max_batch=3, buckets=(64, 128))
    reqs = [sched.submit(np.zeros((50, 8), np.float32)) for _ in range(4)]
    reqs += [sched.submit(np.zeros((100, 8), np.float32))]
    done = sched.drain()
    assert len(done) == 5 and all(r.done for r in reqs)
    assert all(r.result[0].shape[0] == len(r.payload) for r in reqs)
    assert sched.stats["batches"] >= 2        # two buckets at least
