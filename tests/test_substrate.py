"""Substrate tests: checkpointing, fault tolerance, elastic planning and the
serving scheduler's bucketing."""


import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpointing.manager import CheckpointManager
from repro.checkpointing.elastic import plan_rescale, abstract_target_mesh
from repro.runtime.fault import (HeartbeatMonitor, StragglerDetector,
                                 SupervisedLoop)


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
