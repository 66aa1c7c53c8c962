"""Distributed tests (8 fake devices, run in a subprocess so the forced device
count never leaks into other tests' jax runtime).

Mesh construction and every shard_map go through `repro.runtime.jaxcompat`,
the one call site for jax's mesh API.  CI runs this file in a dedicated step with
``--xla_force_host_platform_device_count=8`` (``make test-dist``)."""

import json
import os
import subprocess
import sys

import pytest

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np, jax, jax.numpy as jnp
from repro.core import (erdos_renyi_hmm, random_emissions, viterbi_decode,
                        viterbi_decode_batch)
from repro.core import reference as ref
from repro.core.distributed import make_flash_viterbi_2d, make_batched_flash_decoder
from repro.launch.mesh import make_test_mesh, data_axis_size
from repro.launch.steps import build_cell, lower_cell
from repro.configs import get_arch
from repro.sharding.rules import SINGLE_POD_RULES
from repro.train import TrainConfig, init_train_state, make_train_step, train_state_specs
from jax.sharding import NamedSharding, PartitionSpec as P

out = {}

# 0. mesh construction through the compat shim (once an import-time
#    regression, when a newer-jax symbol was spelled at the call site)
mesh = make_test_mesh()   # (4, 2) data x model
out["mesh_import_and_build"] = (len(mesh.devices.ravel()) == 8 and
                                data_axis_size(mesh) == 4)
mesh_mp = make_test_mesh(multi_pod=True)
out["mesh_multipod_build"] = dict(mesh_mp.shape) == {"pod": 2, "data": 2,
                                                     "model": 2}

# 1. 2-D sharded FLASH viterbi is exact in both model-axis layouts, and the
#    row/col layouts agree with each other
K, T = 64, 96
k1, k2 = jax.random.split(jax.random.key(3))
hmm = erdos_renyi_hmm(k1, K, edge_prob=0.4)
em = random_emissions(k2, T, K)
npath, nscore = ref.viterbi_numpy(np.asarray(hmm.log_pi), np.asarray(hmm.log_A), np.asarray(em))
paths2d = {}
for shard in ("row", "col"):
    dec = make_flash_viterbi_2d(mesh, T, K, shard=shard)
    path, score = dec(hmm.log_pi, hmm.log_A, em)
    paths2d[shard] = np.asarray(path)
    out[f"viterbi_2d_{shard}_exact"] = bool(np.array_equal(np.asarray(path), npath)) and \
        abs(float(score) - nscore) < 1e-3 * abs(nscore)
out["viterbi_2d_row_col_agree"] = bool(np.array_equal(paths2d["row"], paths2d["col"]))

# 2. sharded ragged batched decode is bit-identical to looped unbatched
#    decodes, for every serving method
B, TMAX = 8, 40
lengths = np.array([TMAX, 17, 1, 33, TMAX, 9, 25, 2], np.int32)
emb = random_emissions(jax.random.key(7), B * TMAX, K).reshape(B, TMAX, K)
for method in ("vanilla", "flash", "fused"):
    bdec = make_batched_flash_decoder(mesh, method=method)
    paths, scores = bdec(hmm.log_pi, hmm.log_A, emb, jnp.asarray(lengths))
    ok = True
    for i, L in enumerate(lengths):
        p, s = viterbi_decode(emb[i, :int(L)], hmm.log_pi, hmm.log_A,
                              method="vanilla")
        ok = ok and bool(np.array_equal(np.asarray(paths[i, :int(L)]),
                                        np.asarray(p)))
        ok = ok and bool(np.isclose(float(scores[i]), float(s), rtol=1e-6))
    out[f"batched_{method}_ragged_bit_identical"] = ok

# 3. viterbi_decode_batch(mesh=...) is bit-identical to the single-device call
ps, ss = viterbi_decode_batch(emb, hmm.log_pi, hmm.log_A, jnp.asarray(lengths),
                              method="flash", mesh=mesh)
p0, s0 = viterbi_decode_batch(emb, hmm.log_pi, hmm.log_A, jnp.asarray(lengths),
                              method="flash")
out["sharded_batch_bit_identical"] = bool(np.array_equal(np.asarray(ps), np.asarray(p0))) \
    and bool(np.array_equal(np.asarray(ss), np.asarray(s0)))

# 4. serving alignment head shards a non-divisible bucket (pads with dummies)
from repro.serving.alignment import AlignmentConfig, make_alignment_head
head = make_alignment_head(hmm.log_pi, hmm.log_A,
                           AlignmentConfig(method="flash"), mesh=mesh)
ems5 = emb[:5]
lens5 = jnp.asarray(lengths[:5])
hp, hs = head(ems5, lens5)
ok = hp.shape == (5, TMAX) and hs.shape == (5,)
for i in range(5):
    L = int(lengths[i])
    p, s = viterbi_decode(emb[i, :L], hmm.log_pi, hmm.log_A, method="flash",
                          lanes=None)
    ok = ok and bool(np.array_equal(np.asarray(hp[i, :L]), np.asarray(p)))
    ok = ok and bool(np.isclose(float(hs[i]), float(s), rtol=1e-6))
out["alignment_head_sharded_exact"] = ok

# 4b. the offline serving path on a data placement (a 4-device sub-mesh):
#     BatchScheduler over the placed head, ragged lengths over two buckets,
#     partial batches that need dummy rows
from repro.core import DataPlacement, FusedSpec, ViterbiDecoder
from repro.serving.scheduler import BatchScheduler
Kp = 128
hmm_p = erdos_renyi_hmm(jax.random.key(11), Kp, edge_prob=0.3)
lp_np, la_np = np.asarray(hmm_p.log_pi), np.asarray(hmm_p.log_A)
placed = DataPlacement.over_devices(FusedSpec(), 4)
head_p = make_alignment_head(hmm_p.log_pi, hmm_p.log_A, placed)
lens_p = [16, 9, 31, 32, 5, 20, 32, 17, 12, 27, 3]     # buckets 16 and 32
ems_p = np.asarray(random_emissions(jax.random.key(12), len(lens_p) * 32,
                                    Kp)).reshape(len(lens_p), 32, Kp)
puts = []
real_put = jax.device_put
def spy_put(x, *a, **kw):
    y = real_put(x, *a, **kw)
    puts.append((x, y))
    return y
jax.device_put = spy_put
try:
    sched_p = BatchScheduler(head_p, max_batch=4, buckets=(16, 32))
    reqs = [sched_p.submit(ems_p[i, :L]) for i, L in enumerate(lens_p)]
    dummies = 0
    while sched_p.queue:
        dummies += -len(sched_p.step()) % 4
finally:
    jax.device_put = real_put
one_chip = ViterbiDecoder(FusedSpec(), hmm_p.log_pi, hmm_p.log_A)
identical = reference_ok = True
for bucket in (16, 32):
    idx = [i for i, L in enumerate(lens_p) if (L <= 16) == (bucket == 16)]
    lens_b = np.asarray([lens_p[i] for i in idx], np.int32)
    pb, sb = one_chip.decode_batch(ems_p[idx, :bucket], lens_b)
    pb, sb = np.asarray(pb), np.asarray(sb)
    for j, i in enumerate(idx):
        L = lens_p[i]
        path, score = reqs[i].result
        identical = identical and bool(np.array_equal(path, pb[j, :L])) \
            and np.float32(score).tobytes() == sb[j].tobytes()
        rpath, rscore = ref.viterbi_numpy(lp_np, la_np, ems_p[i, :L])
        reference_ok = reference_ok and bool(np.array_equal(path, rpath)) \
            and abs(score - rscore) <= 1e-5 * abs(rscore)
out["placed_scheduler_bit_identical"] = identical and all(r.done for r in reqs)
out["placed_scheduler_matches_reference"] = reference_ok
# each put of the batch goes from the host straight to the four devices,
# every device holding its own rows
em_puts = [(x, y) for x, y in puts if getattr(x, "ndim", 0) == 3]
on_own = bool(em_puts)
for x, y in em_puts:
    shards = sorted(y.addressable_shards, key=lambda sh: sh.index[0].start)
    rows = x.shape[0] // 4
    on_own = on_own and isinstance(x, np.ndarray) \
        and len({sh.device for sh in shards}) == 4 \
        and all(np.array_equal(np.asarray(sh.data), x[k * rows:(k + 1) * rows])
                for k, sh in enumerate(shards))
out["placed_put_each_shard_on_its_device"] = on_own
stats_p = head_p.decoder.stats
out["placed_dummy_rows_counted"] = (dummies > 0
                                    and stats_p["dummy_rows"] == dummies
                                    and stats_p["sharded_batches"]
                                    == len(em_puts))

# 5. smoke train step actually runs SPMD on the test mesh (not just lowers)
cfg = get_arch("tinyllama_1_1b").SMOKE
from repro.models import build_model
model = build_model(cfg)
tcfg = TrainConfig()
with mesh:
    state = init_train_state(model, jax.random.key(0))
    specs = train_state_specs(model, SINGLE_POD_RULES, 4)
    sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                is_leaf=lambda x: isinstance(x, P))
    state = jax.tree_util.tree_map(jax.device_put, state, sh)
    from repro.optim.adamw import AdamWConfig
    tcfg = TrainConfig(opt=AdamWConfig(lr=5e-3, warmup_steps=1, total_steps=100))
    step = jax.jit(make_train_step(model, tcfg), donate_argnums=0)
    kt = jax.random.key(1)
    batch = {"tokens": jax.random.randint(kt, (8, 16), 0, cfg.vocab),
             "labels": jax.random.randint(kt, (8, 16), 0, cfg.vocab),
             "mask": jnp.ones((8, 16))}
    batch = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
    losses = []
    for _ in range(6):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    out["spmd_train_losses_finite"] = all(np.isfinite(l) for l in losses)
    out["spmd_train_loss_decreases"] = losses[-1] < losses[0]

# 6. dry-run cell lowers+compiles on the 8-device mesh for a non-trivial arch
with mesh:
    cell = build_cell(get_arch("gemma_2b"), "decode_32k", mesh)
    compiled = lower_cell(cell).compile()
    out["gemma_decode_compiles"] = compiled is not None

print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, PYTHONPATH=_SRC)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_mesh_builds_on_stock_jax(results):
    """Regression: launch/mesh.py imports + builds meshes through the shim."""
    assert results["mesh_import_and_build"]
    assert results["mesh_multipod_build"]


def test_viterbi_2d_exact(results):
    assert results["viterbi_2d_row_exact"]
    assert results["viterbi_2d_col_exact"]


def test_viterbi_2d_row_col_agree(results):
    assert results["viterbi_2d_row_col_agree"]


@pytest.mark.parametrize("method", ["vanilla", "flash", "fused"])
def test_batched_ragged_bit_identical(results, method):
    """Sharded ragged batch == looped unbatched decodes, bit for bit."""
    assert results[f"batched_{method}_ragged_bit_identical"]


def test_sharded_batch_matches_single_device(results):
    """viterbi_decode_batch(mesh=...) == viterbi_decode_batch() exactly."""
    assert results["sharded_batch_bit_identical"]


def test_alignment_head_sharded(results):
    assert results["alignment_head_sharded_exact"]


def test_placed_scheduler_bit_identical_to_one_chip(results):
    """BatchScheduler over a head on a 4-device data placement: every
    answer equals the one-chip decode_batch bit for bit."""
    assert results["placed_scheduler_bit_identical"]


def test_placed_scheduler_matches_reference(results):
    assert results["placed_scheduler_matches_reference"]


def test_placed_put_sends_each_device_its_rows(results):
    assert results["placed_put_each_shard_on_its_device"]


def test_placed_dummy_rows_counted(results):
    assert results["placed_dummy_rows_counted"]


def test_spmd_train_step_runs_and_learns(results):
    assert results["spmd_train_losses_finite"]
    assert results["spmd_train_loss_decreases"]


def test_dryrun_cell_compiles_on_test_mesh(results):
    assert results["gemma_decode_compiles"]
