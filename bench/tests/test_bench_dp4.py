"""The data-parallel offline cell's mix and spec builder, run whole on four
CPU devices at a tiny size.

`default_k512_dp4.offline` feeds `BatchScheduler` batches to a head built
from the `data_mesh` builder's placement, so every batch goes through
`ViterbiDecoder.decode_sharded` over a four-device ``data`` mesh.  The
forced device count must be set before jax starts, so the run is a
subprocess: `run.run_cell` with the look for a chip skipped, on the cell's
own mix cut to `tiny_er`'s lengths (its spec and kind as they are).
"""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, DATA, ROOT

CELL = "default_k512_dp4.offline"

SCRIPT = r"""
import json, sys, time
import numpy as np
import run
from lib import registry

bench = registry.load_benchmark()
cell = registry.find_cell(bench, CELL)
mix = dict(registry.load_traffic(cell["traffic"]), lengths=[12, 16, 24, 32],
           pool_per_length=2, buckets=[16, 32], max_batch=8, queue_batches=4)
config = registry.load_config("tiny_er", DATA)
e2e = registry.metrics_for(bench, CELL, "end_to_end")


def altered(client):
    fn = client.sched.fn

    def decode(padded, lens):
        paths, scores = fn(padded, lens)
        paths = np.array(paths)
        t = int(lens[0]) // 2
        paths[0, t] = (paths[0, t] + 1) % 128
        return paths, scores

    client.sched.fn = decode


out = {}
for name, wrap in (("sound", None), ("altered", altered)):
    seen = {}

    def look(client, wrap=wrap):
        seen["stats"] = client.head.decoder.stats
        if wrap is not None:
            wrap(client)

    result, info = run.run_cell(cell, config, mix, e2e, [], 2147483651, 1.0,
                                False, require_tpu=False,
                                t_start=time.perf_counter(), wrap_program=look)
    out[name] = {"correct": result["correct"], "attempted":
                 result["attempted"], "metrics": sorted(result["metrics"]),
                 "spec": info["spec"], "compiles": info["compiles_in_window"],
                 "stats": seen["stats"]}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(BENCH)]))
    script = f"CELL = {CELL!r}\nfrom pathlib import Path\n" \
             f"DATA = Path({str(DATA)!r})\n" + SCRIPT
    p = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    line = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_sharded_cell_runs_whole_and_is_correct(runs):
    sound = runs["sound"]
    assert sound["correct"] and sound["attempted"] > 0
    assert sound["metrics"] == ["frames_per_s", "setup_s"]
    assert "DataPlacement" in sound["spec"] and "FusedSpec" in sound["spec"]
    assert "axis_sizes=(4,)" in sound["spec"]
    assert sound["stats"]["sharded_batches"] > 0
    assert sound["compiles"] == 0


def test_sharded_cell_with_an_answer_altered_is_not_correct(runs):
    assert not runs["altered"]["correct"]


def test_data_mesh_builder_refuses_more_devices_than_jax_sees():
    import jax
    from lib.offline import resolve_spec
    with pytest.raises(ValueError, match="jax sees"):
        resolve_spec({"builder": "data_mesh", "class": "FusedSpec",
                      "data": len(jax.devices()) + 1}, K=8, T=8, batch=8,
                     log_pi=None, log_A=None)


def test_decode_roofline_dp4_reads_log_A_once_a_chip():
    """Every chip reads its own replica of log_A once a batch, so four chips
    need three log_A reads a batch more than one chip doing the same work;
    the time is the program's, summed over the chips."""
    from lib import registry
    read = registry.load_metric("decode_roofline.dp4").read
    K, frames, batches = 512, 1000, 10
    run = {"counters": {"K": K, "frames": frames, "batches": batches},
           "peaks": {"hbm_bytes_per_s": 1e9}}
    shares = {}
    for chips in (1, 4):
        run["trace"] = {"devices": chips,
                        "module_s_total": {"jit__decode_shard": 1.0}}
        shares[chips] = read(run)
    gap = 3 * 4 * K * K * batches
    assert shares[4] - shares[1] == pytest.approx(100.0 * gap / 1e9)
    run["trace"] = {"devices": 4, "module_s_total": {"jit__unknown": 1.0}}
    assert read(run) is None
