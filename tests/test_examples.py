"""Every example runs to its end and prints its own check.

Each example is run as its own process, as a user runs it, with
``PYTHONPATH=src``.  The module's fixture starts all of them at once, and
each test waits for its own: the examples are independent, and waiting
for them one after another would make this file the suite's longest.  A
case fails on a non-zero exit, on its time limit, or when the line the
example prints to report its check is missing or false.
"""

import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
#: seconds from the start of the examples until each must have ended (each
#: takes well under a minute on one CPU core, jax import included)
TIMEOUT_S = 600

EXAMPLES = {
    "quickstart": ("quickstart.py",),
    "batch_decode": ("batch_decode.py",),
    "streaming_decode": ("streaming_decode.py",),
    "map_matching": ("map_matching.py",),
    "forced_alignment_serving": ("forced_alignment_serving.py",),
    "adaptive_edge": ("adaptive_edge.py", "--budget-kb", "8"),
}

#: quickstart specs that decode exactly: each must print the score and the
#: relative error of `VanillaSpec()`'s row (the same path scores the same)
_EXACT_ROWS = ("CheckpointSpec()", "FlashSpec(P=1)", "FlashSpec(P=7)",
               "FlashSpec(P=16)")


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Start every example; yield name -> (process, output file, deadline)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH")
                               else []))
    out_dir = tmp_path_factory.mktemp("examples")
    deadline = time.monotonic() + TIMEOUT_S
    started = {}
    try:
        for name, (script, *args) in EXAMPLES.items():
            out = open(out_dir / f"{name}.out", "w+")
            err = open(out_dir / f"{name}.err", "w+")
            proc = subprocess.Popen(
                [sys.executable, str(ROOT / "examples" / script), *args],
                cwd=ROOT, env=env, stdout=out, stderr=err, text=True)
            started[name] = (proc, out, err)
        yield {name: (proc, out, err, deadline)
               for name, (proc, out, err) in started.items()}
    finally:
        for proc, out, err in started.values():
            if proc.poll() is None:
                proc.kill()
                proc.wait()
            out.close()
            err.close()


def _run(runs, name):
    """Wait for one example; return its standard output."""
    proc, out, err, deadline = runs[name]
    try:
        rc = proc.wait(timeout=max(1.0, deadline - time.monotonic()))
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        pytest.fail(f"{name} ran past {TIMEOUT_S} s")
    out.seek(0)
    err.seek(0)
    stdout = out.read()
    assert rc == 0, stdout[-2000:] + err.read()[-4000:]
    return stdout


def test_quickstart_exact_specs_agree_and_the_planner_answers(runs):
    out = _run(runs, "quickstart")
    rows = {line.split()[0]: line.split() for line in out.splitlines()
            if line.split() and line.split()[0].endswith(")")}
    score, err = rows["VanillaSpec()"][-2:]
    assert float(err) < 1e-5           # float rounding of the path's score
    for name in _EXACT_ROWS:
        assert rows[name][-2:] == [score, err], rows[name]
    assert sum(" KiB -> " in line for line in out.splitlines()) == 3


def test_batch_decode_batched_and_scheduled_equal_the_loop(runs):
    out = _run(runs, "batch_decode")
    assert "batched == looped per sequence: True" in out
    assert "scheduler results == batched decode: True" in out


def test_streaming_decode_assembles_the_offline_path(runs):
    out = _run(runs, "streaming_decode")
    assert "assembled path == offline decode" in out
    assert "of states agree with exact" in out


def test_map_matching_is_oracle_clean(runs):
    out = _run(runs, "map_matching")
    assert "map matching oracle-clean: True" in out


def test_forced_alignment_serving_paths_are_monotone(runs):
    out = _run(runs, "forced_alignment_serving")
    assert "served 12 alignment requests" in out
    assert "alignment paths are monotone: True" in out


def test_adaptive_edge_plans_an_exact_spec_within_budget(runs):
    out = _run(runs, "adaptive_edge")
    assert "budget=8KiB K=512 T=512 -> FlashSpec(" in out
    # an exact spec: only float rounding of the path's score is left
    assert float(out.rsplit("rel.err=", 1)[1].split()[0]) < 1e-5
