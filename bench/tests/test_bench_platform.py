"""The run refuses any platform but the TPU, and a tree without the program."""

import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "default_k512.offline",
         "--seed", "2147483648", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_non_tpu_platform_exits_nonzero_and_names_it():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(ROOT, env)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "TPU" in p.stderr
    assert _no_result(p.stdout)


def test_a_tree_with_only_the_benchmark_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert _no_result(p.stdout)
