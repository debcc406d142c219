"""The benchmark's command reports nothing where it cannot measure: without
a TPU, for a workload it does not know, and in a checkout that holds only
``BENCHMARK.json`` and the benchmark's own files. Each case must exit
non-zero and print no result line."""
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import spec

CELL = spec.load_benchmark()["workloads"][0]["name"]


def run(cwd, workload=CELL):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", workload,
         "--seed", str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def no_result(proc):
    assert proc.returncode != 0, proc.stdout
    assert '"correct"' not in proc.stdout


def test_fails_without_a_tpu():
    proc = run(spec.ROOT)
    no_result(proc)
    assert "needs a TPU" in proc.stderr


def test_fails_for_an_unknown_workload():
    no_result(run(spec.ROOT, "no-such-config.no-such-traffic"))


@pytest.mark.parametrize("with_src", [False, True])
def test_fails_in_a_bare_checkout(tmp_path, with_src):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(spec.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    if with_src:      # the program is there, the chip is not
        shutil.copytree(spec.ROOT / "src", tmp_path / "src",
                        ignore=shutil.ignore_patterns("__pycache__"))
    no_result(run(tmp_path))
