"""A run with its timed path broken underneath reports ``correct`` false.

Each test skips the look for a chip and drives the rest of a run at the
program's smoke widths, under the cell's own limits, with one fault of
``faults.py`` planted: a step that returns its state unchanged; half of
the batch left out; and, under the ZeRO data-parallel mix
(``traffic/zero-dp4-4k.json``, kept for the four-chip cell to come), the
gradient exchange between chips left out (on four CPU devices, in a
process of its own, under the one-chip granite cell's limits)."""
import json
import os
import subprocess
import sys
import time

import pytest

from chipbench import faults, harness, smoke, spec

SEED = 2 ** 32 + 3
ONE_CHIP = "granite-3-2b.pretrain-4k"


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_planted_fault_is_caught(fault):
    cell = smoke.cell(ONE_CHIP)
    res = harness.run(cell, SEED, 0.5, False, t_start=time.perf_counter(),
                      fault=getattr(faults, fault))
    assert not res["correct"], res["checks"]


def four_device_runs() -> dict:
    """Run in a process with four CPU devices: the sound ZeRO run and the
    one without the exchange; print both checks."""
    cell = smoke.cell(ONE_CHIP, rows_per_chip=1, traffic="zero-dp4-4k")
    out = {}
    for name in ("sound", "exchange"):
        ctx = faults.exchange() if name == "exchange" else None
        if ctx:
            ctx.__enter__()
        try:
            res = harness.run(cell, SEED, 0.5, False,
                              t_start=time.perf_counter())
        finally:
            if ctx:
                ctx.__exit__(None, None, None)
        out[name] = {"correct": res["correct"], "checks": res["checks"]}
    return out


def test_missing_exchange_is_caught():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import json, chipbench.test_bench_faults as t; "
            "print(json.dumps(t.four_device_runs()))")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=spec.ROOT, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"], out["sound"]["checks"]
    assert not out["exchange"]["correct"], out["exchange"]["checks"]
