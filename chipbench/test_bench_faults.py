"""A run with its timed path broken underneath reports ``correct`` false.

Each test skips the look for a chip and drives the rest of a run at the
program's smoke widths, under the cell's own limits, with one fault of
``faults.py`` planted: a step that returns its state unchanged; half of
the batch left out; and, in the four-chip ZeRO cell (on four CPU devices,
in a process of its own), each of those and the gradient exchange between
chips left out. There the control, the program's plain bf16 strategy (A),
fails too where the program as configured passes."""
import contextlib
import json
import os
import subprocess
import sys
import time

import pytest

from chipbench import faults, harness, smoke, spec

SEED = 2 ** 32 + 3
ONE_CHIP = "granite-3-2b.pretrain-4k"
FOUR_CHIP = "granite-3-2b.zero-dp4-4k"
PLANTED = {"unchanged": faults.unchanged, "half_batch": faults.half_batch}


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_planted_fault_is_caught(fault):
    cell = smoke.cell(ONE_CHIP)
    res = harness.run(cell, SEED, 0.5, False, t_start=time.perf_counter(),
                      fault=getattr(faults, fault))
    assert not res["correct"], res["checks"]


def four_device_runs() -> dict:
    """Run in a process with four CPU devices: the sound ZeRO run, the
    control, and one run with each fault; print every check."""
    cell = smoke.cell(FOUR_CHIP, rows_per_chip=1)
    out = {}
    for name in ("sound", "control", "unchanged", "half_batch", "exchange"):
        with (faults.exchange() if name == "exchange"
              else contextlib.nullcontext()):
            res = harness.run(cell, SEED, 0.5, False,
                              t_start=time.perf_counter(),
                              precision="A" if name == "control" else None,
                              fault=PLANTED.get(name))
        out[name] = {"correct": res["correct"], "checks": res["checks"]}
    return out


@pytest.fixture(scope="module")
def four_devices() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import json, chipbench.test_bench_faults as t; "
            "print(json.dumps(t.four_device_runs()))")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=spec.ROOT, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"], out["sound"]["checks"]
    return out


def test_missing_exchange_is_caught(four_devices):
    assert not four_devices["exchange"]["correct"], four_devices["exchange"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_planted_fault_is_caught_on_four_devices(four_devices, fault):
    assert not four_devices[fault]["correct"], four_devices[fault]


def test_control_fails_on_four_devices(four_devices):
    assert not four_devices["control"]["correct"], four_devices["control"]
