"""The output check's control, kept as a test at a size a CPU holds.

The control is the program with its own plain bf16 strategy (A) in place
of the configured Collage C: the precision below the one the configuration
states. Driven through a whole run at the program's smoke widths, under
each one-chip cell's own limits, the program as configured passes and the
control fails. (On the chip, at the cells' sizes, the same control is read
by ``calibrate.py``; ``PERF.md`` gives those readings.)"""
import time

import pytest

from chipbench import harness, smoke, spec

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]
         if w["chips"] == 1]
SEED = 2 ** 33 + 17


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_where_the_program_passes(name):
    cell = smoke.cell(name)
    sound = harness.run(cell, SEED, 0.5, False, t_start=time.perf_counter())
    assert sound["correct"], sound["checks"]
    control = harness.run(cell, SEED, 0.5, False,
                          t_start=time.perf_counter(), precision="A")
    assert not control["correct"], control["checks"]
    # plain bf16 loses the small updates that the Collage expansion keeps
    assert (control["checks"]["change_gap"]["value"]
            > control["checks"]["change_gap"]["limit"])
