"""A cell cut to the program's smoke widths, for the CPU tests.

``cell(name)`` keeps the cell's family, traffic mix, hyperparameters,
kernels and limits, and sets the configuration to its family's ``SMOKE``
sizes (the program's ``--smoke`` widths) with ``seq_len`` tokens a row, so
that a whole run fits a test on the CPU. ``root`` is the checkout whose
``BENCHMARK.json`` names the cell."""
from __future__ import annotations

import dataclasses

from chipbench import spec


def cell(name: str, seq_len: int = 128, rows_per_chip: int = 2,
         pool: int = 4, root=spec.ROOT) -> spec.Cell:
    real = spec.load_cell(name, root)
    t = dict(real.traffic)
    launcher = list(t["launcher"]) + ["--smoke"]
    if "--flash-min-len" in launcher:     # flash stays on at the short rows
        launcher[launcher.index("--flash-min-len") + 1] = str(seq_len)
    t.update(seq_len=seq_len, rows_per_chip=rows_per_chip, pool=pool,
             launcher=launcher)
    return dataclasses.replace(
        real, config={**real.config, **real.family.SMOKE}, traffic=t)
