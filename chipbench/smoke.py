"""A cell cut to the program's smoke widths, for the CPU tests.

``cell(name)`` keeps the cell's traffic mix, hyperparameters, kernels and
limits, and sets the configuration to the program's ``--smoke`` sizes
(2 layers, d 64, 4 heads on 2 KV heads, d_ff 128, vocab 256) with
``seq_len`` tokens a row, so that a whole run fits a test on the CPU.
``traffic`` puts another mix of ``chipbench/traffic`` in the cell's."""
from __future__ import annotations

import dataclasses
import json

from chipbench import spec

SMOKE = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "intermediate_size": 128,
         "vocab_size": 256, "num_hidden_layers": 2}


def cell(name: str, seq_len: int = 128, rows_per_chip: int = 2,
         pool: int = 4, traffic: str | None = None) -> spec.Cell:
    real = spec.load_cell(name)
    t = dict(real.traffic if traffic is None else json.loads(
        (spec.HERE / "traffic" / f"{traffic}.json").read_text()))
    launcher = list(t["launcher"]) + ["--smoke"]
    if "--flash-min-len" in launcher:     # flash stays on at the short rows
        launcher[launcher.index("--flash-min-len") + 1] = str(seq_len)
    t.update(seq_len=seq_len, rows_per_chip=rows_per_chip, pool=pool,
             launcher=launcher)
    return dataclasses.replace(real, config={**real.config, **SMOKE},
                               traffic=t)
