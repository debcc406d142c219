#!/usr/bin/env python3
"""Readings that set a cell's output-check limits, read on the chip.

    python3 chipbench/calibrate.py --workload granite-3-2b.pretrain-4k \\
        --seeds 101-112 --control-seeds 101-103 --fault-seeds 101-103 \\
        [--faults half_batch,exchange] [--out FILE]

For every seed, the numbers that ``compare.py`` compares, for the program
as the cell configures it; on the control seeds, for the control: the
program with its plain bf16 strategy (A), the precision below Collage's;
on the fault seeds, for the program with each fault of ``faults.py``
planted. No window is measured: the set-up steps are what the check reads.
One reference per seed serves every reading of that seed. Each reading is
printed as one JSON line and, with ``--out``, written to that file."""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def seeds(text: str) -> list:
    out = []
    for part in filter(None, text.split(",")):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--faults", default="half_batch")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from chipbench import compare, faults, harness, spec
    from chipbench.run import CACHE_DIR, chips_or_none

    cell = spec.load_cell(args.workload)
    devs, why = chips_or_none(cell.chips)
    if devs is None:
        print(f"calibrate: {why}", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    sound, control, fault = (seeds(args.seeds), seeds(args.control_seeds),
                             seeds(args.fault_seeds))
    kinds = [k for k in args.faults.split(",") if k]
    programs = {"sound": harness.TrainProgram(cell)}
    if control:
        programs["control"] = harness.TrainProgram(cell, precision="A")
    if "exchange" in kinds:
        # this fault changes the compiled step: it is compiled, on its
        # first seed, with the reduction left out
        programs["exchange"] = harness.TrainProgram(cell)
    out = open(args.out, "w") if args.out else None
    for seed in sorted(set(sound) | set(control) | set(fault)):
        runs = [("sound", None)] if seed in sound else []
        if seed in control:
            runs.append(("control", None))
        if seed in fault:
            runs += [(k, None if k == "exchange" else getattr(faults, k))
                     for k in kinds]
        reads = {}
        for name, plant in runs:
            prog = programs.get(name, programs["sound"])
            step = prog.step
            with (faults.exchange() if name == "exchange"
                  else contextlib.nullcontext()):
                state, pool, tokens, reads[name] = harness.start(
                    prog, seed, plant)
            prog.step = step
            del state, pool
        t0 = time.perf_counter()
        ref = harness.reference_readings(
            cell, harness.keys(seed)[0], tokens[:harness.CHECK_STEPS],
            programs["sound"].devices)
        ref_s = time.perf_counter() - t0
        for name, read in reads.items():
            line = {"workload": cell.name, "seed": seed, "run": name,
                    "reference_s": ref_s,
                    "values": compare.readings(read, ref),
                    "program": read, "reference": ref}
            text = json.dumps(line)
            print(text, flush=True)
            if out:
                out.write(text + "\n")
                out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
