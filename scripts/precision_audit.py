"""Precision-flow & memory audit driver (DESIGN.md §8).

Lowers a matrix of (config × strategy × parallelism-mode) train cells
through launch/dryrun.lower_cell on smoke-scale host meshes, runs the
repro.analysis pass suite over each lowering (precision flow, donation,
liveness, roofline cost), and writes ``BENCH_precision_audit.json`` —
gated against ``benchmarks/baselines/`` by benchmarks.check_regression.

  PYTHONPATH=src python scripts/precision_audit.py [--quick] [--out PATH]

The artifact is the machine-checked form of the paper's central claim:
every (16,16) strategy cell certifies ZERO parameter-shaped f32 buffers
live across steps (no fp32 master copy), while the strategy-D baseline
cells — same model, same mesh, same engine — report their master copy,
proving the detector has teeth. The liveness pass turns the same
lowerings into the collage-vs-mixed peak-HBM gap as a gated number.
"""
from __future__ import annotations

import os
# 8 host devices: enough for a (2,4) pipe×data mesh, small enough that a
# full-matrix lowering sweep stays CI-sized. Must precede any jax import
# (dryrun's own setdefault of 512 yields to this).
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402

from repro.analysis import audit_cell, is_sixteen_bit  # noqa: E402
from repro.analysis.source_lint import lint_paths  # noqa: E402
from repro.launch import dryrun  # noqa: E402
from repro.launch.mesh import auto_mesh  # noqa: E402

# one small dense, one mid dense (GQA), one MoE — the shapes that exercise
# every param-layout branch (flat buckets, tree/pipeline, expert tensors)
ARCHS = ("gpt-tiny", "granite-3-2b", "qwen3-moe-30b-a3b")
STRATEGIES_16BIT = ("C", "SR")

# parallelism modes for the 16-bit strategies; the D baseline runs flat
# tree-layout only (one master-copy witness per arch is enough)
MODES = {
    # flat dp in the tree layout, uncompressed wire: the SAME layout the D
    # baseline runs, so the memory gap below is strategy-only
    "flat": dict(engine="sharded", bucketed="0", compress="none", smoke="1"),
    "zero": dict(engine="sharded", bucketed="1", zero="1",
                 compress="bf16_ef", smoke="1"),
    "pipeline": dict(engine="sharded", bucketed="0", pipeline="pipe",
                     accum="4", compress="none", smoke="1"),
}
D_OVERRIDES = dict(engine="sharded", bucketed="0", smoke="1")


def _mesh(mode: str):
    if mode.startswith("pipeline"):
        return auto_mesh((2, 4), ("pipe", "data"))
    return auto_mesh((8,), ("data",))


def run_one(arch: str, strategy: str, mode: str, overrides: dict) -> dict:
    t0 = time.time()
    _, _, lowered, compiled, meta = dryrun.lower_cell(
        arch, "train_smoke", _mesh(mode), strategy, overrides=dict(overrides))
    cell = audit_cell(lowered.as_text(), compiled.as_text(),
                      strategy=strategy)
    pf, don = cell["precision_flow"], cell["donation"]
    live, cost = cell["liveness"], cell["cost"]
    return {
        "strategy": strategy,
        "mode": mode,
        "sixteen_bit": pf["sixteen_bit"],
        "zero_shard": meta.get("zero_shard"),
        "pipeline_axis": meta.get("pipeline_axis"),
        # precision flow — hard invariant + advisory structural counts
        "n_param_f32_persistent": len(pf["param_f32_persistent"]),
        "param_f32_persistent": [x["name"]
                                 for x in pf["param_f32_persistent"]],
        "state_bytes": pf["state_bytes"],
        "f32_state_bytes": pf["f32_state_bytes"],
        "transient_param_shaped_f32": pf["transient_param_shaped_f32"],
        "double_round_chains": pf["double_round_chains"],
        # donation
        "n_donated": don["n_donated"],
        "n_aliased": don["n_aliased"],
        "n_unrealized": len(don["unrealized"]),
        # liveness + modeled cost
        "peak_bytes_tpu": live["peak_bytes_tpu"],
        "param_bytes_tpu": live["param_bytes_tpu"],
        "modeled_step_s": cost["modeled_step_s"],
        "bound": cost["bound"],
        "ok": cell["ok"],
        "wall_seconds": round(time.time() - t0, 1),
    }


def run_audit(archs=ARCHS, quick: bool = False) -> dict:
    cells = {}
    for arch in archs:
        for strategy in STRATEGIES_16BIT:
            for mode, ov in MODES.items():
                key = f"{arch}/{strategy}/{mode}"
                print(f"[audit] {key} ...", flush=True)
                cells[key] = run_one(arch, strategy, mode, ov)
                print(f"[audit] {key}: ok={cells[key]['ok']} "
                      f"({cells[key]['wall_seconds']}s)", flush=True)
        key = f"{arch}/D/flat"
        print(f"[audit] {key} ...", flush=True)
        cells[key] = run_one(arch, "D", "flat", D_OVERRIDES)
        print(f"[audit] {key}: master_leaves="
              f"{cells[key]['param_f32_persistent']} "
              f"({cells[key]['wall_seconds']}s)", flush=True)

    # ONE 1F1B cell (PR 7): the schedule interpreter's explicit-vjp
    # backward is a new precision path — the no-master-copy invariant must
    # hold through it too. A single (smallest-arch, C) cell keeps the
    # matrix CI-sized; per-schedule numerics are pinned by the parity
    # tests, this pins the STATIC precision flow.
    key = f"{archs[0]}/C/pipeline_1f1b"
    print(f"[audit] {key} ...", flush=True)
    cells[key] = run_one(archs[0], "C", "pipeline_1f1b",
                         dict(MODES["pipeline"], schedule="1f1b"))
    print(f"[audit] {key}: ok={cells[key]['ok']} "
          f"({cells[key]['wall_seconds']}s)", flush=True)

    # collage-vs-mixed memory gap, per arch, from the flat cells
    memory_gap = {}
    for arch in archs:
        c = cells.get(f"{arch}/C/flat")
        d = cells.get(f"{arch}/D/flat")
        if not (c and d):
            continue
        memory_gap[arch] = {
            "state_bytes_collage": c["state_bytes"],
            "state_bytes_mixed": d["state_bytes"],
            "state_ratio": round(c["state_bytes"] / d["state_bytes"], 4),
            "peak_tpu_collage": c["peak_bytes_tpu"],
            "peak_tpu_mixed": d["peak_bytes_tpu"],
            "peak_ratio": round(c["peak_bytes_tpu"] / d["peak_bytes_tpu"], 4),
        }

    lint = lint_paths(repo_root=str(REPO))

    sixteen = {k: c for k, c in cells.items() if c["sixteen_bit"]}
    mixed = {k: c for k, c in cells.items() if not c["sixteen_bit"]}
    ok = {
        "no_master_copy_all_16bit_cells":
            bool(sixteen) and all(c["ok"]["no_master_copy"]
                                  for c in sixteen.values()),
        "mixed_baseline_has_master_copy":
            bool(mixed) and all(c["n_param_f32_persistent"] > 0
                                for c in mixed.values()),
        "all_donations_realized":
            all(c["ok"]["all_donations_realized"] for c in cells.values()),
        "no_double_rounding":
            all(c["double_round_chains"] == 0 for c in cells.values()),
        "collage_state_smaller_than_mixed":
            bool(memory_gap) and all(g["state_ratio"] < 1.0
                                     for g in memory_gap.values()),
        "collage_peak_hbm_below_mixed":
            bool(memory_gap) and all(g["peak_ratio"] < 1.0
                                     for g in memory_gap.values()),
        "source_lint_clean": not lint,
    }
    return {
        "bench": "precision_audit",
        "quick": quick,
        "n_cells": len(cells),
        "cells": cells,
        "memory_gap": memory_gap,
        "source_lint": {"n_findings": len(lint), "findings": lint},
        "ok": ok,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="gpt-tiny only (7 cells) for local iteration")
    ap.add_argument("--out", default="BENCH_precision_audit.json")
    args = ap.parse_args(argv)
    archs = ARCHS[:1] if args.quick else ARCHS
    t0 = time.time()
    report = run_audit(archs, quick=args.quick)
    pathlib.Path(args.out).write_text(json.dumps(report, indent=1))
    failed = [k for k, v in report["ok"].items() if not v]
    print(f"[audit] wrote {args.out}: {report['n_cells']} cells in "
          f"{time.time() - t0:.0f}s; ok={report['ok']}")
    if failed:
        print(f"[audit] FAILED invariants: {failed}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
