"""Benchmark driver: one harness per paper table/figure + roofline summary.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only table3]

Prints ``name,us_per_call,derived`` CSV rows and a claim-validation summary;
exits non-zero if any validated claim fails.

One process per device: ``--only NAME`` runs that harness in this process;
without it, every harness runs in a child process of its own and this
parent never imports JAX — a parent that touched the device would hold
it, and a child that needs it (``train_step`` starts one) would fail or
hang.
"""
from __future__ import annotations

import argparse
import importlib
import subprocess
import sys
import time

# name → "module:function"; imported only by the process that runs it
BENCHES = [
    ("opt_step", "benchmarks.optimizer_step:optimizer_step_bench"),
    ("train_step", "benchmarks.train_step:train_step_bench"),
    ("attention", "benchmarks.attention:attention_bench"),
    ("table1", "benchmarks.table_benchmarks:table1_expansions"),
    ("table2", "benchmarks.table_benchmarks:table2_memory"),
    ("table3", "benchmarks.table_benchmarks:table3_pretrain"),
    ("table6", "benchmarks.table_benchmarks:table6_beta2_ablation"),
    ("table7", "benchmarks.table_benchmarks:table7_throughput"),
    ("table8", "benchmarks.table_benchmarks:table8_memory_compat"),
    ("fig3", "benchmarks.table_benchmarks:fig3_edq"),
    ("appD", "benchmarks.table_benchmarks:appendix_d_weight_decay"),
    ("roofline", "benchmarks.roofline:main"),
]


def _run_here(name: str, target: str, quick: bool) -> dict:
    mod, fn = target.split(":")
    t0 = time.time()
    rows, ok = getattr(importlib.import_module(mod), fn)(quick=quick)
    for r in rows:
        print(r)
    print(f"# {name}: {time.time() - t0:.1f}s", file=sys.stderr)
    return {f"{name}/{k}": v for k, v in ok.items()}


def _run_child(name: str, quick: bool) -> dict:
    """Run one harness in its own process; its claims come back as the
    ``validation/<name>/<claim>,0.0,PASS|FAIL`` rows it prints."""
    args = [sys.executable, "-m", "benchmarks.run", "--only", name]
    if quick:
        args.append("--quick")
    proc = subprocess.run(args, capture_output=True, text=True)
    sys.stderr.write(proc.stderr)
    ok = {}
    for line in proc.stdout.splitlines():
        if line.startswith("validation/"):
            key, _, verdict = line[len("validation/"):].split(",")
            ok[key] = verdict == "PASS"
        elif line != "name,us_per_call,derived":
            print(line)
    if proc.returncode and not ok:
        ok[f"{name}/crashed"] = False
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)

    print("name,us_per_call,derived")
    all_ok = {}
    for name, target in BENCHES:
        if args.only and args.only != name:
            continue
        all_ok.update(_run_here(name, target, args.quick) if args.only
                      else _run_child(name, args.quick))

    print("\n# paper-claim validation", file=sys.stderr)
    failed = [k for k, v in all_ok.items() if not v]
    for k, v in sorted(all_ok.items()):
        print(f"#  {'PASS' if v else 'FAIL'} {k}", file=sys.stderr)
    for k, v in sorted(all_ok.items()):
        print(f"validation/{k},0.0,{'PASS' if v else 'FAIL'}")
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        return 1
    print("# all validated claims PASS", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
