#!/usr/bin/env python3
"""Chip benchmark of Collage training: one run of one cell.

    python3 chipbench/run.py --workload granite-3-2b.pretrain-4k \\
        --seed 7 --seconds 10 --trace 0

Run from the root of a checkout on a machine that holds the chips the
cell asks for. ``--trace 0`` measures the cell's end-to-end metrics,
``--trace 1`` records the window with the profiler and reports its
per-layer metrics. Either way the run checks what the timed path produced
against the plain reference; the numbers compared, each beside its limit,
are the last lines on standard error and the last key of the result.
The last line on standard output is the result, one JSON object. Without
a TPU, with fewer chips than the cell asks for, or without the program
beside the benchmark, the run exits non-zero and prints no result."""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"     # fixed: the path is part of the key


@dataclasses.dataclass
class Facts:
    """What a per-layer metric reader (``chipbench/metrics``) reads."""
    family: object           # the cell's module chipbench/families/<family>
    dims: object             # the family's sizes (``family.dims_of``)
    traffic: dict
    chips: int
    peaks: dict
    steps: int               # steps in the traced window
    window_s: float          # host seconds of the traced window
    tokens_per_step: int     # all chips
    optimizer_bytes: int     # collage_update bytes per step, one chip
    summary: object          # trace.Summary
    phases: dict | None      # scopes.phase_seconds; None without scopes
    events: dict             # trace.load of the traced window
    scope_paths: dict        # scopes.scope_paths of the compiled step

    def scope_s(self, name: str):
        """Device self time in the window, seconds per chip, of the ops
        under the named scope ``name`` (any component of their op_name
        path); None where the program names no such scope."""
        from chipbench import scopes
        return scopes.scope_seconds(self.events, self.scope_paths, name)


def fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr)
    return 2


def chips_or_none(want: int):
    """The devices a cell runs on, or a reason there are none."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        return None, f"JAX found no device: {e}"
    if devs[0].platform != "tpu":
        return None, f"needs a TPU, JAX found {devs[0].platform}"
    if len(devs) < want:
        return None, f"the cell needs {want} chips, JAX found {len(devs)}"
    return devs[:want], None


def per_layer(cell, facts: Facts) -> dict:
    from chipbench import spec
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"])(facts)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def result_line(cell, res: dict, trace: bool, device) -> dict:
    from chipbench import scopes, spec, trace as trace_lib
    devs = res["devices"]
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": res["peak_bytes"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"]}
    if trace:
        summary = trace_lib.summarize(res["events"], res["kernel_ops"])
        facts = Facts(family=res["family"], dims=res["dims"],
                      traffic=cell.traffic, chips=len(devs),
                      peaks=spec.peaks(device.device_kind),
                      steps=res["attempted"], window_s=res["window_s"],
                      tokens_per_step=res["tokens_per_step"],
                      optimizer_bytes=res["optimizer_bytes"],
                      summary=summary,
                      phases=scopes.phase_seconds(res["events"],
                                                  res["phase_map"]),
                      events=res["events"],
                      scope_paths=res["scope_paths"])
        line["metrics"] = per_layer(cell, facts)
        dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
        line["device"] = dev
        line["breakdown"] = {"device_ops": summary.device_ops,
                             "idle_gaps": summary.idle_gaps}
        line["phases"] = facts.phases
    else:
        values = {
            "train_tokens_per_s": res["tokens_per_step"] * res["attempted"]
            / res["window_s"],
            "peak_hbm_gib": res["peak_bytes"] / 2 ** 30,
            "setup_s": res["setup_s"]}
        line["metrics"] = {m["name"]: {"value": values[m["name"]],
                                       "unit": m["unit"]}
                           for m in cell.end_to_end}
        line["device"] = dev
    line["checks"] = res["checks"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from chipbench import spec
    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        return fail(str(e))
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"no program beside the benchmark in {ROOT}")
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    devs, why = chips_or_none(cell.chips)
    if devs is None:
        return fail(why)
    try:
        spec.peaks(devs[0].device_kind)
    except spec.SpecError as e:
        return fail(str(e))
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from chipbench import harness
    res = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START)
    line = result_line(cell, res, bool(args.trace), devs[0])
    print("optimizer (last window step): " + json.dumps(res["last_step"]),
          file=sys.stderr)
    print("readings: " + json.dumps(res["readings"]), file=sys.stderr)
    print("memory: " + json.dumps(res["memory"]), file=sys.stderr)
    print("window step ends (s): " + json.dumps(res["step_ends"]),
          file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
