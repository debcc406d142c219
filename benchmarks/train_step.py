"""Sharded train-step benchmark: leaf-wise vs bucket-wise compressed
gradient collectives, and dp=1 vs dp=8 host-device scaling.

What is measured (8 virtual host devices, smoke-size gpt):

  * collective census of the LOWERED step (StableHLO, pre-XLA-optimization
    — the CPU backend upcasts low-precision collectives at compile time, a
    backend artifact the staged IR doesn't have):
      - tree layout + bf16_ef → one gradient all-reduce PER LEAF
      - bucketed layout + bf16_ef → one PER DTYPE BUCKET
    validated claim: bucket-level compression uses STRICTLY FEWER
    collective ops than leaf-wise.
  * staged wire bytes compressed (bf16/fp8 payload) vs uncompressed (f32):
    validated claim: strictly fewer bytes.
  * per-device cost of dp=8 vs dp=1 (utils.hlo_analysis on the compiled
    HLO): validated claim: dp=8 per-device FLOPs < dp=1/4 (the container
    has too few physical cores for wall-clock scaling to be meaningful;
    step times are reported informationally).

  PYTHONPATH=src python -m benchmarks.train_step [--quick]

Emits ``BENCH_train_step.json``; wired into benchmarks.run as the
``train_step`` entry (which re-execs this module in a fresh interpreter so
the 8-device host-platform flag can take effect before jax initializes).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

N_DEV = 8


# --------------------------------------------------------------------------
# heavy work (fresh interpreter: jax imported only inside)
# --------------------------------------------------------------------------

def _bench(quick: bool, out_path: str) -> dict:
    import jax

    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core.collage import CollageAdamW
    from repro.core.precision import (BucketPolicy, PrecisionPolicy,
                                      Strategy)
    from repro.data.synthetic import make_batch_fn
    from repro.distributed import compression
    from repro.distributed import sharding as shard_lib
    from repro.launch.mesh import auto_mesh
    from repro.models.model import build_model
    from repro.train import sharded
    from repro.utils import hlo_analysis

    cfg = get_config("gpt-tiny", smoke=True)
    model = build_model(cfg)
    shape = ShapeConfig("bench", 64, 32, "train")
    batch_fn = make_batch_fn(cfg, shape)
    mesh8 = auto_mesh((N_DEV,), ("data",))
    mesh1 = auto_mesh((1,), ("data",))

    def mkopt(bucketed: bool, mesh) -> CollageAdamW:
        bp = BucketPolicy(
            enabled=bucketed,
            pad_multiple=shard_lib.bucket_pad_multiple(
                mesh, block=compression.BLOCK)) \
            if bucketed else BucketPolicy()
        return CollageAdamW(1e-3, b2=0.95, policy=PrecisionPolicy(
            strategy=Strategy.C_COLLAGE_PLUS, bucketing=bp))

    def build(mesh, bucketed, compress, zero):
        opt = mkopt(bucketed, mesh)
        state = sharded.init_state(model, opt, jax.random.PRNGKey(0), mesh,
                                   grad_compression=compress)
        state = sharded.device_put_state(state, mesh, zero_shard=zero)
        step = sharded.make_sharded_train_step(
            model, opt, mesh, grad_compression=compress, zero_shard=zero,
            jit=False)
        return opt, state, step

    def census(mesh, bucketed, compress, zero):
        _, state, step = build(mesh, bucketed, compress, zero)
        txt = jax.jit(step).lower(state, batch_fn(0)).as_text()
        return _census_of(txt)

    def _census_of(txt):
        colls = hlo_analysis.stablehlo_collectives(txt)
        # gradient-sized collectives only (scalars are metric pmeans)
        grad_colls = [c for c in colls if c["numel"] > 64]
        return {
            "ops_total": len(colls),
            "grad_ops": len(grad_colls),
            "grad_ops_by_dtype": _by_dtype(grad_colls),
            "staged_wire_bytes": sum(c["bytes"] for c in grad_colls),
            # fabric-total traffic: a collective with G replica groups runs
            # G independent reductions of the same payload — this is where
            # the embed/head joint-group dedup shows its S× saving
            "global_wire_bytes": sum(
                c["bytes"] * (c["n_groups"] or 1) for c in grad_colls),
            "grad_groups": sorted(
                (c["dtype"], c["n_groups"], c["group_size"])
                for c in grad_colls),
        }

    def census_pipeline(compress, schedule="gpipe"):
        # 2 stages × dp 4: the dp gradient reduction compresses at (leaf
        # class × dtype) bucket granularity — stage chunks / embed / head
        # each ship ONE compressed all-reduce; embed and head lower with a
        # single JOINT (pipe × dp) replica group instead of one dp group
        # per stage row (train/sharded.py dedup)
        pmesh = auto_mesh((2, 4), ("pipe", "data"))
        opt = mkopt(False, pmesh)
        state = sharded.init_state(model, opt, jax.random.PRNGKey(0),
                                   pmesh, axis="data",
                                   grad_compression=compress,
                                   pipeline_axis="pipe")
        state = sharded.device_put_state(state, pmesh, axis="data",
                                         pipeline_axis="pipe")
        step = sharded.make_sharded_train_step(
            model, opt, pmesh, axis="data", pipeline_axis="pipe",
            grad_compression=compress, schedule=schedule, jit=False)
        chunked = jax.tree_util.tree_map(
            lambda x: x.reshape((4, 8) + x.shape[1:]), batch_fn(0))
        txt = jax.jit(step).lower(state, chunked).as_text()
        return _census_of(txt)

    def schedule_model():
        # structural cost model (analysis/cost_model.py): masked-tick
        # bubbles per schedule + single-channel comm overlap, at the bench
        # cell's scale (S=2 pipeline below; a deeper S=4 point shows the
        # ramp effects). Pure arithmetic on the Schedule IR — gated as
        # ORDERINGS, not absolute seconds.
        from repro.analysis import cost_model
        from repro.core import bucketing
        from repro.distributed import pipeline as pp
        comm = {"stage": 2e-4, "embed": 1e-4, "head": 1e-4}
        out = {}
        for S, M in ((2, 4), (4, 8)):
            cell = {}
            for name, V in (("gpipe", 1), ("1f1b", 1), ("interleaved", 2)):
                st = pp.make_schedule(name, n_stages=S, n_micro=M,
                                      n_virtual=V).stats()
                cell[name] = cost_model.schedule_cost(
                    st, fwd_unit_s=1e-3, bwd_unit_s=2e-3, comm_cost_s=comm)
            out[f"S{S}_M{M}"] = cell
        # flat-dp per-bucket overlap: grads close in reverse layer order
        # during the backward; each bucket's all-reduce launches at its
        # close rank (core/bucketing.py close-rank metadata == the
        # engine's reduce_fn program order). The uniform-bf16 bench model
        # packs ONE bucket (nothing to overlap), so the model point uses a
        # mixed-precision layout — bf16 matmuls + f32 norm scales per
        # layer, the option-D master-dtype split — where the dtype buckets
        # close at different backward ranks.
        import jax.numpy as jnp
        tree = {}
        for i in range(8):
            tree[f"l{i:02d}_w"] = jnp.zeros((4096,), jnp.bfloat16)
            tree[f"l{i:02d}_scale"] = jnp.zeros((256,), jnp.float32)
        layout = bucketing.build_layout(tree, pad_multiple=512)
        n_leaves = len(layout.slots)
        leaf_ranks = tuple(n_leaves - 1 - i for i in range(n_leaves))
        close = bucketing.bucket_close_ranks(layout, leaf_ranks)
        bwd_s = 2e-3
        events = sorted(
            ((close[b] + 1) / n_leaves * bwd_s,
             layout.buckets[b].padded * 2 / 50e9, b)
            for b in bucketing.readiness_order(layout, leaf_ranks))
        out["flat_buckets"] = {
            "n_buckets": layout.n_buckets,
            "close_ranks": list(close),
            **cost_model.overlap_comm(events, bwd_s),
        }
        return out

    def _by_dtype(colls):
        out: dict = {}
        for c in colls:
            k = f'{c["kind"]}:{c["dtype"]}'
            out[k] = out.get(k, 0) + 1
        return out

    def timed(mesh, bucketed, compress, zero, iters):
        _, state, step = build(mesh, bucketed, compress, zero)
        jstep = jax.jit(step)
        batch = batch_fn(0)
        lowered = jstep.lower(state, batch)
        compiled = lowered.compile()
        costs = hlo_analysis.analyze(compiled.as_text())
        state, m = jstep(state, batch)          # warmup
        jax.block_until_ready(m["loss"])
        times = []
        for i in range(iters):
            t0 = time.perf_counter()
            state, m = jstep(state, batch_fn(i + 1))
            jax.block_until_ready(m["loss"])
            times.append(time.perf_counter() - t0)
        times.sort()
        return {
            "steady_s": times[len(times) // 2],
            "per_device_flops": costs.flops,
            "per_device_collective_bytes": dict(costs.collective_bytes),
            "per_device_collective_counts": dict(costs.collective_counts),
        }

    iters = 5 if quick else 10
    n_leaves = len(jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))))

    results = {
        "n_param_leaves": n_leaves,
        "census": {
            "leafwise_bf16_ef": census(mesh8, False, "bf16_ef", False),
            "bucket_bf16_ef": census(mesh8, True, "bf16_ef", False),
            "bucket_fp8_ef": census(mesh8, True, "fp8_ef", False),
            "bucket_uncompressed": census(mesh8, True, "none", False),
            "bucket_zero_bf16_ef": census(mesh8, True, "bf16_ef", True),
            "pipeline_fp8_ef": census_pipeline("fp8_ef"),
            "pipeline_1f1b_fp8_ef": census_pipeline("fp8_ef",
                                                    schedule="1f1b"),
            "pipeline_uncompressed": census_pipeline("none"),
        },
        "schedule_model": schedule_model(),
        "timing": {
            "dp1_bucket_bf16_ef": timed(mesh1, True, "bf16_ef", False,
                                        iters),
            "dp8_bucket_bf16_ef": timed(mesh8, True, "bf16_ef", False,
                                        iters),
            "dp8_bucket_zero_bf16_ef": timed(mesh8, True, "bf16_ef", True,
                                             iters),
            "dp8_leafwise_bf16_ef": timed(mesh8, False, "bf16_ef", False,
                                          iters),
        },
    }

    c = results["census"]
    t = results["timing"]
    results["ok"] = {
        # the acceptance-criteria claim: one collective per bucket beats one
        # per leaf, strictly
        "bucket_fewer_collective_ops_than_leafwise":
            c["bucket_bf16_ef"]["grad_ops"]
            < c["leafwise_bf16_ef"]["grad_ops"],
        "compressed_fewer_wire_bytes_than_uncompressed":
            c["bucket_bf16_ef"]["staged_wire_bytes"]
            < c["bucket_uncompressed"]["staged_wire_bytes"]
            and c["bucket_fp8_ef"]["staged_wire_bytes"]
            < c["bucket_bf16_ef"]["staged_wire_bytes"],
        # host-device scaling: per-device compute shrinks ~linearly with dp
        # (wall-clock is meaningless on this container's core count)
        "dp8_per_device_flops_under_quarter_of_dp1":
            t["dp8_bucket_bf16_ef"]["per_device_flops"]
            < 0.25 * t["dp1_bucket_bf16_ef"]["per_device_flops"],
        # pipeline parity (PR 5): the dp gradient reduction ships exactly
        # one fp8 all-reduce per leaf class (stage / embed / head) and
        # strictly fewer wire bytes than the uncompressed pipeline step
        "pipeline_one_compressed_collective_per_leaf_class":
            c["pipeline_fp8_ef"]["grad_ops_by_dtype"]
            .get("all_reduce:f8E4M3FN") == 3,
        "pipeline_compressed_fewer_wire_bytes":
            c["pipeline_fp8_ef"]["staged_wire_bytes"]
            < c["pipeline_uncompressed"]["staged_wire_bytes"],
    }

    def joint_dedup(cen):
        # embed + head each lower with ONE joint (pipe×dp = 8-wide) replica
        # group; the stage-class reduce stays dp-only (2 groups of 4). The
        # old per-stage-row scheme would ship S=2 groups for embed/head too
        # — S× the fabric traffic for those classes.
        g = [t for t in cen["grad_groups"] if t[0] == "f8E4M3FN"]
        return sorted(tuple(t[1:]) for t in g) == [(1, 8), (1, 8), (2, 4)]

    sm = results["schedule_model"]
    results["ok"].update({
        # satellite 1: the wire-bytes dedup census — joint groups on the
        # lowered IR for every schedule, and compressed fabric traffic
        # strictly below the uncompressed pipeline step's
        "pipeline_embed_head_joint_group_dedup":
            joint_dedup(c["pipeline_fp8_ef"])
            and joint_dedup(c["pipeline_1f1b_fp8_ef"]),
        "pipeline_global_wire_bytes_compressed_below_uncompressed":
            c["pipeline_fp8_ef"]["global_wire_bytes"]
            < c["pipeline_uncompressed"]["global_wire_bytes"],
        # satellite 2: per-schedule bubble accounting, gated as orderings
        "schedule_1f1b_bubble_below_gpipe": all(
            cell["1f1b"]["bubble_fraction"]
            < cell["gpipe"]["bubble_fraction"]
            for k, cell in sm.items() if k.startswith("S")),
        "schedule_interleaved_bubble_below_gpipe": all(
            cell["interleaved"]["bubble_fraction"]
            < cell["gpipe"]["bubble_fraction"]
            for k, cell in sm.items() if k.startswith("S")),
        # overlapped collectives launched at bucket-class readiness beat
        # the everything-after-compute serialization
        "schedule_overlap_below_serialized": all(
            cell["1f1b"]["comm"]["overlapped_total_s"]
            < cell["1f1b"]["comm"]["serialized_total_s"]
            for k, cell in sm.items() if k.startswith("S")),
        "flat_bucket_overlap_below_serialized":
            sm["flat_buckets"]["overlapped_total_s"]
            < sm["flat_buckets"]["serialized_total_s"],
    })

    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    return results


# --------------------------------------------------------------------------
# benchmarks.run entry (fresh interpreter for the device-count flag)
# --------------------------------------------------------------------------

def train_step_bench(quick: bool = False,
                     out_path: str = "BENCH_train_step.json"):
    """Returns (csv_rows, ok_dict) for benchmarks.run."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N_DEV}"
    env.setdefault("PYTHONPATH", "src")
    args = [sys.executable, "-m", "benchmarks.train_step", "--out", out_path]
    if quick:
        args.append("--quick")
    # _bench writes the json before claim evaluation, so its absence (not
    # the exit code — 1 also means "a claim failed") is the crash signal;
    # drop any stale file so a crash can't report a previous run's numbers
    if os.path.exists(out_path):
        os.remove(out_path)
    proc = subprocess.run(args, env=env, capture_output=True, text=True)
    if not os.path.exists(out_path):
        raise RuntimeError(
            f"train_step bench crashed (exit {proc.returncode}):\n"
            f"{proc.stdout}\n{proc.stderr}")
    with open(out_path) as f:
        results = json.load(f)
    rows = []
    for name, r in results["timing"].items():
        rows.append(f"train_step/{name},{r['steady_s'] * 1e6:.1f},"
                    f"flops/dev={r['per_device_flops']:.3e}")
    for name, r in results["census"].items():
        rows.append(f"train_step/census/{name},0.0,"
                    f"grad_collectives={r['grad_ops']} "
                    f"wire_bytes={r['staged_wire_bytes']}")
    return rows, dict(results["ok"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_train_step.json")
    args = ap.parse_args(argv)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={N_DEV}"
        ).strip()
    results = _bench(args.quick, args.out)
    for k, v in results["ok"].items():
        print(f"#  {'PASS' if v else 'FAIL'} {k}")
    return 0 if all(results["ok"].values()) else 1


if __name__ == "__main__":
    raise SystemExit(main())
