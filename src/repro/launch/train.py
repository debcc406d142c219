"""Training launcher: end-to-end driver (CPU-runnable; same step function the
dry-run lowers for the production meshes).

  PYTHONPATH=src python -m repro.launch.train --arch gpt-tiny --steps 200 \
      --precision C [--resume] [--smoke]

Distributed (shard_map engine, train/sharded.py): ``--dp N`` runs the
data-parallel sharded step (+ ``--zero`` for ZeRO bucket sharding with
``--bucketed``, ``--pipeline-stages S`` with ``--schedule
gpipe|1f1b|interleaved`` for the schedule-as-data pipeline engine on
uniform decoder stacks; interleaved takes ``--virtual-stages V``). On CPU
this needs ``XLA_FLAGS=--xla_force_host_platform_device_count=<dp·stages>``
exported BEFORE launch (jax locks the device count at first use).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import tempfile
import time
from typing import Any

import jax

from repro.configs import get_config, with_layers
from repro.configs.base import ShapeConfig
from repro.core.collage import CollageAdamW, cosine_schedule
from repro.core.precision import BucketPolicy, PrecisionPolicy, parse_strategy
from repro.data.synthetic import make_batch_fn
from repro.distributed import compression
from repro.distributed import sharding as shard_lib
from repro.launch import compile_cache
from repro.launch import mesh as mesh_lib
from repro.models.model import build_model
from repro.train import checkpoint as ckpt_lib
from repro.train import sharded
from repro.train import train_loop
from repro.train.elastic import RunSupervisor, SupervisorConfig


def build(args):
    cfg = with_layers(get_config(args.arch, smoke=args.smoke), args.layers)
    shape = ShapeConfig("custom", args.seq_len, args.batch, "train")
    model = build_model(cfg)
    mesh = None
    pipeline_axis = "pipe" if args.pipeline_stages > 1 else None
    if args.dp > 1 or pipeline_axis:
        if pipeline_axis:
            mesh = mesh_lib.auto_mesh((args.pipeline_stages, args.dp),
                                      ("pipe", "data"))
        else:
            mesh = mesh_lib.auto_mesh((args.dp,), ("data",))
    pad = shard_lib.bucket_pad_multiple(mesh, block=compression.BLOCK) if mesh is not None \
        else None
    bucket_policy = BucketPolicy(enabled=args.bucketed) if pad is None else \
        BucketPolicy(enabled=args.bucketed, pad_multiple=pad)
    policy = PrecisionPolicy(strategy=parse_strategy(args.precision),
                             bucketing=bucket_policy)
    opt = CollageAdamW(
        cosine_schedule(args.lr, args.warmup, args.steps),
        b1=0.9, b2=args.b2, weight_decay=args.weight_decay, policy=policy,
        compute_metrics=not args.no_metrics,
        use_fused_kernel=args.fused_kernel, sr_seed=args.sr_seed)
    if mesh is not None:
        # explicit --zero passes True so the engine can reject invalid
        # combinations loudly; absent → None lets it auto-enable for
        # bucketed dp>1 layouts
        step_fn = sharded.make_sharded_train_step(
            model, opt, mesh, axis="data", microbatch=args.microbatch,
            remat=args.remat, grad_compression=args.grad_compression,
            zero_shard=True if args.zero else None,
            pipeline_axis=pipeline_axis,
            schedule=args.schedule if pipeline_axis else "gpipe",
            virtual_stages=args.virtual_stages if pipeline_axis else 1,
            flash_min_len=args.flash_min_len)
    else:
        # the old state is dead once the step returns: donating it lets
        # the new params/optimizer state reuse its buffers in place
        step_fn = jax.jit(train_loop.make_train_step(
            model, opt, microbatch=args.microbatch, remat=args.remat,
            grad_compression=args.grad_compression,
            flash_min_len=args.flash_min_len), donate_argnums=(0,))
    batch_fn = make_batch_fn(cfg, shape, seed=args.seed)
    return cfg, model, opt, step_fn, batch_fn, mesh, pipeline_axis


@dataclasses.dataclass
class TrainRun:
    """What ``main`` returns: the logged metrics plus the host-clock facts
    a caller (``chip_smoke.py``) reports — nothing here is a device
    metric."""

    history: list        # logged metric dicts, one per logged step
    compile_s: float     # ahead-of-time compile of the step
    step_s: list         # per-step seconds, after block_until_ready
    compiled: Any        # the compiled step (HLO text, memory analysis)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt-tiny")
    ap.add_argument("--precision", default="C")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--lr", type=float, default=6e-4)
    ap.add_argument("--b2", type=float, default=0.95)
    ap.add_argument("--weight-decay", type=float, default=0.1)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--grad-compression", default="none")
    ap.add_argument("--fused-kernel", action="store_true")
    ap.add_argument("--bucketed", action="store_true",
                    help="persistent flat-bucket params/opt-state (DESIGN.md §5)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel devices for the shard_map engine "
                         "(train/sharded.py); 1 = single-program step")
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO-shard the flat buckets over the dp axis "
                         "(needs --bucketed; composes with --precision SR "
                         "— the counter-based noise stream is shard-offset "
                         "so the sharded run is bit-identical)")
    ap.add_argument("--pipeline-stages", type=int, default=1,
                    help="pipeline stages over a 'pipe' mesh axis (uniform "
                         "decoder stacks incl. MoE; batch is chunked to "
                         "--microbatch rows per microbatch; composes with "
                         "--grad-compression on the dp axis)")
    ap.add_argument("--schedule", default="gpipe",
                    choices=("gpipe", "1f1b", "interleaved"),
                    help="pipeline schedule IR to compile "
                         "(distributed/pipeline.py make_schedule); "
                         "interleaved needs --virtual-stages >= 2 and "
                         "n_micro %% stages == 0")
    ap.add_argument("--virtual-stages", type=int, default=1,
                    help="virtual chunks per device for the interleaved "
                         "schedule (layer stacks reshaped to "
                         "(V, S, L/(S*V), ...))")
    ap.add_argument("--xla-latency-hiding", action="store_true",
                    help="enable XLA's latency-hiding scheduler + async "
                         "collective streams (GPU backends; parsed but "
                         "inert on CPU — informational there). Appended to "
                         "XLA_FLAGS before first device use")
    ap.add_argument("--sr-seed", type=int, default=0,
                    help="stochastic-rounding noise seed (--precision SR)")
    ap.add_argument("--flash-min-len", type=int, default=None,
                    help="dispatch causal self-attention to the Pallas "
                         "flash custom-VJP kernels when seq_len >= this "
                         "(0 = masked/banded jnp paths, unset = config "
                         "default; the flash train step has no O(L^2) "
                         "score buffer in either pass)")
    ap.add_argument("--no-metrics", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the decoder to this many layers (a whole "
                         "number of layer periods); widths stay published")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=os.path.join(
        tempfile.gettempdir(), "repro_train_ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default=None)
    ap.add_argument("--trace-dir", default=None,
                    help="record the --trace-steps with the JAX profiler "
                         "into this directory (README: profiling)")
    ap.add_argument("--trace-steps", type=_step_range, default=(1, 3),
                    metavar="A:B", help="steps A..B-1 to record")
    return ap.parse_args(argv)


def _step_range(text: str) -> tuple:
    a, sep, b = text.partition(":")
    if not sep or not a.isdigit() or not b.isdigit() or int(a) >= int(b):
        raise argparse.ArgumentTypeError(f"want A:B with A < B, got {text!r}")
    return int(a), int(b)


def init_state(model, opt, seed: int, grad_compression: str = "none"):
    """The single-program step's fresh state, made by one jitted call:
    made op by op, the init's intermediate buffers set the run's peak
    device memory."""
    make = jax.jit(lambda key: train_loop.init_state(
        model, opt, key, grad_compression))
    return make(jax.random.PRNGKey(seed))


def main(argv=None) -> TrainRun:
    args = parse_args(argv)

    if args.xla_latency_hiding:
        # must land in XLA_FLAGS before the first backend init (imports
        # don't trigger it; building the mesh below does). The flags are
        # registered on every backend but only move the schedule on GPU —
        # SNIPPETS latency-hiding recipe.
        lh = ("--xla_gpu_enable_latency_hiding_scheduler=true "
              "--xla_gpu_enable_highest_priority_async_stream=true")
        os.environ["XLA_FLAGS"] = \
            (os.environ.get("XLA_FLAGS", "") + " " + lh).strip()
        if jax.default_backend() == "cpu":
            print("[xla-latency-hiding] CPU backend: flags parsed but "
                  "scheduling is unchanged (informational)")

    compile_cache.configure()
    cfg, model, opt, step_fn, batch_fn, mesh, pipeline_axis = build(args)
    if mesh is not None:
        vstages = args.virtual_stages if pipeline_axis else 1
        state = sharded.init_state(model, opt, jax.random.PRNGKey(args.seed),
                                   mesh, axis="data",
                                   grad_compression=args.grad_compression,
                                   pipeline_axis=pipeline_axis,
                                   virtual_stages=vstages)
        zero_eff = args.zero or (args.bucketed and args.dp > 1
                                 and pipeline_axis is None)
        state = sharded.device_put_state(
            state, mesh, axis="data", zero_shard=zero_eff,
            pipeline_axis=pipeline_axis, virtual_stages=vstages)
        if pipeline_axis is not None and not args.microbatch:
            raise SystemExit("--pipeline-stages needs --microbatch (the "
                             "GPipe schedule consumes (n_micro, mb, L) "
                             "chunked batches)")
        if pipeline_axis is not None:
            raw_batch_fn = batch_fn
            mb = args.microbatch

            def batch_fn(i):   # noqa: F811 — pipeline wants (n, mb, L)
                return jax.tree_util.tree_map(
                    lambda x: x.reshape((x.shape[0] // mb, mb) + x.shape[1:]),
                    raw_batch_fn(i))
    else:
        state = init_state(model, opt, args.seed, args.grad_compression)
    start = 0
    if args.resume:
        latest = ckpt_lib.latest_step(args.ckpt_dir)
        if latest is not None:
            state, extra = ckpt_lib.restore_bucketed(args.ckpt_dir, latest,
                                                     state)
            start = extra["step"]
            print(f"resumed from step {start}")

    # compile once, ahead of time: the step's compile time is set-up, and
    # the executable (HLO, memory analysis) is what callers inspect
    t0 = time.perf_counter()
    compiled = step_fn.lower(state, batch_fn(start)).compile()
    compile_s = time.perf_counter() - t0
    print(f"compiled train step in {compile_s:.1f}s")

    sup = RunSupervisor(SupervisorConfig(
        args.ckpt_dir, args.ckpt_every, trace_dir=args.trace_dir,
        trace_steps=args.trace_steps))
    history, step_s = [], []
    t0 = time.time()

    def logged_step(state, batch):
        ts = time.perf_counter()
        with jax.profiler.TraceAnnotation("repro.step"):
            state, metrics = jax.block_until_ready(compiled(state, batch))
        step_s.append(time.perf_counter() - ts)
        with jax.profiler.TraceAnnotation("repro.log"):
            step = int(state.opt_state.step)
            if step % args.log_every == 0 or step == 1:
                m = {k: float(v) for k, v in metrics.items()}
                m["step"] = step
                history.append(m)
                print(f"step {step:5d} loss {m['loss']:.4f} ppl {m['ppl']:.2f} "
                      f"edq {m.get('edq', 0):.3e} impr% {m.get('imprecision_pct', 0):.2f}")
        return state, metrics

    state, step, _ = sup.run(state, logged_step, batch_fn, args.steps,
                             start_step=start)
    dt = time.time() - t0
    print(f"done: {step} steps, {dt:.1f}s host wall")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(history, f)
    return TrainRun(history, compile_s, step_s, compiled)


if __name__ == "__main__":
    main()
