"""Sharded train-step engine: end-to-end shard_map data parallelism with
ZeRO bucket sharding, bucket-granular compressed gradient collectives, and
an opt-in GPipe stage schedule — DESIGN.md §4.

Why shard_map and not plain pjit/GSPMD: under GSPMD the data-parallel
gradient reduction is *implicit* (inserted by the partitioner inside the
backward pass), so there is no seam to compress it at — the "compressed
all-reduce" of the old train_loop path could only model the wire loss
locally. Here the whole step body is a per-device program, the collective
is an explicit ``psum``/``psum_scatter`` whose operand IS the compressed
payload (asserted on the lowered HLO by tests/test_sharded_engine.py), and
the error-feedback residual is honest per-device compressor state.

Composition with the PR-1 bucket engine (core.bucketing):

  * ZeRO state sharding — every flat bucket (params AND all optimizer
    roles) is sharded along its single axis over the dp axis
    (``sharding.bucket_pad_multiple`` makes the padded length divide). The
    per-device body all-gathers the param buckets at the top of the step
    (ZeRO-3 gather-at-use), computes full-size local gradients, and
    reduce-scatters them so the purely elementwise optimizer update runs on
    1/n_dp of every bucket.
  * bucket-granular compression — ONE quantize → psum/psum_scatter →
    dequantize per dtype bucket (vs one per leaf: O(buckets) collectives,
    benchmarks/train_step.py), residual rows living in
    ``BucketedOptState.grad_err`` with a leading per-device dim.
  * tree layout still works (params replicated, leaf-wise collectives) —
    it is the reference and the benchmark baseline.

Pipeline (opt-in, ``pipeline_axis=``): uniform single-group decoder stacks
execute through the schedule-as-data interpreter
(``pipeline.make_schedule`` + ``pipeline.run_schedule``, DESIGN.md §9)
inside the same shard_map. ``schedule=`` picks GPipe / 1F1B / interleaved
(``virtual_stages=V`` round-robins V layer chunks per device); the
backward is EXPLICIT (per-tick ``jax.vjp`` recompute at the stashed
input), so nothing is differentiated through the schedule and there is no
transposed-psum gradient scale to fix up — each leaf class has one
honest collective:

  * stage chunks: stage-local (disjoint across the pipe axis), reduced
    over dp only;
  * embedding: the lookup pullback of the interpreter's ``dxs`` cotangents
    (nonzero only on stage 0; tied models add the head's embed grad from
    stage S−1), reduced ONCE over the joint (pipe × dp) axes;
  * head (final norm + lm head): nonzero only on stage S−1, reduced ONCE
    over the joint axes.

  The joint-axis reduce IS the embed/head dedup: the legacy engine ran S
  identical dp all-reduces (one per stage row) plus an uncompressed f32
  pipe-axis psum — now a single compressed all-reduce with widened replica
  groups carries each class (S× fewer compressed wire bytes, zero
  uncompressed gradient traffic; census-gated in BENCH_train_step.json).
  Collectives launch in bucket-readiness order (``Schedule.comm_ready``:
  head closes at the last final-chunk Bwd tick, embed at the last chunk-0
  Bwd tick), matching the overlap cost model in analysis/cost_model.py.

  * dp gradient compression stays at (leaf-class × dtype) bucket
    granularity (EF residual rows in ``TrainState.grad_err``, leading dim
    = stage·dp device index: every mesh cell quantizes its OWN partial
    gradient, so compressor state is per cell);
  * real StepMetrics: the tree-layout optimizer exports RAW per-leaf metric
    partials, the engine psums the stage-local leaves' partials over the
    pipeline axis, adds the replicated leaves' once, and finalizes a single
    time (ops.finalize_metrics) — stage-partial norms combine exactly
    because the partials are plain sums;
  * MoE aux losses ride the schedule (per-tick aux masked to scheduled
    (chunk, micro) backward units, psum'd across stages);
  * per-micro CE: the interpreter computes each microbatch's head loss at
    its final-chunk Bwd tick, normalized by that micro's own token count —
    the same decomposition as train_loop.make_accum_grads.

SR + ZeRO: the counter-based noise stream indexes elements bucket-globally,
so the per-device body passes ``axis_index · padded/n_dp`` as the
per-bucket element offset into ``step_bucketed`` — every shard draws
exactly the noise the unsharded step would, making SR + ZeRO bit-identical
to SR + dp-replicated (tested at 10 steps in tests/test_sharded_engine.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import bucketing
from repro.core.collage import CollageAdamW, StepMetrics
from repro.kernels.collage_update import ops as kops
from repro.core.precision import Strategy
from repro.distributed import compression
from repro.distributed import pipeline as pp
from repro.distributed import sharding as shard_lib
from repro.models import transformer as tf
from repro.models.layers import embed_lookup
from repro.models.model import AUX_LOSS_COEF, Model
from repro.train import scopes, train_loop

Axis = Union[str, tuple]


def _axis_size(mesh: Mesh, axis: Axis) -> int:
    names = axis if isinstance(axis, tuple) else (axis,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


def _nones(k: int) -> tuple:
    return (None,) * k


def _in_groups(path) -> bool:
    """Leaf belongs to the stacked decoder groups (dim 0 = layer stack)."""
    return any(isinstance(e, jax.tree_util.DictKey) and e.key == "groups"
               for e in path)


# --------------------------------------------------------------------------
# PartitionSpecs (shard_map in/out_specs and device_put shardings)
# --------------------------------------------------------------------------

def state_pspecs(state: Any, *, axis: Axis, zero_shard: bool,
                 pipeline_axis: Optional[str] = None,
                 virtual_stages: int = 1) -> Any:
    """PartitionSpecs for a TrainState under the engine.

    grad_err leaves shard their leading per-device dim over ``axis`` (in
    pipeline mode over ``(pipeline_axis, axis)`` — each (stage, dp) cell
    quantizes a different gradient bucket, so compressor state is per
    mesh cell, not per dp rank); ZeRO buckets shard their flat axis;
    pipeline mode shards the stacked-layer dim of decoder-group leaves
    (params and their co-shaped optimizer state) over ``pipeline_axis`` —
    with ``virtual_stages > 1`` the leaves carry the (V, S, L/(S·V), …)
    round-robin chunk layout of ``pipeline.split_virtual`` and shard dim 1;
    everything else is replicated."""
    def leaf_fn(path, leaf):
        nd = getattr(leaf, "ndim", 0)
        if shard_lib._is_grad_err_leaf(path) and nd >= 1:
            if pipeline_axis is not None:
                return P((pipeline_axis,) + (axis if isinstance(axis, tuple)
                                             else (axis,)),
                         *_nones(nd - 1))
            return P(axis, *_nones(nd - 1))
        if pipeline_axis is not None and _in_groups(path) and nd >= 1:
            if virtual_stages > 1:
                return P(None, pipeline_axis, *_nones(nd - 2))
            return P(pipeline_axis, *_nones(nd - 1))
        if zero_shard and shard_lib._is_bucket_leaf(path, leaf):
            return P(axis)
        return P()
    return jax.tree_util.tree_map_with_path(leaf_fn, state)


def batch_pspecs(batch: Any, *, axis: Axis) -> Any:
    """Batch dim over the dp axis: dim 0 for (B, ...) leaves, dim 1 for
    loader-side pre-chunked (n_micro, mb, ...) batches."""
    chunked = batch["tokens"].ndim == 3

    def leaf_fn(leaf):
        nd = getattr(leaf, "ndim", 0)
        if nd == 0:
            return P()
        if chunked:
            return P(None, axis, *_nones(nd - 2))
        return P(axis, *_nones(nd - 1))
    return jax.tree_util.tree_map(leaf_fn, batch)


def named_shardings(tree: Any, pspecs: Any, mesh: Mesh) -> Any:
    del tree
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs,
                                  is_leaf=lambda x: isinstance(x, P))


def _virtualize(tree: Any, n_stages: int, n_virtual: int) -> Any:
    """Reshape every decoder-group leaf (and co-shaped optimizer state) of
    a params-like tree to the (V, S, L/(S·V), …) round-robin chunk layout
    (pipeline.split_virtual): chunk c = v·S + s at [v, s], so sharding
    dim 1 over the pipe axis hands device s its interleaved chunks with a
    uniform +1 ring and no permutation."""
    C = n_stages * n_virtual

    def fix(path, leaf):
        if _in_groups(path) and getattr(leaf, "ndim", 0) >= 1:
            L = leaf.shape[0]
            assert L % C == 0, (jax.tree_util.keystr(path), L, C)
            return leaf.reshape(n_virtual, n_stages, L // C, *leaf.shape[1:])
        return leaf
    return jax.tree_util.tree_map_with_path(fix, tree)


def init_state(model: Model, opt: CollageAdamW, key, mesh: Mesh, *,
               axis: Axis = "data", grad_compression: str = "none",
               pipeline_axis: Optional[str] = None,
               virtual_stages: int = 1) -> train_loop.TrainState:
    """TrainState with one EF-residual row per dp device (see
    train_loop.init_state). In pipeline mode the EF residual is the
    per-(leaf-class × dtype) flat-bucket dict of
    :func:`pipeline_error_state` instead of the per-leaf tree;
    ``virtual_stages > 1`` stores group leaves in the (V, S, L/(S·V), …)
    chunk layout (``virtual_stages == 1`` keeps the flat (L, …) layout —
    checkpoint-compatible with pre-interleaving states)."""
    dtype, use_ef = compression.parse_spec(grad_compression)
    if pipeline_axis is None:
        if virtual_stages != 1:
            raise ValueError("virtual_stages requires pipeline_axis")
        return train_loop.init_state(model, opt, key, grad_compression,
                                     n_dp=_axis_size(mesh, axis))
    # pipeline mode: skip the per-leaf residual tree (an (n_dp, …) zero
    # block per parameter leaf that would be discarded immediately) and
    # attach the per-leaf-class bucket rows directly
    state = train_loop.init_state(model, opt, key, "none")
    if virtual_stages > 1:
        S = mesh.shape[pipeline_axis]
        state = train_loop.TrainState(
            _virtualize(state.params, S, virtual_stages),
            _virtualize(state.opt_state, S, virtual_stages),
            state.grad_err)
    if use_ef:
        state = dataclasses.replace(
            state, grad_err=pipeline_error_state(
                state.params, mesh.shape[pipeline_axis],
                _axis_size(mesh, axis), dtype))
    return state


# --------------------------------------------------------------------------
# pipeline-mode gradient compression: (leaf class × dtype) flat buckets
# --------------------------------------------------------------------------

def _pipeline_leaf_class(path) -> str:
    """Gradient leaf class under the pipeline fixup: ``stage`` (stacked
    decoder chunks, stage-local), ``embed`` (psum'd over stages), ``head``
    (final norm + lm head, replicated across stages). Each class quantizes
    into its own flat bucket so the compressed dp collective count is
    O(classes × dtypes), not O(leaves)."""
    if _in_groups(path):
        return "stage"
    if any(isinstance(e, jax.tree_util.DictKey) and e.key == "embed"
           for e in path):
        return "embed"
    return "head"


def _pipeline_bucket_order(flat) -> dict:
    """{bucket key: [leaf index]} over ``tree_flatten_with_path`` output —
    insertion-ordered by first leaf, shared by init and the step body so
    residual rows and in-step buckets always line up."""
    order: dict = {}
    for i, (path, leaf) in enumerate(flat):
        key = f"{_pipeline_leaf_class(path)}:{jnp.dtype(leaf.dtype)}"
        order.setdefault(key, []).append(i)
    return order


def pipeline_error_state(params: Any, n_stages: int, n_dp: int,
                         dtype) -> dict:
    """Zero EF residuals for the pipeline engine: one
    ``(n_stages · n_dp, bucket_len)`` row-block per (leaf class × dtype)
    bucket. ``bucket_len`` is the PER-STAGE length (stage-chunk leaves
    contribute ``size / n_stages``); the leading dim is the flattened
    (stage, dp) device index, sharded ``P((pipeline_axis, axis))``."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    order = _pipeline_bucket_order(flat)
    rows = {}
    for key, idxs in order.items():
        length = 0
        for i in idxs:
            leaf = flat[i][1]
            size = int(leaf.size)
            if _pipeline_leaf_class(flat[i][0]) == "stage":
                # size-based so both the flat (L, …) and virtual
                # (V, S, L/(S·V), …) chunk layouts divide
                assert size % n_stages == 0, (leaf.shape, n_stages)
                size //= n_stages
            length += size
        rdt = compression.residual_dtype(dtype, flat[idxs[0]][1].dtype)
        rows[key] = jnp.zeros((n_stages * n_dp, length), rdt)
    return rows


def _compress_pipeline_grads(grads: Any, err_rows: Optional[dict], dtype,
                             axis: Axis, n_dp: int, *,
                             pipeline_axis: Optional[str] = None,
                             n_pipe: int = 1,
                             class_order: Optional[Sequence[str]] = None):
    """Bucket-granular EF-compressed mean of the per-device gradient tree:
    concat each (leaf class × dtype) bucket's leaves flat, ONE quantize →
    psum → dequantize per bucket, slice the mean back to the leaves.

    With ``pipeline_axis``, embed/head buckets reduce over the JOINT
    (pipe × dp) axes in one collective — their per-device grads are
    single-origin partials (embed nonzero on stage 0 [+ tied part on
    stage S−1], head on stage S−1), so the joint psum IS the pipe-sum +
    dp-sum and dividing by ``n_dp`` yields the dp mean. This is the
    embed/head dedup: one widened all-reduce instead of S identical
    per-stage-row dp reduces plus an uncompressed pipe psum. fp8 headroom
    widens to S·n_dp (every mesh cell ships a payload — zero rows flush
    their EF residuals through the same reduce). Stage buckets stay
    dp-only (their grads are stage-local by construction).

    ``class_order`` launches buckets in gradient-readiness order
    (Schedule.comm_ready — head closes first) so collective k sits next
    to the work that freed it in program order.

    Returns (grads in leaf dtypes, new residual rows or None)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(grads)
    order = _pipeline_bucket_order(flat)
    keys = list(order)
    if class_order is not None:
        rank = {c: r for r, c in enumerate(class_order)}
        keys.sort(key=lambda k: (rank.get(k.split(":")[0], len(rank)), k))
    new_leaves: list = [None] * len(flat)
    new_rows: Optional[dict] = {} if err_rows is not None else None
    for key in keys:
        idxs = order[key]
        parts = [flat[i][1].reshape(-1) for i in idxs]
        bucket = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        err = err_rows[key][0] if err_rows is not None else None
        if pipeline_axis is not None and key.split(":")[0] != "stage":
            red_axis: Axis = ((pipeline_axis,)
                              + (axis if isinstance(axis, tuple)
                                 else (axis,)))
            headroom: Optional[float] = float(n_pipe * n_dp)
        else:
            red_axis, headroom = axis, None
        mean32, resid = compression.pmean_compressed(bucket, err, dtype,
                                                     red_axis, n_dp,
                                                     headroom=headroom)
        if new_rows is not None:
            new_rows[key] = resid[None]
        off = 0
        for i in idxs:
            leaf = flat[i][1]
            seg = jax.lax.slice(mean32, (off,), (off + leaf.size,))
            new_leaves[i] = seg.reshape(leaf.shape).astype(leaf.dtype)
            off += leaf.size
    return treedef.unflatten(new_leaves), new_rows


def device_put_state(state, mesh: Mesh, *, axis: Axis = "data",
                     zero_shard: bool = False,
                     pipeline_axis: Optional[str] = None,
                     virtual_stages: int = 1):
    specs = state_pspecs(state, axis=axis, zero_shard=zero_shard,
                         pipeline_axis=pipeline_axis,
                         virtual_stages=virtual_stages)
    return jax.device_put(state, named_shardings(state, specs, mesh))


# --------------------------------------------------------------------------
# metrics plumbing
# --------------------------------------------------------------------------

_METRIC_KEYS = ("loss", "ce", "aux", "ppl", "edq", "update_norm",
                "imprecision_pct", "grad_norm")


def _metric_dict(loss, lmetrics, om: StepMetrics) -> dict:
    return {"loss": loss, "ce": lmetrics["ce"], "aux": lmetrics["aux"],
            "ppl": jnp.exp(lmetrics["ce"]),
            "edq": om.edq, "update_norm": om.update_norm,
            "imprecision_pct": om.imprecision_pct,
            "grad_norm": om.grad_norm}


def _zero_step_metrics() -> StepMetrics:
    return StepMetrics(*(jnp.zeros((), jnp.float32),) * 5)


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------

def make_sharded_train_step(model: Model, opt: CollageAdamW, mesh: Mesh, *,
                            axis: Axis = "data",
                            microbatch: int = 0, remat: str = "none",
                            grad_compression: str = "none",
                            zero_shard: Optional[bool] = None,
                            pipeline_axis: Optional[str] = None,
                            schedule: str = "gpipe",
                            virtual_stages: int = 1,
                            flash_min_len: Optional[int] = None,
                            donate: bool = False,
                            jit: bool = True) -> Callable:
    """Build the shard_map train step: (TrainState, batch) → (TrainState,
    metrics), with state/batch sharded per ``state_pspecs``/``batch_pspecs``.

    zero_shard (default: on iff the optimizer is bucketed and the dp axis
    has >1 device): ZeRO-shard every flat bucket over ``axis``; requires
    the layout's pad_multiple to divide (``sharding.bucket_pad_multiple``).
    grad_compression: "none" | "bf16[_ef]" | "fp8[_ef]" — quantizes the
    gradient collective at bucket granularity (bucketed) or per leaf (tree
    layout); "_ef" keeps the error-feedback residual. On the bucketed flat
    path the per-bucket collective runs through ``step_bucketed``'s
    ``reduce_fn`` hook, so collective *i* is adjacent to update *i* in
    program order (bucket-granular readiness → overlap).
    pipeline_axis: opt-in pipeline parallelism for a uniform single-group
    decoder stack (tree layout, pre-chunked batches).
    schedule: "gpipe" | "1f1b" | "interleaved" — the pipeline schedule
    compiled by pipeline.make_schedule and run by one interpreter.
    virtual_stages: virtual chunks per device (interleaved only; the
    TrainState must be built with the same value — init_state).
    flash_min_len: override of ``model.cfg.flash_min_len`` (the flash
    train-path dispatch, models/attention.py). The flash kernels compose
    with shard_map for free: the per-device body sees the LOCAL batch, so
    the Pallas grid's batch/head dims are already post-dp/tp-split sizes.
    """
    model = train_loop.with_flash(model, flash_min_len)
    bucketed = opt.policy.bucketing.enabled
    n_dp = _axis_size(mesh, axis)
    if zero_shard is None:
        zero_shard = bucketed and n_dp > 1
    dtype, use_ef = compression.parse_spec(grad_compression)

    if zero_shard:
        if not bucketed:
            raise ValueError("zero_shard requires the bucketed layout "
                             "(opt.policy.bucketing.enabled)")
        if not isinstance(axis, str):
            raise ValueError("zero_shard needs a single named dp axis")
        # every bucket length is a multiple of pad_multiple, so checking it
        # checks every shard: shards must divide the dp axis, and for fp8
        # each shard must be a whole number of scaling blocks or the
        # reduce-scattered payload's per-block scales misalign silently
        need = n_dp * (compression.BLOCK
                       if dtype is not None and compression.is_fp8(dtype)
                       else 1)
        pad = opt.policy.bucketing.pad_multiple
        if pad % need:
            raise ValueError(
                f"bucket pad_multiple {pad} must be a multiple of {need} "
                f"for ZeRO over {n_dp} devices"
                + (" with fp8 block scaling" if need > n_dp else "")
                + " — build the BucketPolicy with "
                "sharding.bucket_pad_multiple(mesh, block=compression.BLOCK)")
    if pipeline_axis is None:
        if schedule != "gpipe" or virtual_stages != 1:
            raise ValueError("schedule/virtual_stages require pipeline_axis")
    else:
        if bucketed or zero_shard:
            raise ValueError("pipeline mode requires the tree layout")
        if opt.use_fused_kernel:
            # fail at build time, not mid-trace: the pipeline body needs
            # the tree-layout step (per-leaf metric partials; the fused
            # shim re-flattens and reduces per bucket)
            raise ValueError("pipeline mode requires the tree-layout "
                             "optimizer step (use_fused_kernel=False)")
        if schedule not in pp.SCHEDULES:
            raise ValueError(f"unknown schedule {schedule!r}; "
                             f"one of {pp.SCHEDULES}")
        if schedule != "interleaved" and virtual_stages != 1:
            raise ValueError(f"virtual_stages={virtual_stages} requires "
                             f"schedule='interleaved' (got {schedule!r})")
        if schedule == "interleaved" and virtual_stages < 2:
            raise ValueError("interleaved schedule needs virtual_stages>=2")
        _check_pipelinable(model,
                           mesh.shape[pipeline_axis] * virtual_stages)

    accum = train_loop.make_accum_grads(model, microbatch=microbatch,
                                        remat=remat)

    def pmean32(x, ax):
        return (jax.lax.psum(x.astype(jnp.float32), ax) / n_dp).astype(x.dtype)

    # ---------------------------------------------------- per-device body --
    def body(state: train_loop.TrainState, batch):
        if pipeline_axis is not None:
            return _pipeline_body(state, batch)
        opt_state = state.opt_state
        params = state.params
        grad_err = state.grad_err
        if bucketed and zero_shard:
            with jax.named_scope(scopes.PARAM_GATHER):
                full = bucketing.BucketedParams(
                    tuple(jax.lax.all_gather(d, axis, tiled=True)
                          for d in params.data), params.layout)
        else:
            full = params
        loss, lmetrics, grads = accum(full, batch)
        loss = jax.lax.pmean(loss, axis)
        lmetrics = {k: jax.lax.pmean(lmetrics[k], axis)
                    for k in ("ce", "aux")}

        if bucketed:
            err_rows = tuple(e[0] for e in opt_state.grad_err) \
                if use_ef else None
            # Per-bucket readiness → collective launch: each bucket's
            # reduce (compressed or plain) runs through step_bucketed's
            # reduce_fn hook, immediately before that bucket's fused
            # update — collective i is adjacent to update i in program
            # order, so the scheduler can hide collective i+1 under
            # update i instead of paying one serialized all-reduce wall
            # (the modeled win is gated by analysis.cost_model /
            # benchmarks). Residuals surface via a trace-time list: the
            # hook runs while the optimizer step traces, so the tracers
            # are in scope when the new opt state is assembled below.
            new_rows: list = [None] * params.layout.n_buckets

            def reduce_bucket(i, g):
                with jax.named_scope(scopes.GRAD_REDUCE):
                    if dtype is not None:
                        e = err_rows[i] if use_ef else None
                        red = compression.psum_scatter_compressed \
                            if zero_shard else compression.pmean_compressed
                        m, r = red(g, e, dtype, axis, n_dp)
                        new_rows[i] = r
                        return m.astype(g.dtype)
                    if zero_shard:
                        return (jax.lax.psum_scatter(
                            g.astype(jnp.float32), axis, scatter_dimension=0,
                            tiled=True) / n_dp).astype(g.dtype)
                    return pmean32(g, axis)

            offs = None
            if zero_shard and opt.policy.strategy is Strategy.SR:
                # counter-based SR under ZeRO: this shard's elements start
                # at axis_index · padded/n_dp inside each full bucket —
                # passing that offset makes the noise stream bucket-global,
                # so the sharded update is bit-identical to the unsharded
                # one (the shard boundary never shows in the noise)
                idx = jax.lax.axis_index(axis).astype(jnp.uint32)
                offs = tuple(idx * jnp.uint32(b.padded // n_dp)
                             for b in params.layout.buckets)
            with jax.named_scope(scopes.OPTIMIZER):
                if zero_shard and opt.compute_metrics:
                    # cross-shard StepMetrics: the optimizer exports its RAW
                    # (5,) metric partials (kernels.collage_update.ops), the
                    # engine psums them over the dp axis and finalizes ONCE
                    # — definitionally exact, no hand-maintained inverse of
                    # the finalize step
                    new_params, new_opt, parts = opt.step_bucketed(
                        grads.data, params, opt_state, metrics_partials=True,
                        elem_offsets=offs, reduce_fn=reduce_bucket)
                    om = kops.finalize_metrics(jax.lax.psum(parts, axis),
                                               params.layout.total_size)
                else:
                    new_params, new_opt, om = opt.step_bucketed(
                        grads.data, params, opt_state, elem_offsets=offs,
                        reduce_fn=reduce_bucket)
            if use_ef and dtype is not None:
                new_opt = dataclasses.replace(
                    new_opt, grad_err=tuple(r[None] for r in new_rows))
        else:
            with jax.named_scope(scopes.GRAD_REDUCE):
                if dtype is not None:
                    # residual leaves carry a per-device dim: strip this
                    # device's row for the shared leaf-wise reducer,
                    # restore it for the out specs
                    err_plain = jax.tree_util.tree_map(
                        lambda e: e[0], grad_err) if use_ef else None
                    grads, new_err = compression.pmean_compressed_tree(
                        grads, err_plain, dtype, axis, n_dp)
                    if use_ef:
                        grad_err = jax.tree_util.tree_map(lambda r: r[None],
                                                          new_err)
                else:
                    grads = jax.tree_util.tree_map(
                        lambda g: pmean32(g, axis), grads)
            with jax.named_scope(scopes.OPTIMIZER):
                new_params, new_opt, om = opt.step(grads, params, opt_state)
        return (train_loop.TrainState(new_params, new_opt, grad_err),
                _metric_dict(loss, lmetrics, om))

    # --------------------------------------------------- pipeline variant --
    S = mesh.shape[pipeline_axis] if pipeline_axis is not None else 1
    V = virtual_stages

    def _pipeline_body(state, batch):
        params = state.params
        cfg = model.cfg
        group = cfg.decoder_program()[0]
        n_micro = batch["tokens"].shape[0]
        sched = pp.make_schedule(schedule, n_stages=S, n_micro=n_micro,
                                 n_virtual=V)

        def chunk_body(chunk_p, h):
            return tf.group_apply(chunk_p, h, group, cfg, remat=remat)

        # Local chunk params with a leading (V, …) chunk dim for the
        # interpreter. V == 1 keeps the flat stored layout (L/S, …);
        # V > 1 stores (V, S, L/(S·V), …) sharded on dim 1, locally
        # (V, 1, Lc, …).
        g0 = params["decoder"]["groups"][0]
        if V == 1:
            chunk_params = jax.tree_util.tree_map(lambda p: p[None], g0)
        else:
            chunk_params = jax.tree_util.tree_map(lambda p: p[:, 0], g0)

        # Head = final norm + lm head (the TIED embedding when
        # cfg.tie_embeddings); computed ONLY at final-chunk Bwd ticks
        # inside the interpreter — head grads are single-origin (stage
        # S−1), not replicated, so their collective is one joint-axis
        # reduce, never an S-fold.
        tied = cfg.tie_embeddings
        head_params = {"norm": params["decoder"]["final_norm"],
                       "w": params["embed"] if tied else params["lm_head"]}

        def head_loss_fn(hp, y, lab):
            pseudo = {"decoder": {"final_norm": hp["norm"]},
                      ("embed" if tied else "lm_head"): hp["w"]}
            return model.token_ce(model._head(pseudo, y), lab)

        xs = embed_lookup(params["embed"], batch["tokens"])
        out = pp.run_schedule(sched, chunk_body, head_loss_fn,
                              chunk_params, head_params, xs,
                              batch["labels"], axis=pipeline_axis)

        # Embedding grad: pull the interpreter's dxs cotangents (nonzero
        # only on the chunk-0 device) back through the lookup; the tied
        # head contribution (nonzero only on stage S−1) adds in f32. The
        # joint (pipe × dp) reduce below recovers the total — no leaf is
        # ever replicated-then-summed, so no 1/S fixup exists on this
        # path (contrast stage_schedule's transposed psum, DESIGN.md §9).
        (g_embed,) = jax.vjp(
            lambda emb: embed_lookup(emb, batch["tokens"]),
            params["embed"])[1](out["dxs"].astype(xs.dtype))
        if tied:
            # the head contribution adds in f32 (the tied leaf is the one
            # place two gradient paths meet); untied keeps the pullback's
            # stored dtype — widening here would be a pure double-round
            g_embed = (g_embed.astype(jnp.float32)
                       + out["g_head"]["w"]).astype(params["embed"].dtype)

        def to_stored(g, p):
            g = g[0] if V == 1 else g[:, None]
            return g.astype(p.dtype)

        grads = {
            "embed": g_embed,
            "decoder": {
                "groups": [jax.tree_util.tree_map(to_stored,
                                                  out["g_chunks"], g0)],
                "final_norm": out["g_head"]["norm"].astype(
                    params["decoder"]["final_norm"].dtype),
            },
        }
        if not tied:
            grads["lm_head"] = out["g_head"]["w"].astype(
                params["lm_head"].dtype)

        # collectives in bucket-readiness order (head closes first: its
        # last contributing Bwd tick precedes the stage/embed closes)
        class_order = sorted(sched.comm_ready,
                             key=lambda c: sched.comm_ready[c])
        grad_err = state.grad_err
        joint_axis = (pipeline_axis,) + (axis if isinstance(axis, tuple)
                                         else (axis,))
        if dtype is not None:
            # (leaf class × dtype) bucket granularity: ONE compressed
            # all-reduce per bucket — stage over dp, embed/head over the
            # joint (pipe × dp) axes (the dedup: no per-stage-row
            # repetition, no uncompressed pipe psum)
            grads, new_rows = _compress_pipeline_grads(
                grads, grad_err if use_ef else None, dtype, axis, n_dp,
                pipeline_axis=pipeline_axis, n_pipe=S,
                class_order=class_order)
            if use_ef:
                grad_err = new_rows
        else:
            def reduce_leaf(path, g):
                if _pipeline_leaf_class(path) == "stage":
                    return pmean32(g, axis)
                return (jax.lax.psum(g.astype(jnp.float32), joint_axis)
                        / n_dp).astype(g.dtype)
            grads = jax.tree_util.tree_map_with_path(reduce_leaf, grads)

        # loss decomposition: ce/aux are SUMS over micros on their owning
        # devices — psum over pipe, /n_micro (per-micro CE matches the
        # unpipelined accum's microbatch decomposition)
        ce = jax.lax.psum(out["ce"], pipeline_axis) / n_micro
        aux = jax.lax.psum(out["aux"], pipeline_axis) / n_micro
        loss = jax.lax.pmean(ce + AUX_LOSS_COEF * aux, axis)
        lmetrics = {"ce": jax.lax.pmean(ce, axis),
                    "aux": jax.lax.pmean(aux, axis)}
        if opt.compute_metrics:
            # real StepMetrics: raw per-leaf partials, stage-local leaves
            # psum'd over the pipeline axis (disjoint chunks sum exactly),
            # replicated leaves counted once, finalized ONCE — the same
            # scalar-partials scheme as the ZeRO path
            new_params, new_opt, parts = opt.step(
                grads, params, state.opt_state, metrics_partials=True)
            flat, _ = jax.tree_util.tree_flatten_with_path(grads)
            zero5 = (jnp.float32(0.0),) * 5
            stage_tot, shared_tot = zero5, zero5
            count = 0
            for (path, leaf), part in zip(flat, parts):
                if _pipeline_leaf_class(path) == "stage":
                    stage_tot = tuple(a + p
                                      for a, p in zip(stage_tot, part))
                    count += leaf.size * S
                else:
                    shared_tot = tuple(a + p
                                       for a, p in zip(shared_tot, part))
                    count += leaf.size
            stage_tot = jax.lax.psum(stage_tot, pipeline_axis)
            om = kops.finalize_metrics(
                tuple(a + b for a, b in zip(stage_tot, shared_tot)), count)
        else:
            new_params, new_opt, _ = opt.step(grads, params,
                                              state.opt_state)
            om = _zero_step_metrics()
        return (train_loop.TrainState(new_params, new_opt, grad_err),
                _metric_dict(loss, lmetrics, om))

    # ------------------------------------------------------------ wrapper --
    def step(state, batch):
        sspecs = state_pspecs(state, axis=axis, zero_shard=zero_shard,
                              pipeline_axis=pipeline_axis,
                              virtual_stages=virtual_stages)
        bspecs = batch_pspecs(batch, axis=axis)
        mspecs = {k: P() for k in _METRIC_KEYS}
        fn = jax.shard_map(body, mesh=mesh, in_specs=(sspecs, bspecs),
                           out_specs=(sspecs, mspecs), check_vma=False)
        return fn(state, batch)

    if jit:
        return jax.jit(step, donate_argnums=(0,) if donate else ())
    return step


def _check_pipelinable(model: Model, n_stages: int):
    cfg = model.cfg
    prog = cfg.decoder_program()
    if cfg.is_encdec or cfg.family == "vlm":
        raise ValueError("pipeline mode: decoder-only models only")
    if len(prog) != 1:
        raise ValueError(
            f"pipeline mode needs a uniform single-group decoder stack, "
            f"got {len(prog)} groups")
    group = prog[0]
    if any(s.kind == "cross_attn" for s in group.period):
        raise ValueError("pipeline mode: cross-attn groups unsupported")
    if group.repeats % n_stages:
        raise ValueError(
            f"decoder depth {group.repeats} not divisible by "
            f"{n_stages} pipeline stages")
