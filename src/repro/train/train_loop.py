"""Training loop: Collage-precision train_step with microbatched gradient
accumulation, remat, optional compressed gradient all-reduce, metrics.

The step function is pure (TrainState → TrainState) and jit/pjit-friendly —
the same function is used by the CPU examples, the distributed launcher and
the multi-pod dry-run. The *sharded* engine (``train/sharded.py``) reuses
this module's gradient accumulation and state containers but runs the whole
step under ``shard_map`` so the gradient collective is explicit (and
compressible); ``make_train_step`` here stays the single-program reference.

Two parameter layouts are supported transparently (DESIGN.md §5):

  * tree layout: ``TrainState.params`` is the model pytree, optimizer state
    is a per-leaf CollageOptState — the reference path. The error-feedback
    residual of gradient compression lives per-leaf in
    ``TrainState.grad_err``.
  * bucket layout (``opt.policy.bucketing.enabled``): params and ALL
    optimizer state persist as flat buckets (core.bucketing). The loss is
    computed against ``params.tree()`` — the only place leaf views are
    materialized — so ``jax.grad`` yields flat gradient buckets and the
    optimizer step runs with zero per-step flatten/concat traffic. Gradient
    compression happens at BUCKET granularity (one quantize/round-trip per
    dtype bucket) and its residual lives bucket-resident in
    ``BucketedOptState.grad_err``; ``TrainState.grad_err`` stays None.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import bucketing
from repro.core.collage import CollageAdamW
from repro.distributed import compression
from repro.models.model import Model
from repro.train import scopes


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class TrainState:
    params: Any                      # model pytree OR BucketedParams
    opt_state: Any                   # CollageOptState OR BucketedOptState
    grad_err: Optional[Any]          # per-leaf EF residual (tree layout)

    def tree_flatten_with_keys(self):
        # keyed registration is load-bearing: the sharded engine's spec
        # rules identify EF residual leaves by the GetAttrKey("grad_err")
        # path segment (an unkeyed node would yield FlattenedIndexKeys and
        # the per-device residual dim would silently lose its sharding)
        g = jax.tree_util.GetAttrKey
        return (((g("params"), self.params),
                 (g("opt_state"), self.opt_state),
                 (g("grad_err"), self.grad_err)), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_state(model: Model, opt: CollageAdamW, key,
               grad_compression: str = "none",
               n_dp: Optional[int] = None) -> TrainState:
    """Build a fresh TrainState.

    ``n_dp``: None for the single-program step below; an integer (the dp
    axis size — 1 included) for the sharded engine, whose EF-compression
    residuals ALWAYS carry a leading per-device dim so the shard_map specs
    are layout-independent of the axis size. The residual template is built
    from the GRADIENT structure — identical to params for the tree layout,
    the flat bucket tuple for the bucketed layout (where a params-shaped
    template would miss the bucket granularity and pick the wrong dtype).
    Pipeline-mode engines replace the tree residual with the per-leaf-class
    flat-bucket dict of ``sharded.pipeline_error_state`` (built by
    ``sharded.init_state(pipeline_axis=...)``)."""
    params = model.init(key)
    if opt.policy.bucketing.enabled:
        params, opt_state = opt.init_bucketed(params)
    else:
        opt_state = opt.init(params)
    dtype, use_ef = compression.parse_spec(grad_compression)
    err = None
    if use_ef:
        if isinstance(params, bucketing.BucketedParams):
            rows = compression.init_error_state(params, dtype)
            if n_dp is not None and n_dp > 1:
                rows = tuple(jnp.tile(r, (n_dp, 1)) for r in rows)
            opt_state = dataclasses.replace(opt_state, grad_err=rows)
        else:
            err = compression.init_error_state(params, dtype)
            if n_dp is not None:
                err = jax.tree_util.tree_map(
                    lambda e: jnp.tile(e[None], (n_dp,) + (1,) * e.ndim), err)
    return TrainState(params, opt_state, err)


def with_flash(model: Model, flash_min_len: Optional[int]) -> Model:
    """Step-builder override of ``cfg.flash_min_len`` (None = keep cfg).

    The flash dispatch itself lives in the model (models/attention.py);
    this hook lets a launcher flip it per-step-function without rebuilding
    configs — the sharded engine threads it the same way so a flash train
    step and a masked eval step can share one model object."""
    if flash_min_len is None:
        return model
    cfg = dataclasses.replace(model.cfg, flash_min_len=int(flash_min_len))
    return dataclasses.replace(model, cfg=cfg)


def make_accum_grads(model: Model, *, microbatch: int = 0,
                     remat: str = "none",
                     flash_min_len: Optional[int] = None) -> Callable:
    """Build ``accum(params, batch) → (loss, metrics, grads)``.

    Shared by the single-program step below and the sharded engine.
    microbatch > 0: split the (local) batch into chunks of that many rows
    and accumulate grads in fp32 with a lax.scan (bounded activation
    memory — the paper's Table 8 trade-off). Pre-chunked (n, mb, L) batches
    are consumed as-is (loader-side chunking avoids a GSPMD reshape of the
    dp-sharded batch dim). flash_min_len overrides the model's flash
    dispatch threshold (``with_flash``)."""
    model = with_flash(model, flash_min_len)

    def loss_fn(params, batch):
        if isinstance(params, bucketing.BucketedParams):
            # model-apply boundary: the ONLY place bucket views materialize
            with jax.named_scope(scopes.BUCKET_VIEWS):
                params = params.tree()
        with jax.named_scope(scopes.FORWARD):
            return model.loss(params, batch, remat=remat)

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return loss, metrics, grads

    def accum_grads(params, batch):
        pre_chunked = batch["tokens"].ndim == 3  # loader-side (n, mb, L)
        if not microbatch and not pre_chunked:
            return grads_of(params, batch)
        if pre_chunked:
            n = batch["tokens"].shape[0]
            chunks = batch
        else:
            B = batch["tokens"].shape[0]
            assert B % microbatch == 0, (B, microbatch)
            n = B // microbatch
            chunks = jax.tree_util.tree_map(
                lambda x: x.reshape((n, microbatch) + x.shape[1:]), batch)

        def body(carry, mb):
            acc, loss_acc, ce_acc, aux_acc = carry
            loss, m, grads = grads_of(params, mb)
            acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), acc, grads)
            return (acc, loss_acc + loss, ce_acc + m["ce"],
                    aux_acc + m["aux"]), None

        zero = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, loss_sum, ce_sum, aux_sum), _ = jax.lax.scan(
            body, (zero, 0.0, 0.0, 0.0), chunks)
        grads = jax.tree_util.tree_map(
            lambda g, p: (g / n).astype(p.dtype), gsum, params)
        loss = loss_sum / n
        ce = ce_sum / n                  # CE alone — the total includes
        aux = aux_sum / n                # 0.01·aux on MoE configs
        return loss, {"ce": ce, "aux": aux, "ppl": jnp.exp(ce)}, grads

    return accum_grads


def _apply_opt(opt: CollageAdamW, grads, params, opt_state):
    if isinstance(params, bucketing.BucketedParams):
        return opt.step_bucketed(grads, params, opt_state)
    return opt.step(grads, params, opt_state)


def make_train_step(model: Model, opt: CollageAdamW, *,
                    microbatch: int = 0, remat: str = "none",
                    grad_compression: str = "none",
                    psum_axis: Optional[str] = None,
                    flash_min_len: Optional[int] = None) -> Callable:
    """Build the pure train_step(state, batch) → (state, metrics).

    psum_axis: when run under shard_map, the named axis to pmean gradients
    over. With compression, the quantize happens BEFORE the collective and
    the payload on the wire IS the compressed dtype (asserted on the lowered
    HLO by tests/test_sharded_engine.py); without an explicit axis (plain
    pjit/GSPMD inserts the reduction itself) compression degrades to a local
    round-trip that *models* the wire loss — use train/sharded.py for the
    real compressed collective.
    """
    accum_grads = make_accum_grads(model, microbatch=microbatch, remat=remat,
                                   flash_min_len=flash_min_len)
    dtype, use_ef = compression.parse_spec(grad_compression)

    def train_step(state: TrainState, batch):
        loss, lmetrics, grads = accum_grads(state.params, batch)
        grad_err = state.grad_err
        opt_state = state.opt_state
        if dtype is not None:
            if psum_axis is not None:
                # psum of a python scalar folds to the static axis size
                n_dev = jax.lax.psum(1, psum_axis)
            if isinstance(grads, bucketing.BucketedParams):
                # bucket granularity: one round-trip per dtype bucket; the
                # residual lives in BucketedOptState.grad_err (rows are
                # per-dp-device; this single-program path is row 0)
                err = None
                if use_ef:
                    err = tuple(e[0] for e in opt_state.grad_err)
                if psum_axis is not None:
                    gdata, new_err = compression.pmean_compressed_buckets(
                        grads.data, err, dtype, psum_axis, n_dev)
                else:
                    gdata, new_err = [], []
                    for g, e in zip(grads.data,
                                    err or [None] * len(grads.data)):
                        deq, r = compression.compress_decompress(g, e, dtype)
                        gdata.append(deq.astype(g.dtype))
                        new_err.append(r)
                grads = bucketing.BucketedParams(tuple(gdata), grads.layout)
                if use_ef:
                    opt_state = dataclasses.replace(
                        opt_state,
                        grad_err=tuple(r[None] for r in new_err))
            else:
                if psum_axis is not None:
                    grads, new_err = compression.pmean_compressed_tree(
                        grads, grad_err if use_ef else None, dtype,
                        psum_axis, n_dev)
                    if use_ef:
                        grad_err = new_err
                else:
                    grads, new_err = compression.compress_tree(
                        grads, grad_err if use_ef else None, dtype)
                    if use_ef:
                        grad_err = new_err
        elif psum_axis is not None:
            grads = jax.lax.pmean(grads, psum_axis)
        with jax.named_scope(scopes.OPTIMIZER):
            params, opt_state, ometrics = _apply_opt(opt, grads,
                                                     state.params, opt_state)
        metrics = {"loss": loss, **lmetrics,
                   "edq": ometrics.edq, "update_norm": ometrics.update_norm,
                   "imprecision_pct": ometrics.imprecision_pct,
                   "grad_norm": ometrics.grad_norm}
        return TrainState(params, opt_state, grad_err), metrics

    return train_step


def make_eval_step(model: Model) -> Callable:
    def eval_step(params, batch):
        if isinstance(params, bucketing.BucketedParams):
            params = params.tree()
        loss, metrics = model.loss(params, batch)
        return metrics
    return eval_step
