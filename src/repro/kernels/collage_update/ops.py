"""Bucketed execution engine: one fused launch per persistent flat bucket.

HBM traffic per param (bf16): Collage-plus = 6 reads + 5 writes = 22 B;
option D's unfused path = 4×4B reads + 3×4B writes = 28 B *plus* the extra
kernel-launch round-trips of the unfused implementation (each elementwise op
re-reads its operands). The fused kernel is the Remark 5.2 realization — and
with the bucketing layout (core.bucketing) the flat view is persistent, so
the steady-state step contains NO concatenate / dynamic_slice of parameter
buckets at all (asserted on the jaxpr by tests/test_bucketing.py).

Two entrypoints:

  * ``bucketed_step``: the first-class path. Params/optimizer state live as
    BucketedParams / BucketedOptState; gradients arrive as flat buckets
    (taking ``jax.grad`` w.r.t. BucketedParams yields them directly). Zero
    per-step flatten/concat work.
  * ``fused_step``: tree-compat shim behind ``CollageAdamW.step(use_fused_
    kernel=True)``. It still flattens/concats the pytree every call (that is
    what the bucketed path eliminates) but now covers ALL six strategies and
    returns real StepMetrics from the in-kernel partial-reduction epilogue.

Stochastic rounding uses the engine's counter-based noise stream
(bucketing.sr_noise_bits) in both entrypoints — deterministic in
(seed, step, bucket, element), unlike the per-leaf threefry stream of the
non-fused library path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bucketing
from repro.core.collage import CollageOptState, StepMetrics, bucket_state
from repro.core.mcf import Expansion
from repro.core.precision import Strategy
from repro.kernels import on_tpu
from repro.kernels.collage_update import collage_update as cu
from repro.kernels.collage_update import ref as cu_ref

STRATEGY_CODE = {
    Strategy.A_BF16: "A",
    Strategy.B_COLLAGE_LIGHT: "B",
    Strategy.C_COLLAGE_PLUS: "C",
    Strategy.KAHAN: "KAHAN",
    Strategy.SR: "SR",
    Strategy.D_MINUS_MW: "D-",
    Strategy.D_MIXED_MW: "D",
}

# bucket-state field name → BucketedOptState role (theta lives in params)
_FIELD_ROLE = {"m": "m", "vhi": "vhi", "vlo": "vlo", "delta": "delta",
               "master": "master"}


def _update_one_bucket(opt, state_dict, g, lr, bc1, bc2, seed,
                       elem_offset=None):
    """Fused update of one flat bucket: the Pallas kernel on a TPU (or when
    ``opt.use_fused_kernel`` asks for it elsewhere, interpreted), else the
    bit-identical pure-jnp oracle (same math).

    ``elem_offset`` (SR): element-0's position inside the FULL bucket — a
    ZeRO shard passes its flat-axis start so the counter-based noise is
    indexed bucket-globally (bit-identical to the unsharded step)."""
    code = STRATEGY_CODE[opt.policy.strategy]
    kw = dict(b1=opt.b1, b2=opt.b2, eps=opt.eps, wd=opt.wd, strategy=code,
              pt_decay=(opt.policy.wd_mode == "pytorch"),
              compute_metrics=opt.compute_metrics)
    if opt.use_fused_kernel or on_tpu():
        return cu.collage_bucket_update(state_dict, g, lr, bc1, bc2, seed,
                                        elem_offset, **kw)
    # flat library-semantics path (one fused XLA computation per bucket);
    # fast metrics sums — equal to the kernel's tiled partials up to f32
    # summation order (the tiled oracle mode is for bit-identity tests).
    return cu_ref.collage_bucket_update_ref(state_dict, g, lr, bc1, bc2,
                                            seed, elem_offset,
                                            tiled_metrics=False, **kw)


def sum_partials(partials_list) -> tuple:
    """Σ of per-bucket metric partials — the RAW pre-finalization
    quantities (⟨Δθ,Δθ̂⟩, ‖Δθ‖², ‖Δθ̂‖², #lost, ‖g‖²) as a 5-tuple of f32
    scalars. They are plain sums over elements, so partials from ZeRO
    shards / more buckets combine by addition (one pytree ``psum`` in the
    sharded engine) before finalizing ONCE. Kept as a scalar tuple — a
    stacked (5,) array would put a ``concatenate`` into the steady-state
    optimizer jaxpr, which must stay concat-free (DESIGN.md §5)."""
    tot = (jnp.float32(0.0),) * 5
    for p in partials_list:   # kernel/oracle emit per-bucket 5-tuples
        tot = tuple(t + q for t, q in zip(tot, p))
    return tot


def finalize_metrics(partials, total: int) -> StepMetrics:
    """Raw partials (5-tuple or (5,) array) → StepMetrics (Paper Def. 3.3).

    ``total`` is the UNPADDED parameter count — padding lanes contribute
    exact zeros to every partial, so only the denominator needs care."""
    dot, un2, en2, lost, gn2 = partials
    un = jnp.sqrt(un2)
    return StepMetrics(
        edq=dot / jnp.maximum(un, 1e-30),
        update_norm=un,
        effective_norm=jnp.sqrt(en2),
        imprecision_pct=100.0 * lost / total,
        grad_norm=jnp.sqrt(gn2))


def _finalize_metrics(partials_list, total: int) -> StepMetrics:
    return finalize_metrics(sum_partials(partials_list), total)


def _zero_metrics() -> StepMetrics:
    return StepMetrics(*(jnp.zeros((), jnp.float32),) * 5)


def _scalars(opt, t):
    tf = t.astype(jnp.float32)
    lr = opt.lr(t).astype(jnp.float32)
    bc1 = 1.0 - jnp.float32(opt.b1) ** tf
    bc2 = 1.0 - jnp.float32(opt.b2) ** tf
    return lr, bc1, bc2


# --------------------------------------------------------------------------
# first-class bucketed path: zero per-step flatten/concat
# --------------------------------------------------------------------------

def bucketed_step(opt, grads, bparams: bucketing.BucketedParams,
                  bstate: bucketing.BucketedOptState, *,
                  metrics_partials: bool = False,
                  elem_offsets=None, reduce_fn=None):
    """One optimizer step over persistent buckets.

    ``grads``: BucketedParams (from ``jax.grad`` w.r.t. a BucketedParams) or
    a bare tuple of flat bucket arrays matching ``bparams.layout``.
    ``metrics_partials``: return the RAW summed metric partials (5-tuple
    of f32 scalars) instead of finalized StepMetrics — a cross-shard
    caller (train/sharded.py ZeRO) psums them and calls
    :func:`finalize_metrics` once, which is exact by construction (no
    un-finalize inverse to keep in sync).
    ``elem_offsets``: per-bucket element offsets (uint32 scalars, one per
    bucket) of this caller's shard inside the full bucket — a ZeRO-sharded
    step passes ``axis_index · padded/n_dp`` so the SR noise stream stays
    bucket-global and SR + ZeRO is bit-identical to the unsharded step.
    None → offset 0 (unsharded). Ignored for non-SR strategies (the update
    is otherwise purely elementwise).
    ``reduce_fn``: optional ``(bucket_index, raw_bucket_grad) → reduced
    grad`` hook called immediately before each bucket's update. The sharded
    engine passes its compressed-collective closure here so collective *i*
    sits adjacent to update *i* in program order — bucket-granular
    readiness the latency-hiding scheduler can overlap (collective *i+1*
    runs under update *i*) instead of one serialized all-reduce wall before
    the whole optimizer step. None → grads are used as given."""
    s = opt.policy.strategy
    layout = bparams.layout
    gdata = grads.data if isinstance(grads, bucketing.BucketedParams) \
        else tuple(grads)
    assert len(gdata) == layout.n_buckets
    if elem_offsets is not None:
        assert len(elem_offsets) == layout.n_buckets
    t = bstate.step + 1
    lr, bc1, bc2 = _scalars(opt, t)
    fields = cu.state_fields(STRATEGY_CODE[s])

    new: dict = {f: [] for f in fields}
    partials = []
    for i in range(layout.n_buckets):
        sd = {"theta": bparams.data[i]}
        for f in fields:
            if f != "theta":
                sd[f] = getattr(bstate, _FIELD_ROLE[f])[i]
        seed = bucketing.fold_seed(bstate.rng, t, i) if s is Strategy.SR \
            else None
        off = elem_offsets[i] if elem_offsets is not None else None
        g_i = gdata[i] if reduce_fn is None else reduce_fn(i, gdata[i])
        out, part = _update_one_bucket(opt, sd, g_i, lr, bc1, bc2,
                                       seed, elem_offset=off)
        for f in fields:
            new[f].append(out[f])
        if part is not None:
            partials.append(part)

    if metrics_partials:
        metrics = sum_partials(partials) if opt.compute_metrics \
            else (jnp.float32(0.0),) * 5
    else:
        metrics = _finalize_metrics(partials, layout.total_size) \
            if opt.compute_metrics else _zero_metrics()
    new_state = bucketing.BucketedOptState(
        step=t, m=tuple(new["m"]), vhi=tuple(new["vhi"]),
        vlo=tuple(new["vlo"]) if "vlo" in fields else bstate.vlo,
        delta=tuple(new["delta"]) if "delta" in fields else bstate.delta,
        master=tuple(new["master"]) if "master" in fields else bstate.master,
        rng=bstate.rng, layout=layout, grad_err=bstate.grad_err)
    new_params = bucketing.BucketedParams(tuple(new["theta"]), layout)
    return new_params, new_state, metrics


# --------------------------------------------------------------------------
# tree-compat shim (CollageAdamW.step with use_fused_kernel=True)
# --------------------------------------------------------------------------

def fused_step(opt, grads, params, state: CollageOptState, lr, bc1, bc2):
    """Drop-in replacement for CollageAdamW.step — all six strategies.

    Re-flattens the pytrees every call (the cost ``bucketed_step`` removes);
    kept as the migration path for tree-shaped TrainStates."""
    s = opt.policy.strategy
    bp = opt.policy.bucketing
    layout = bucketing.build_layout(params,
                                    max_bucket_elems=bp.max_bucket_elems,
                                    pad_multiple=bp.pad_multiple)
    t = state.step + 1
    code = STRATEGY_CODE[s]
    fields = cu.state_fields(code)

    # one shared definition of role→bucket rules (dtype, hi/lo split):
    # bucket_state is also what init_bucketed / checkpoint migration use
    b_params, b_state = bucket_state(state, params, layout, opt.policy)
    buckets = {"theta": b_params.data, "m": b_state.m, "vhi": b_state.vhi,
               "vlo": b_state.vlo, "delta": b_state.delta,
               "master": b_state.master}
    g_buckets = bucketing.bucket_tree(grads, layout)
    seed_base = None
    if s is Strategy.SR:
        seed_base = bucketing.fold_seed(state.rng[0] ^ state.rng[1])

    new: dict = {f: [] for f in fields}
    partials = []
    for i in range(layout.n_buckets):
        sd = {f: buckets[f][i] for f in fields}
        seed = bucketing.fold_seed(seed_base, t, i) \
            if seed_base is not None else None
        out, part = _update_one_bucket(opt, sd, g_buckets[i],
                                       lr, bc1, bc2, seed)
        for f in fields:
            new[f].append(out[f])
        if part is not None:
            partials.append(part)

    unflat = layout.treedef.unflatten
    new_p = bucketing.unbucket(new["theta"], layout)
    new_m = bucketing.unbucket(new["m"], layout)
    if s.uses_expansion_second_moment:
        his = bucketing.unbucket_leaves(new["vhi"], layout)
        los = bucketing.unbucket_leaves(new["vlo"], layout)
        new_v = unflat([Expansion(h, l) for h, l in zip(his, los)])
    else:
        new_v = bucketing.unbucket(new["vhi"], layout)
    new_d = bucketing.unbucket(new["delta"], layout) \
        if "delta" in fields else None
    new_w = bucketing.unbucket(new["master"], layout) \
        if "master" in fields else None
    new_rng = jax.random.fold_in(state.rng, t) if s is Strategy.SR else None

    metrics = _finalize_metrics(partials, layout.total_size) \
        if opt.compute_metrics else _zero_metrics()
    new_state = CollageOptState(step=t, m=new_m, v=new_v, delta=new_d,
                                master=new_w, rng=new_rng)
    return new_p, new_state, metrics
