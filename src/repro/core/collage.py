"""Collage: precision-aware AdamW (Paper Algorithm 2) and baselines.

The optimizer is a drop-in plugin: model code sees a plain ``param_dtype``
pytree ``params``; all MCF residuals / master weights / Kahan buffers live in
``CollageOptState``. ``step`` fuses the optimizer math with the parameter
update (required — Grow must see θ and Δθ together).

Numerical placement follows the paper exactly:
  * tensor EMA arithmetic in the *component dtype* (bf16) so options A/B
    faithfully exhibit the β₂→1.0 rounding and lost arithmetic;
  * scalar computations (lr, bias corrections, 1−β) in fp32 before casting
    (App. D "rule of thumb");
  * per-element update Δθ formed in fp32 registers (storage stays bf16 — on
    TPU this is free: the VPU computes in fp32 lanes), then rounded once to
    bf16 and applied with Grow (B/C), Kahan (KAHAN), ⊕ (A/D⁻ᴹᵂ) or SR (SR);
  * weight decay fused into the summed update (Alg. 2 line 12) by default.

A fused single-HBM-pass Pallas kernel implementing the same math lives in
``repro.kernels.collage_update``; the bucket layout runs it on a TPU
(``use_fused_kernel=True`` forces it elsewhere, interpreted); its oracle
is this module. Two execution layouts exist:

  * tree layout (``init``/``step``): per-leaf pytree state — the reference
    semantics. With ``use_fused_kernel`` the step routes through the bucket
    engine but re-flattens the pytrees every call.
  * bucket layout (``init_bucketed``/``step_bucketed``): params + ALL
    optimizer state persist as contiguous flat buckets (core.bucketing,
    DESIGN.md §5) — one fused launch per bucket, zero per-step concat/split
    traffic. Stochastic rounding uses the engine's counter-based noise
    stream instead of the per-leaf threefry keys (both unbiased; streams
    differ bit-wise).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import bucketing, mcf
from repro.core.mcf import Expansion
from repro.core.precision import PrecisionPolicy, Strategy

Schedule = Callable[[jax.Array], jax.Array]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CollageOptState:
    """Optimizer state. Leaves shard identically to their parameter."""

    step: jax.Array                 # i32 scalar
    m: Any                          # first moment (component or fp32 dtype)
    v: Any                          # second moment; Expansion leaves for plus
    delta: Optional[Any]            # δθ (B/C) or Kahan c (KAHAN), else None
    master: Optional[Any]           # fp32 master weights (D), else None
    rng: Optional[jax.Array]        # SR only

    def tree_flatten(self):
        return (self.step, self.m, self.v, self.delta, self.master, self.rng), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


class StepMetrics(NamedTuple):
    """Per-step precision diagnostics (Paper Def. 3.3 & Fig. 3)."""

    edq: jax.Array                 # effective descent quality  ⟨Δθ/‖Δθ‖, Δθ̂⟩
    update_norm: jax.Array         # ‖Δθ‖ (== EDQ when nothing is lost)
    effective_norm: jax.Array      # ‖Δθ̂‖
    imprecision_pct: jax.Array     # % params with Δθ≠0 but no effective change
    grad_norm: jax.Array


def _cast(x, dt):
    return x.astype(dt)


class CollageAdamW:
    """AdamW with selectable precision strategy (Paper Table 2 options).

    Not an optax dependency-clone: ``init(params)`` / ``step(grads, params,
    state)`` where ``step`` returns ``(new_params, new_state, metrics)``.
    """

    def __init__(self,
                 learning_rate: float | Schedule,
                 b1: float = 0.9,
                 b2: float = 0.999,
                 eps: float = 1e-8,
                 weight_decay: float = 0.0,
                 policy: PrecisionPolicy | None = None,
                 compute_metrics: bool = False,
                 use_fused_kernel: bool = False,
                 sr_seed: int = 0):
        self.lr = learning_rate if callable(learning_rate) else (lambda t: jnp.float32(learning_rate))
        self.b1 = float(b1)
        self.b2 = float(b2)
        self.eps = float(eps)
        self.wd = float(weight_decay)
        self.policy = policy or PrecisionPolicy()
        self.compute_metrics = compute_metrics
        # tree layout: route through the fused kernel (re-flattening shim).
        # The bucketed layout always takes the kernel on a TPU; elsewhere
        # this flag asks for the interpreted kernel over the jnp oracle.
        self.use_fused_kernel = use_fused_kernel
        # SR rounding-noise seed. Configurable so a migrated/resumed run does
        # not silently replay the identical noise stream (the old behaviour
        # hard-coded PRNGKey(0) in both init and convert_state).
        self.sr_seed = int(sr_seed)

    # ------------------------------------------------------------------ init
    def init(self, params: Any) -> CollageOptState:
        s = self.policy.strategy
        cdt = self.policy.param_dtype
        zeros = lambda dt: jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, dt), params)
        if s in (Strategy.D_MINUS_MW, Strategy.D_MIXED_MW):
            m, v = zeros(jnp.float32), zeros(jnp.float32)
        else:
            m, v = zeros(cdt), zeros(cdt)
        if s.uses_expansion_second_moment:
            v = jax.tree_util.tree_map(mcf.zeros_like_expansion, v)
        delta = None
        if s.uses_expansion_params or s is Strategy.KAHAN:
            delta = zeros(cdt)
        master = None
        if s.uses_master_weights:
            # f32-ok: strategy D baseline — the master copy IS the point here
            master = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
        rng = jax.random.PRNGKey(self.sr_seed) if s is Strategy.SR else None
        return CollageOptState(step=jnp.zeros((), jnp.int32), m=m, v=v,
                               delta=delta, master=master, rng=rng)

    # -------------------------------------------------------- bucketed layout
    def init_bucketed(self, params: Any) -> tuple[
            bucketing.BucketedParams, bucketing.BucketedOptState]:
        """Init with params + optimizer state as persistent flat buckets.

        The layout knobs come from ``policy.bucketing``. The returned
        BucketedParams replaces the params pytree in the TrainState;
        materialize the model view with ``.tree()`` at the apply boundary."""
        bp = self.policy.bucketing
        layout = bucketing.build_layout(
            params, max_bucket_elems=bp.max_bucket_elems,
            pad_multiple=bp.pad_multiple)
        return bucket_state(self.init(params), params, layout, self.policy,
                            sr_seed=self.sr_seed)

    def step_bucketed(self, grads, bparams: bucketing.BucketedParams,
                      bstate: bucketing.BucketedOptState, *,
                      metrics_partials: bool = False,
                      elem_offsets=None, reduce_fn=None):
        """One step over buckets: one fused launch per bucket, no per-step
        flatten/concat (tests assert the jaxpr is concat-free). ``grads`` is
        a BucketedParams (``jax.grad`` w.r.t. bucketed params) or a tuple of
        flat bucket arrays. ``metrics_partials=True`` returns the raw
        metric-partial 5-tuple in place of StepMetrics (see
        ops.bucketed_step) — how the ZeRO engine makes its cross-shard
        metrics exact. ``elem_offsets`` (SR + ZeRO): per-bucket flat-axis
        start of this shard inside the full bucket, so the counter-based
        noise stream indexes elements bucket-globally and the sharded step
        stays bit-identical to the unsharded one. ``reduce_fn`` (sharded
        engine): per-bucket ``(i, grad) → reduced grad`` hook so each
        bucket's gradient collective launches at its readiness point,
        adjacent to its own update, instead of in one serialized wall."""
        from repro.kernels.collage_update import ops as kops
        return kops.bucketed_step(self, grads, bparams, bstate,
                                  metrics_partials=metrics_partials,
                                  elem_offsets=elem_offsets,
                                  reduce_fn=reduce_fn)

    # ------------------------------------------------------------------ step
    def step(self, grads: Any, params: Any, state: CollageOptState, *,
             metrics_partials: bool = False
             ) -> tuple[Any, CollageOptState, Any]:
        """One tree-layout step. ``metrics_partials=True`` returns, in place
        of finalized StepMetrics, the PER-LEAF raw metric partials — a list
        (treedef leaf order) of (⟨Δθ,Δθ̂⟩, ‖Δθ‖², ‖Δθ̂‖², #lost, ‖g‖²)
        5-tuples. Raw partials are plain sums over elements, so a sharded
        caller (the pipeline engine) can psum the stage-local leaves' tuples
        over the stage axis, add the replicated leaves' once, and finalize a
        single time — exact by construction, where combining the finalized
        norms post-hoc is not (√ doesn't distribute over +)."""
        s = self.policy.strategy
        cdt = self.policy.param_dtype
        t = state.step + 1
        tf = t.astype(jnp.float32)  # f32-ok: scalar step counter
        # --- scalars in fp32 (App. D rule of thumb) --- f32-ok
        lr = self.lr(t).astype(jnp.float32)
        bc1 = 1.0 - jnp.float32(self.b1) ** tf
        bc2 = 1.0 - jnp.float32(self.b2) ** tf

        if self.use_fused_kernel:
            if metrics_partials:
                raise ValueError("metrics_partials is a tree-layout feature "
                                 "(per-leaf partials); the fused shim "
                                 "reduces per bucket")
            # engine covers all six strategies + real StepMetrics; SR uses
            # the counter-based noise stream (differs bit-wise from the
            # per-leaf threefry stream below, equally unbiased).
            from repro.kernels.collage_update import ops as kops
            new_params, new_state, metrics = kops.fused_step(
                self, grads, params, state, lr, bc1, bc2)
            return new_params, new_state, metrics

        leaves_g, treedef = jax.tree_util.tree_flatten(grads)
        leaves_p = treedef.flatten_up_to(params)
        leaves_m = treedef.flatten_up_to(state.m)
        leaves_v = treedef.flatten_up_to(state.v)
        leaves_d = treedef.flatten_up_to(state.delta) if state.delta is not None else [None] * len(leaves_g)
        leaves_w = treedef.flatten_up_to(state.master) if state.master is not None else [None] * len(leaves_g)

        rng = state.rng
        sub_keys = [None] * len(leaves_g)
        if s is Strategy.SR:
            rng, *sub_keys = jax.random.split(rng, len(leaves_g) + 1)

        outs = [self._leaf_step(g, p, m, v, d, w, k, lr, bc1, bc2, cdt)
                for g, p, m, v, d, w, k in
                zip(leaves_g, leaves_p, leaves_m, leaves_v, leaves_d, leaves_w, sub_keys)]
        (new_p, new_m, new_v, new_d, new_w, upd, eff) = map(list, zip(*outs))

        if metrics_partials:
            metrics = [self._leaf_partials(g, u, e)
                       for g, u, e in zip(leaves_g, upd, eff)] \
                if self.compute_metrics \
                else [(jnp.float32(0.0),) * 5 for _ in leaves_g]
        elif self.compute_metrics:
            metrics = self._metrics(leaves_g, upd, eff)
        else:
            metrics = StepMetrics(*(jnp.zeros((), jnp.float32),) * 5)

        unflat = treedef.unflatten
        new_state = CollageOptState(
            step=t, m=unflat(new_m), v=unflat(new_v),
            delta=unflat(new_d) if state.delta is not None else None,
            master=unflat(new_w) if state.master is not None else None,
            rng=rng)
        return unflat(new_p), new_state, metrics

    # ------------------------------------------------- per-leaf update rules
    def _leaf_step(self, g, p, m, v, d, w, key, lr, bc1, bc2, cdt):
        s = self.policy.strategy
        b1, b2, eps, wd = self.b1, self.b2, self.eps, self.wd
        f32 = jnp.float32

        if s in (Strategy.D_MINUS_MW, Strategy.D_MIXED_MW):
            # fp32 optimizer states; grads arrive in bf16 (Table 2) → upcast.
            g32 = _cast(g, f32)
            m = b1 * m + (1.0 - b1) * g32
            v = b2 * v + (1.0 - b2) * g32 * g32
            mhat = m / bc1
            vhat = v / bc2
            fpu = mcf.fpu(cdt)
            theta_ref = w if s is Strategy.D_MIXED_MW else _cast(p, f32)
            upd32 = -lr * (mhat / (jnp.sqrt(vhat) + eps) + self._wd_term(wd, theta_ref))
            if s is Strategy.D_MIXED_MW:
                w = w + upd32                       # fp32 master update
                new_p32 = fpu.rn(w)                 # RN onto bf16 grid
                eff = new_p32 - fpu.load(p)
                new_p = fpu.store(new_p32)
            else:
                theta32 = fpu.load(p)
                new_p32 = fpu.add(theta32, fpu.rn(upd32))  # bf16 ⊕ → lost arith
                eff = new_p32 - theta32
                new_p = fpu.store(new_p32)
            return new_p, m, v, d, w, upd32, eff

        # --- bf16-storage families (A / B / C / KAHAN / SR) ---
        # EMA arithmetic in the component dtype via the strict FPU — this
        # faithfully reproduces the β₂→bf16 rounding issues (and is immune
        # to XLA's excess-precision convert elision; see mcf.py docstring).
        fpu = mcf.fpu(cdt)
        g32 = fpu.load(g)
        theta32 = fpu.load(p)
        cb1, c1m = fpu.rn(jnp.float32(b1)), fpu.rn(jnp.float32(1 - b1))
        cb2, c2m = fpu.rn(jnp.float32(b2)), fpu.rn(jnp.float32(1 - b2))
        m32 = fpu.add(fpu.mul(cb1, fpu.load(m)), fpu.mul(c1m, g32))
        m = fpu.store(m32)
        g2 = fpu.mul(g32, g32)
        if s.uses_expansion_second_moment:
            beta2_e = mcf.from_float(b2, dtype=cdt, shape=v.hi.shape)
            v = mcf.grow(mcf.mul(beta2_e, v),
                         fpu.store(fpu.mul(c2m, g2)))   # Alg. 2 line 9
            vhat32 = v.value(f32) / bc2
        else:
            v32 = fpu.add(fpu.mul(cb2, fpu.load(v)), fpu.mul(c2m, g2))
            v = fpu.store(v32)                          # β₂ cast to bf16 (→1.0!)
            vhat32 = v32 / bc2
        mhat32 = m32 / bc1
        # Δθ formed in fp32 registers (free on the VPU), rounded once.
        upd32 = -lr * (mhat32 / (jnp.sqrt(vhat32) + eps) + self._wd_term(wd, theta32))
        upd16_32 = fpu.rn(upd32)                        # on-grid Δθ
        upd16 = fpu.store(upd16_32)

        if s is Strategy.A_BF16:
            base32 = self._maybe_pt_decay(theta32, lr, fpu)
            new_p32 = fpu.add(base32, upd16_32)         # bf16 ⊕: lost arithmetic
            eff = new_p32 - theta32
            return fpu.store(new_p32), m, v, d, w, upd32, eff
        if s is Strategy.SR:
            new_p = mcf.stochastic_round(theta32 + upd32, cdt, key)
            eff = fpu.load(new_p) - theta32
            return new_p, m, v, d, w, upd32, eff
        if s is Strategy.KAHAN:
            # Kahan: compensate with c (≡ Collage-light under App. D assumption)
            upd_c = fpu.add(upd16_32, fpu.load(d))
            new_p32 = fpu.add(theta32, upd_c)
            new_d32 = fpu.sub(upd_c, fpu.sub(new_p32, theta32))
            eff = new_p32 - theta32
            return fpu.store(new_p32), m, v, fpu.store(new_d32), w, upd32, eff
        # Collage light/plus: Grow Δθ into the (θ, δθ) expansion.
        e = mcf.grow(Expansion(p, d), upd16)
        # Δθ̂ per-component: (hi'−hi) + (lo'−lo). Each difference is exact in
        # f32 (nearby on-grid values) — evaluating (hi+lo) directly in f32
        # would re-lose tiny residuals to ulp_f32(θ) and understate EDQ.
        eff = (fpu.load(e.hi) - theta32) + (fpu.load(e.lo) - fpu.load(d))
        return e.hi, m, v, e.lo, w, upd32, eff

    def _wd_term(self, wd, theta32):
        if self.policy.wd_mode == "fused":
            return wd * theta32
        return jnp.zeros_like(theta32)

    def _maybe_pt_decay(self, theta32, lr, fpu):
        # App. D Eq. 4: separate PyTorch-style decay θ·(1−αλ). In bf16,
        # 1−αλ rounds to 1.0 whenever αλ < ulp(1)/2 = 2⁻⁸ — a silent no-op.
        if self.policy.wd_mode == "pytorch" and self.wd:
            factor = fpu.rn(1.0 - lr * jnp.float32(self.wd))
            return fpu.mul(theta32, factor)
        return theta32

    # ----------------------------------------------------------- diagnostics
    @staticmethod
    def _leaf_partials(g, u, e) -> tuple:
        """Raw metric partials of ONE leaf — the same 5 quantities the
        bucket engine's kernel epilogue exports (ops.finalize_metrics
        consumes either)."""
        f32 = jnp.float32
        u32, e32 = _cast(u, f32), _cast(e, f32)
        return (jnp.sum(u32 * e32), jnp.sum(u32 * u32), jnp.sum(e32 * e32),
                jnp.sum(((jnp.abs(u32) > 0) & (e == 0)).astype(f32)),
                jnp.sum(_cast(g, f32) ** 2))

    def _metrics(self, grads, upds, effs) -> StepMetrics:
        parts = [self._leaf_partials(g, u, e)
                 for g, u, e in zip(grads, upds, effs)]
        dot, un2, en2, lost, gn2 = (sum(p[k] for p in parts)
                                    for k in range(5))
        total = sum(u.size for u in upds)
        un = jnp.sqrt(un2)
        return StepMetrics(
            edq=dot / jnp.maximum(un, 1e-30),
            update_norm=un,
            effective_norm=jnp.sqrt(en2),
            imprecision_pct=100.0 * lost / total,
            grad_norm=jnp.sqrt(gn2))


def bucket_state(state: CollageOptState, params: Any,
                 layout: bucketing.BucketLayout, policy: PrecisionPolicy,
                 *, sr_seed: int = 0) -> tuple[
                     bucketing.BucketedParams, bucketing.BucketedOptState]:
    """Lift a tree-layout (params, CollageOptState) into the persistent
    bucket layout — the one-time concat at init / checkpoint migration.

    The SR threefry key does not carry over (the bucket engine's noise is
    counter-based): the stream restarts from ``sr_seed``."""
    s = policy.strategy
    f32 = jnp.float32
    opt_dt = f32 if s in (Strategy.D_MINUS_MW, Strategy.D_MIXED_MW) else None
    # the fused update assumes component-dtype parameter buckets
    for b in layout.buckets:
        assert jnp.dtype(b.dtype) == jnp.dtype(policy.param_dtype), \
            (b.dtype, policy.param_dtype)
    bparams = bucketing.BucketedParams(
        bucketing.bucket_tree(params, layout), layout)
    m = bucketing.bucket_tree(state.m, layout, dtype=opt_dt)
    if s.uses_expansion_second_moment:
        leaves_v = layout.treedef.flatten_up_to(state.v)
        vhi = bucketing.bucket_leaves([v.hi for v in leaves_v], layout)
        vlo = bucketing.bucket_leaves([v.lo for v in leaves_v], layout)
    else:
        vhi = bucketing.bucket_tree(state.v, layout, dtype=opt_dt)
        vlo = None
    delta = bucketing.bucket_tree(state.delta, layout) \
        if state.delta is not None else None
    master = bucketing.bucket_tree(state.master, layout, dtype=f32) \
        if state.master is not None else None
    rng = jnp.uint32(sr_seed) if s is Strategy.SR else None
    return bparams, bucketing.BucketedOptState(
        step=state.step, m=m, vhi=vhi, vlo=vlo, delta=delta, master=master,
        rng=rng, layout=layout)


def unbucket_state(bparams: bucketing.BucketedParams,
                   bstate: bucketing.BucketedOptState,
                   policy: PrecisionPolicy) -> tuple[Any, CollageOptState]:
    """Inverse of ``bucket_state``: materialize the tree layout (values
    preserved bit-exactly; the SR key is rebuilt from the bucket seed)."""
    s = policy.strategy
    layout = bparams.layout
    params = bparams.tree()
    m = bucketing.unbucket(bstate.m, layout)
    if s.uses_expansion_second_moment:
        his = bucketing.unbucket_leaves(bstate.vhi, layout)
        los = bucketing.unbucket_leaves(bstate.vlo, layout)
        v = layout.treedef.unflatten(
            [Expansion(h, l) for h, l in zip(his, los)])
    else:
        v = bucketing.unbucket(bstate.vhi, layout)
    delta = bucketing.unbucket(bstate.delta, layout) \
        if bstate.delta is not None else None
    master = bucketing.unbucket(bstate.master, layout) \
        if bstate.master is not None else None
    rng = None
    if s is Strategy.SR:
        rng = jnp.stack([jnp.zeros((), jnp.uint32),
                         bstate.rng.astype(jnp.uint32)])
    return params, CollageOptState(step=bstate.step, m=m, v=v, delta=delta,
                                   master=master, rng=rng)


def convert_state(state: CollageOptState, params: Any,
                  new_policy: PrecisionPolicy, *,
                  sr_seed: int = 0) -> CollageOptState:
    """Checkpoint-time precision migration: re-express an optimizer state
    under a different strategy (e.g. resume an fp32-master run as
    Collage-plus, or vice versa). Moment tensors are rounded/expanded;
    master weights and residuals are (re)built as needed. ``sr_seed`` seeds
    the SR stream of the migrated run (don't silently replay noise)."""
    s = new_policy.strategy
    cdt = new_policy.param_dtype
    f32 = jnp.float32

    def val32(x):
        return x.value(f32) if isinstance(x, Expansion) else x.astype(f32)

    m32 = jax.tree_util.tree_map(val32, state.m,
                                 is_leaf=lambda x: isinstance(x, Expansion))
    v32 = jax.tree_util.tree_map(val32, state.v,
                                 is_leaf=lambda x: isinstance(x, Expansion))
    if s in (Strategy.D_MINUS_MW, Strategy.D_MIXED_MW):
        m, v = m32, v32
    else:
        m = jax.tree_util.tree_map(lambda x: x.astype(cdt), m32)
        v = jax.tree_util.tree_map(lambda x: x.astype(cdt), v32)
    if s.uses_expansion_second_moment:
        def expand(x32):
            hi = x32.astype(cdt)
            lo = (x32 - hi.astype(f32)).astype(cdt)
            return Expansion(hi, lo)
        v = jax.tree_util.tree_map(expand, v32)
    delta = None
    if s.uses_expansion_params or s is Strategy.KAHAN:
        old_delta = state.delta
        if old_delta is not None:
            delta = old_delta
        elif state.master is not None:
            # preserve the master-weight residual in the new δθ
            delta = jax.tree_util.tree_map(
                lambda w, p: (w - p.astype(f32)).astype(cdt),
                state.master, params)
        else:
            delta = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, cdt), params)
    master = None
    if s.uses_master_weights:
        if state.master is not None:
            master = state.master
        else:
            d = state.delta
            master = jax.tree_util.tree_map(
                lambda p, dd: p.astype(f32) + (dd.astype(f32) if dd is not None
                                               else 0.0),
                params, d if d is not None else params)
            if d is None:
                master = jax.tree_util.tree_map(
                    lambda p: p.astype(f32), params)
    if s is Strategy.SR:
        rng = state.rng if state.rng is not None \
            else jax.random.PRNGKey(sr_seed)
    else:
        rng = None
    return CollageOptState(step=state.step, m=m, v=v, delta=delta,
                           master=master, rng=rng)


def cosine_schedule(base_lr: float, warmup: int, total: int,
                    min_ratio: float = 0.1) -> Schedule:
    """CosineAnnealing with linear warmup (paper §E.2: 200 warmup iters)."""

    def f(t):
        tf = t.astype(jnp.float32)  # f32-ok: scalar schedule argument
        warm = tf / max(warmup, 1)
        prog = jnp.clip((tf - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = min_ratio + (1 - min_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
        return base_lr * jnp.where(tf < warmup, warm, cos)

    return f
