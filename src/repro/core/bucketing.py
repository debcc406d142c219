"""Bucketed multi-tensor layout: the persistent flat representation that
powers the fused optimizer engine (DESIGN.md §5).

Collage's speed claim (Paper Remark 5.2) is "one HBM pass over all optimizer
state per step". That only holds if the flat, contiguous view of the
parameters is a *first-class persistent representation*: re-flattening and
re-concatenating every leaf inside the jitted step costs an extra HBM
round-trip per tensor and produces O(leaves) XLA ops. This module builds the
layout ONCE at init:

  * parameter leaves are grouped by storage dtype (× an optional size cap)
    into a small number of contiguous 1-D *buckets*, padded to a lane
    multiple so every bucket tiles the VPU/(FSDP flat axis) exactly;
  * a :class:`BucketLayout` records, per leaf, its bucket / offset / shape —
    static, hashable metadata that rides along as pytree aux data;
  * ALL optimizer state (m, v-hi/lo, δθ or Kahan c, fp32 masters, the SR
    seed) is kept bucket-resident, so ``CollageAdamW.step_bucketed`` is one
    fused launch per bucket with zero concat/split traffic;
  * parameter *views* (``unbucket``) are materialized only at the
    model-apply boundary via static ``lax.slice`` + reshape — the optimizer
    step itself contains no ``concatenate`` / ``dynamic_slice`` (asserted by
    tests/test_bucketing.py on the jaxpr).

The layout also defines the **counter-based SR noise stream**: stochastic
rounding inside the fused kernel cannot thread a threefry key per leaf, so
the engine derives 16 noise bits per element from
``hash(seed, step, bucket, element-index)`` (a splitmix/lowbias32 integer
hash). The same pure-jnp definition is used by the Pallas kernel and the
``ref.py`` oracle, making the two bit-identical by construction.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

LANES = 128      # TPU VPU lane count — minimum bucket padding granularity
SUBLANES = 8     # (8, 128) native VMEM tile: default pad keeps rows aligned
PAD_DEFAULT = SUBLANES * LANES
# one full (256, 128) block of the fused collage_update kernel: buckets (and
# ZeRO shards) padded to a multiple of it tile the kernel with full blocks
BLOCK_PAD = 256 * LANES

# Bucket-resident role arrays (leaf names under BucketedParams/-OptState).
# grad_err rows are 2-D (n_dp, padded): per-DEVICE compressor state of the
# error-feedback gradient compression (distributed/compression.py) — the
# leading dim is the data-parallel device index, not a shardable flat axis.
BUCKET_STATE_FIELDS = ("data", "m", "vhi", "vlo", "delta", "master",
                       "grad_err")


# --------------------------------------------------------------------------
# Layout metadata (static / hashable — rides as pytree aux data)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Placement of one parameter leaf inside its bucket."""

    name: str                 # keystr path (diagnostics / checkpoint json)
    bucket: int               # bucket index
    offset: int               # element offset inside the bucket
    size: int
    shape: tuple


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    dtype: str                # storage dtype of the *parameter* bucket
    size: int                 # sum of leaf sizes (unpadded)
    padded: int               # size rounded up to pad_multiple


@dataclasses.dataclass(frozen=True)
class BucketLayout:
    """Persistent flat-param layout: where every leaf lives.

    Hashable and comparable (treedefs hash structurally), so it can be jit
    aux data and checkpoint metadata. ``slots`` are in treedef leaf order.
    """

    treedef: Any
    slots: tuple
    buckets: tuple
    pad_multiple: int = PAD_DEFAULT

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    @property
    def total_size(self) -> int:
        return sum(b.size for b in self.buckets)

    def to_json(self) -> dict:
        return {
            "pad_multiple": self.pad_multiple,
            "buckets": [[b.dtype, b.size, b.padded] for b in self.buckets],
            "slots": [[s.name, s.bucket, s.offset, s.size, list(s.shape)]
                      for s in self.slots],
        }

    @classmethod
    def from_json(cls, d: dict, treedef) -> "BucketLayout":
        """Rebuild from checkpoint metadata. The treedef cannot be serialized
        portably, so the caller supplies it (the params structure is the same
        across layouts — only the bucket partitioning differs)."""
        buckets = tuple(BucketSpec(dt, int(sz), int(pad))
                        for dt, sz, pad in d["buckets"])
        slots = tuple(LeafSlot(n, int(b), int(o), int(s), tuple(sh))
                      for n, b, o, s, sh in d["slots"])
        return cls(treedef, slots, buckets, int(d["pad_multiple"]))


def build_layout(params: Any, *, max_bucket_elems: Optional[int] = None,
                 pad_multiple: int = PAD_DEFAULT) -> BucketLayout:
    """Group parameter leaves by dtype (× size cap) into contiguous buckets.

    Leaves keep treedef order within a bucket, so checkpoints of the same
    layout are stable. ``pad_multiple`` should be a multiple of 128; shard-
    aware callers pass ``lcm(128, dp_size)`` so the flat axis divides the
    FSDP mesh axis exactly (see sharding.bucket_pad_multiple)."""
    assert pad_multiple % LANES == 0, pad_multiple
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    open_buckets: dict = {}         # dtype str -> bucket index
    buckets: list = []              # [dtype, running size]
    slots = []
    for path, leaf in flat:
        dt = str(jnp.dtype(leaf.dtype))
        b = open_buckets.get(dt)
        if b is None or (max_bucket_elems is not None
                         and buckets[b][1] + leaf.size > max_bucket_elems
                         and buckets[b][1] > 0):
            b = len(buckets)
            buckets.append([dt, 0])
            open_buckets[dt] = b
        slots.append(LeafSlot(jax.tree_util.keystr(path), b,
                              buckets[b][1], int(leaf.size),
                              tuple(leaf.shape)))
        buckets[b][1] += int(leaf.size)
    specs = tuple(
        BucketSpec(dt, sz, sz + (-sz) % pad_multiple) for dt, sz in buckets)
    return BucketLayout(treedef, tuple(slots), specs, pad_multiple)


def bucket_close_ranks(layout: BucketLayout,
                       leaf_ranks: Sequence[int]) -> tuple:
    """Per-bucket readiness rank: the rank at which the bucket CLOSES.

    ``leaf_ranks[i]`` is the point (any monotone unit: backward-pass layer
    index, schedule tick, …) at which leaf *i* (treedef order, matching
    ``layout.slots``) has its gradient ready. A bucket's collective may
    launch once its LAST leaf is ready, so close rank = max over member
    leaves. Pure host-side metadata — feeds the cost model's overlap
    analysis and documents the per-bucket launch points the engine's
    ``reduce_fn`` interleaving realizes in program order."""
    assert len(leaf_ranks) == len(layout.slots), \
        (len(leaf_ranks), len(layout.slots))
    close = [None] * layout.n_buckets
    for slot, r in zip(layout.slots, leaf_ranks):
        if close[slot.bucket] is None or r > close[slot.bucket]:
            close[slot.bucket] = r
    return tuple(close)


def readiness_order(layout: BucketLayout,
                    leaf_ranks: Sequence[int]) -> tuple:
    """Bucket indices sorted by close rank (ties: layout order) — the order
    in which per-bucket gradient collectives become launchable."""
    close = bucket_close_ranks(layout, leaf_ranks)
    return tuple(sorted(range(layout.n_buckets), key=lambda b: (close[b], b)))


# --------------------------------------------------------------------------
# bucket / unbucket / rebucket (concat happens ONLY here — at init,
# checkpoint migration, or the model-apply boundary; never in the step)
# --------------------------------------------------------------------------

def bucket_leaves(leaves: Sequence[jax.Array], layout: BucketLayout,
                  dtype=None) -> tuple:
    """Concatenate per-leaf arrays into the layout's flat buckets.

    ``dtype``: None → each bucket keeps its spec (parameter) dtype; a dtype
    → all buckets cast to it (e.g. fp32 moments/masters for option D)."""
    per_bucket: list = [[] for _ in layout.buckets]
    for slot, leaf in zip(layout.slots, leaves):
        assert leaf.size == slot.size, (slot.name, leaf.shape, slot.shape)
        per_bucket[slot.bucket].append(leaf.reshape(-1))
    out = []
    for spec, parts in zip(layout.buckets, per_bucket):
        dt = jnp.dtype(dtype) if dtype is not None else jnp.dtype(spec.dtype)
        parts = [p.astype(dt) for p in parts]
        pad = spec.padded - spec.size
        if pad:
            parts.append(jnp.zeros((pad,), dt))
        out.append(jnp.concatenate(parts) if len(parts) > 1 else parts[0])
    return tuple(out)


def bucket_tree(tree: Any, layout: BucketLayout, dtype=None) -> tuple:
    return bucket_leaves(layout.treedef.flatten_up_to(tree), layout, dtype)


def unbucket_leaves(data: Sequence[jax.Array], layout: BucketLayout) -> list:
    """Materialize per-leaf views with static ``lax.slice`` + reshape (these
    appear only at the model-apply boundary, never in the optimizer step)."""
    out = []
    for slot in layout.slots:
        flat = jax.lax.slice(data[slot.bucket], (slot.offset,),
                             (slot.offset + slot.size,))
        out.append(flat.reshape(slot.shape))
    return out


def unbucket(data: Sequence[jax.Array], layout: BucketLayout) -> Any:
    return layout.treedef.unflatten(unbucket_leaves(data, layout))


def rebucket(data: Sequence[jax.Array], old: BucketLayout,
             new: BucketLayout) -> tuple:
    """Cross-layout migration of one role's bucket set (checkpoint resume
    with a different size cap / pad multiple). Dtype is taken from the old
    bucket arrays, so fp32 moment buckets survive unchanged."""
    assert len(old.slots) == len(new.slots), (len(old.slots), len(new.slots))
    leaves = unbucket_leaves(data, old)
    per_bucket: list = [[] for _ in new.buckets]
    for slot, leaf in zip(new.slots, leaves):
        per_bucket[slot.bucket].append(leaf.reshape(-1))
    out = []
    for spec, parts in zip(new.buckets, per_bucket):
        dt = parts[0].dtype
        pad = spec.padded - spec.size
        if pad:
            parts.append(jnp.zeros((pad,), dt))
        out.append(jnp.concatenate(parts) if len(parts) > 1 else parts[0])
    return tuple(out)


# --------------------------------------------------------------------------
# Bucket-resident pytrees
# --------------------------------------------------------------------------

@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class BucketedParams:
    """Parameters as persistent flat buckets. ``tree()`` materializes the
    model-shaped view; taking ``jax.grad`` w.r.t. a BucketedParams yields
    *flat gradient buckets* directly — no per-step flatten/concat."""

    data: tuple
    layout: BucketLayout

    def tree(self) -> Any:
        return unbucket(self.data, self.layout)

    def tree_flatten_with_keys(self):
        return (((jax.tree_util.GetAttrKey("data"), self.data),), self.layout)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(tuple(children[0]), aux)


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class BucketedOptState:
    """All optimizer state bucket-resident; layout is static aux data.

    Per-role tuples hold one flat array per bucket (or None when the
    strategy doesn't use the role — mirroring CollageOptState):
      m       first moment (component dtype, or fp32 for option D)
      vhi/vlo second moment; vlo only for Collage-plus (MCF expansion)
      delta   δθ (B/C) or Kahan c
      master  fp32 master weights (option D)
      rng     uint32 scalar seed for the counter-based SR stream
      grad_err error-feedback residual of the compressed gradient
              all-reduce, one (n_dp, padded) f32/bf16 row-block per bucket
              (row = per-dp-device compressor state); None when gradient
              compression is off
    """

    step: jax.Array
    m: tuple
    vhi: tuple
    vlo: Optional[tuple]
    delta: Optional[tuple]
    master: Optional[tuple]
    rng: Optional[jax.Array]
    layout: BucketLayout
    grad_err: Optional[tuple] = None

    def tree_flatten_with_keys(self):
        g = jax.tree_util.GetAttrKey
        return (((g("step"), self.step), (g("m"), self.m),
                 (g("vhi"), self.vhi), (g("vlo"), self.vlo),
                 (g("delta"), self.delta), (g("master"), self.master),
                 (g("rng"), self.rng), (g("grad_err"), self.grad_err)),
                self.layout)

    @classmethod
    def tree_unflatten(cls, aux, children):
        step, m, vhi, vlo, delta, master, rng, grad_err = children
        # tolerate non-iterable placeholders (jax internals rebuild trees
        # with proxy objects in place of None subtrees, e.g. device_put)
        as_t = lambda x: tuple(x) if isinstance(x, (list, tuple)) else x
        return cls(step, as_t(m), as_t(vhi), as_t(vlo), as_t(delta),
                   as_t(master), rng, aux, as_t(grad_err))


def migrate(obj: Any, new_layout: BucketLayout) -> Any:
    """Re-express any pytree containing BucketedParams / BucketedOptState
    nodes under ``new_layout`` (values preserved bit-exactly)."""

    def is_bucketed(x):
        return isinstance(x, (BucketedParams, BucketedOptState))

    def fix(x):
        if isinstance(x, BucketedParams):
            return BucketedParams(rebucket(x.data, x.layout, new_layout),
                                  new_layout)
        if isinstance(x, BucketedOptState):
            rb = lambda t: (rebucket(t, x.layout, new_layout)
                            if t is not None else None)
            ge = None
            if x.grad_err is not None:
                # per-device rows migrate independently (vmap over dim 0)
                ge = jax.vmap(
                    lambda rows: rebucket(rows, x.layout, new_layout)
                )(tuple(x.grad_err))
            return BucketedOptState(x.step, rb(x.m), rb(x.vhi), rb(x.vlo),
                                    rb(x.delta), rb(x.master), x.rng,
                                    new_layout, ge)
        return x

    return jax.tree_util.tree_map(fix, obj, is_leaf=is_bucketed)


def state_template_for_layout(obj: Any, layout: BucketLayout) -> Any:
    """Zero-valued clone of ``obj`` with its bucketed nodes re-shaped for
    ``layout`` — used as the restore template when a checkpoint was written
    under a different bucket partitioning (dtype per role is preserved)."""

    def is_bucketed(x):
        return isinstance(x, (BucketedParams, BucketedOptState))

    def zeros_for(t):
        if t is None:
            return None
        dt = t[0].dtype
        return tuple(jnp.zeros((b.padded,), dt) for b in layout.buckets)

    def fix(x):
        if isinstance(x, BucketedParams):
            return BucketedParams(
                tuple(jnp.zeros((b.padded,), jnp.dtype(b.dtype))
                      for b in layout.buckets), layout)
        if isinstance(x, BucketedOptState):
            ge = None
            if x.grad_err is not None:
                n_dp = x.grad_err[0].shape[0]
                # residual dtype is per-bucket (f32 vs exactly-representable
                # component dtype) and buckets group by PARAM dtype, so map
                # it across layouts via the bucket's param dtype — a single
                # template dtype would silently re-round f32 residuals on
                # restore (checkpoint.restore casts to the template)
                by_dtype = {jnp.dtype(b.dtype): e.dtype
                            for b, e in zip(x.layout.buckets, x.grad_err)}
                ge = tuple(
                    jnp.zeros((n_dp, b.padded),
                              by_dtype.get(jnp.dtype(b.dtype),
                                           x.grad_err[0].dtype))
                    for b in layout.buckets)
            return BucketedOptState(x.step, zeros_for(x.m), zeros_for(x.vhi),
                                    zeros_for(x.vlo), zeros_for(x.delta),
                                    zeros_for(x.master), x.rng, layout, ge)
        return x

    return jax.tree_util.tree_map(fix, obj, is_leaf=is_bucketed)


# --------------------------------------------------------------------------
# Deterministic reduction (shared by the kernel epilogue and ref oracle)
# --------------------------------------------------------------------------

def det_sum(x: jax.Array) -> jax.Array:
    """Bit-deterministic sum: explicit binary-tree halving with elementwise
    adds and static slices. XLA is free to pick any accumulation order for a
    ``reduce`` op (and does pick differently depending on fusion context —
    observed 1-ulp drift between the in-kernel and standalone ``jnp.sum``),
    but it may NOT reassociate explicit float adds. The metrics epilogue and
    the ref oracle share this exact op sequence, so StepMetrics partials are
    bit-identical between the Pallas kernel and the pure-jnp reference."""
    x = x.reshape(-1)
    n = x.shape[0]
    while n > 1:
        half = n // 2
        y = x[:half] + x[half:2 * half]
        if n - 2 * half:
            y = y.at[0].add(x[n - 1])
        x = y
        n = half
    return x[0]


# --------------------------------------------------------------------------
# Counter-based SR noise stream (shared by the Pallas kernel and ref oracle)
# --------------------------------------------------------------------------

_GOLDEN = 0x9E3779B9


def lowbias32(x: jax.Array) -> jax.Array:
    """Well-mixed 32-bit integer hash (bias-optimized murmur3 finalizer)."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def fold_seed(seed: jax.Array, *vals) -> jax.Array:
    """Derive a per-(step, bucket) seed from the run seed — the SR state is
    one persistent uint32 scalar; the stream advances with the step counter
    instead of a threaded key (counter-based RNG, splittable per bucket)."""
    s = jnp.asarray(seed).astype(jnp.uint32)
    for v in vals:
        s = lowbias32(s ^ (jnp.asarray(v).astype(jnp.uint32)
                           * jnp.uint32(_GOLDEN)))
    return s


def sr_noise_bits(idx: jax.Array, seed: jax.Array) -> jax.Array:
    """16 uniform noise bits per element for stochastic rounding, keyed by
    the element's global index within its bucket + the folded seed."""
    h = lowbias32(idx.astype(jnp.uint32) * jnp.uint32(_GOLDEN)
                  + seed.astype(jnp.uint32))
    return h & jnp.uint32(0xFFFF)


def stochastic_round_bits(x32: jax.Array, noise16: jax.Array) -> jax.Array:
    """SR f32 → bf16 grid via bit arithmetic (same recipe as
    mcf.stochastic_round, but with the counter-based noise): add 16 uniform
    bits below the kept mantissa, truncate — carries propagate with exactly
    the right probability, E[SR(x)] = x. Returns on-grid f32."""
    # f32-ok: SR bit-trick needs the f32 bit pattern; result is re-narrowed
    bits = jax.lax.bitcast_convert_type(x32.astype(jnp.float32), jnp.uint32)
    rounded = (bits + noise16) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(rounded, jnp.float32)
