"""Sharding rules: param/optimizer/activation/cache PartitionSpecs.

Strategy (DESIGN.md §4): FSDP×TP.
  * TP ("model" axis): attention Q/KV/O head dims, MLP hidden dim, MoE
    *expert* dim (expert parallelism), Mamba/RWKV inner channel dims,
    vocab-parallel embedding/head.
  * FSDP ("data" axis, + "pod" when the pod axis plays dp): the other large
    dim of every weight — ZeRO-3-style; GSPMD inserts the just-in-time
    all-gathers. Collage optimizer state (δθ, m, v, δv) shards *identically*
    to its parameter (pure elementwise update ⇒ zero extra collectives).
  * Sequence: long-context decode shards the KV cache length over "data"
    (context parallelism); activations shard batch over dp axes.

Rules are *name-based* (the last named path component) + rank-based (a
leading layer-stack dim from scan-over-layers gets a None prepended), so one
table covers all 10 architectures.

Bucketed states (core.bucketing, DESIGN.md §5) shard differently: every
flat 1-D bucket — params AND all optimizer roles — shards along its single
axis over the dp axes (ZeRO-style). Because the optimizer update is purely
elementwise and every role bucket has the identical layout, all roles
co-shard with zero extra collectives, exactly like the per-leaf rule; the
engine composes with FSDP for free. Pad buckets with
``bucket_pad_multiple(mesh)`` so the flat axis divides the dp axes exactly.
"""
from __future__ import annotations

import math
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import bucketing

# name → base spec (without the layer-stack dim). "F" marks the FSDP slot.
_F = "__fsdp__"
_RULES: dict[str, tuple] = {
    # embeddings / head
    "embed": ("model", _F),            # (V, D) vocab-parallel
    "lm_head": (_F, "model"),          # (D, V)
    # attention
    "wq": (_F, "model"), "wk": (_F, "model"), "wv": (_F, "model"),
    "wo": ("model", _F),
    "q_norm": (None,), "k_norm": (None,),
    # dense MLP
    "w_gate": (_F, "model"), "w_up": (_F, "model"), "w_down": ("model", _F),
    "w_in": (_F, "model"), "w_out": ("model", _F),
    # MoE (expert-parallel over "model")
    "router": (None, None),
    "we_gate": ("model", _F, None), "we_up": ("model", _F, None),
    "we_down": ("model", None, _F),
    # Mamba
    "in_proj": (_F, "model"), "out_proj": ("model", _F),
    "conv_w": (None, "model"), "x_proj": ("model", None),
    "dt_proj": (None, "model"), "dt_bias": ("model",),
    "A_log": ("model", None), "D": ("model",),
    # RWKV6
    "wr": (_F, "model"), "wg": (_F, "model"),
    "w_a": (_F, None), "w_b": (None, "model"),
    "u": (None, None), "mu": (None, None), "ln_scale": (None,),
    "w0": (None,),
    # norms
    "norm": (None,), "final_norm": (None,),
}


def _dp_axes(mesh: Mesh) -> tuple:
    axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return axes if len(axes) > 1 else (axes[0] if axes else None)


def _last_name(path) -> str:
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
        if isinstance(entry, jax.tree_util.GetAttrKey):
            name = str(entry.name)
            if name not in ("hi", "lo"):   # Expansion components follow param
                return name
    return ""


_ATTN_NAMES = {"wq", "wk", "wv", "wo", "q_norm", "k_norm"}


def param_spec(path, leaf, mesh: Mesh, fsdp: bool = True,
               tp_mode: str = "full") -> P:
    """tp_mode: "full" (default) | "mlponly" (attention replicated across
    the model axis — for archs whose head counts don't divide it, killing
    GSPMD resharding storms) | "none" (pure FSDP; model axis idle)."""
    name = _last_name(path)
    base = _RULES.get(name)
    if base is None:
        return P()                         # replicate unknown/small leaves
    if tp_mode == "none" or (tp_mode == "mlponly" and name in _ATTN_NAMES):
        base = tuple(None if s == "model" else s for s in base)
    fs = _dp_axes(mesh) if fsdp else None
    base = tuple(fs if s == _F else s for s in base)
    extra = leaf.ndim - len(base)
    assert extra in (0, 1), (name, leaf.ndim, base)
    spec = (None,) * extra + base          # leading layer-stack dim
    # drop axis shardings whose size doesn't divide the dim (pjit arguments
    # require exact divisibility — e.g. vocab 49155 stays replicated/padded)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    fixed = []
    for dim, s in zip(leaf.shape, spec):
        names = s if isinstance(s, tuple) else ((s,) if s else ())
        n = 1
        for a in names:
            n *= sizes[a]
        fixed.append(s if n > 1 and dim % n == 0 else None)
    return P(*fixed)


_BUCKET_FIELDS = frozenset(bucketing.BUCKET_STATE_FIELDS)


def _is_bucket_leaf(path, leaf) -> bool:
    """A 1-D leaf reached through a BucketedParams/BucketedOptState role
    attribute then a tuple index (the per-bucket flat arrays)."""
    if getattr(leaf, "ndim", None) != 1:
        return False
    for i, entry in enumerate(path):
        if (isinstance(entry, jax.tree_util.GetAttrKey)
                and entry.name in _BUCKET_FIELDS
                and i + 1 < len(path)
                and isinstance(path[i + 1], jax.tree_util.SequenceKey)):
            return True
    return False


def bucket_spec(leaf, mesh: Mesh, fsdp: bool = True) -> P:
    """Shard a flat bucket along its single axis over the dp axes (ZeRO-3
    style); replicate when the padded length doesn't divide the axis."""
    if not fsdp:
        return P()
    dp = _dp_axes(mesh)
    if dp is None:
        return P()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        n *= sizes[a]
    return P(dp) if n > 1 and leaf.shape[0] % n == 0 else P()


def bucket_pad_multiple(mesh: Mesh, block: int = 1) -> int:
    """Layout pad_multiple that keeps every bucket dividing the mesh's dp
    axes with each device's share a whole number of fused-kernel blocks
    (``bucketing.BLOCK_PAD``) — pass to BucketPolicy.

    ``block``: quantization block size of the compressed gradient collective
    (compression.BLOCK for fp8) — each device's ZeRO flat-axis shard must
    itself be a whole number of blocks so the reduce-scattered payload's
    per-block scales stay shard-aligned."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = _dp_axes(mesh)
    n = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        if a:
            n *= sizes[a]
    return n * math.lcm(bucketing.BLOCK_PAD, block)


def _is_grad_err_leaf(path) -> bool:
    """EF-compression residual leaf (per-device compressor state with a
    leading dp-device dim) — used by the sharded engine's spec rules
    (train/sharded.py). Both TrainState and BucketedOptState register with
    key paths so the ``grad_err`` attribute is visible here."""
    return any(isinstance(e, jax.tree_util.GetAttrKey)
               and e.name == "grad_err" for e in path)


def state_shardings(abstract_tree: Any, mesh: Mesh, fsdp: bool = True,
                    tp_mode: str = "full") -> Any:
    """NamedShardings for a TrainState/params pytree (path-rule based);
    bucketed leaves get the flat-axis FSDP spec. (The sharded engine's
    per-device grad_err rows are spec'd by train/sharded.py's own
    state_pspecs, not here — this is the GSPMD/pjit path.)"""
    def leaf_fn(path, leaf):
        if _is_bucket_leaf(path, leaf):
            return NamedSharding(mesh, bucket_spec(leaf, mesh, fsdp))
        return NamedSharding(mesh, param_spec(path, leaf, mesh, fsdp, tp_mode))
    return jax.tree_util.tree_map_with_path(leaf_fn, abstract_tree)


def batch_shardings(abstract_batch: Any, mesh: Mesh) -> Any:
    dp = _dp_axes(mesh)

    def leaf_fn(path, leaf):
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        n = 1
        for a in (dp if isinstance(dp, tuple) else (dp,)):
            n *= sizes[a] if a else 1
        if leaf.shape[0] % max(n, 1) != 0:   # e.g. long_500k batch=1
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(dp, *([None] * (leaf.ndim - 1))))
    return jax.tree_util.tree_map_with_path(leaf_fn, abstract_batch)


def cache_shardings(abstract_caches: Any, mesh: Mesh,
                    context_parallel: bool = False) -> Any:
    """DecodeState / SlotState / SpecState KV-cache shardings: batch over
    dp, heads/channels over model; the per-row position vector co-shards
    with the batch rows. Routing is by leaf ATTRIBUTE NAME (keyed pytree
    paths), so the speculative ``SpecState`` needs no extra rules: its
    ``slots`` half reuses the SlotState rules and its ``draft`` half is a
    plain DecodeState over the same (max_slots, cache_len) grid — both
    pools co-shard slot-for-slot, which is what keeps draft proposals and
    target verify on the same device rows. When ``context_parallel``
    (long_500k, batch=1): cache LENGTH over "data"."""
    dp = _dp_axes(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_dp = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        n_dp *= sizes[a] if a else 1

    def leaf_fn(path, leaf):
        name = _last_name(path)
        if name == "pos" and leaf.ndim == 1:        # DecodeState.pos (B,)
            bshard = dp if leaf.shape[0] % n_dp == 0 else None
            return NamedSharding(mesh, P(bshard))
        # SlotState per-slot bookkeeping: slots co-shard with batch rows
        if (name in ("active", "done", "n_gen", "budget")
                and leaf.ndim == 1):                # SlotState.* (max_slots,)
            bshard = dp if leaf.shape[0] % n_dp == 0 else None
            return NamedSharding(mesh, P(bshard))
        if name == "tok" and leaf.ndim == 2:        # SlotState.tok (slots, 1)
            bshard = dp if leaf.shape[0] % n_dp == 0 else None
            return NamedSharding(mesh, P(bshard, None))
        bdim = leaf.shape[1] if leaf.ndim > 1 else 1
        bshard = dp if (leaf.ndim > 1 and bdim % n_dp == 0) else None
        if name in ("k", "v") and leaf.ndim == 5:   # (layers, B, S, hk, dh)
            hk = leaf.shape[3]
            hshard = "model" if hk % sizes.get("model", 1) == 0 else None
            if context_parallel:
                sshard = "data" if leaf.shape[2] % sizes.get("data", 1) == 0 \
                    else None
                return NamedSharding(mesh, P(None, None, sshard, hshard, None))
            return NamedSharding(mesh, P(None, bshard, None, hshard, None))
        if name == "h" and leaf.ndim == 4:          # mamba (layers, B, d_in, n)
            return NamedSharding(mesh, P(None, bshard, "model", None))
        if name == "S" and leaf.ndim == 5:          # rwkv (layers, B, H, dk, dv)
            hshard = "model" if leaf.shape[2] % sizes.get("model", 1) == 0 else None
            return NamedSharding(mesh, P(None, bshard, hshard, None, None))
        if name == "conv" and leaf.ndim == 4:       # (layers, B, K-1, d_in)
            return NamedSharding(mesh, P(None, bshard, None, "model"))
        if name == "last_x" and leaf.ndim == 3:     # (layers, B, D)
            return NamedSharding(mesh, P(None, bshard, None))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map_with_path(leaf_fn, abstract_caches)


def make_activation_sharder(mesh: Mesh, sp: bool = False):
    """The fn installed into models.transformer.activation_sharding.

    sp=True: Korthikanti-style sequence parallelism — residual-stream
    activations between blocks are sharded over the *model* axis on the
    sequence dim, so GSPMD lowers the TP boundary all-reduces into
    reduce-scatter (+ all-gather at the next matmul): half the wire bytes
    and the norms/elementwise run on 1/tp of the tokens."""
    dp = _dp_axes(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes.get("model", 1)

    def fn(x, kind):
        if x.ndim == 3:
            seq_axis = "model" if (sp and x.shape[1] % tp == 0) else None
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(dp, seq_axis, None)))
        return x
    return fn
