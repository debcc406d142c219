"""Fused Collage-AdamW Pallas-TPU kernel (Paper Remark 5.2) — all six
strategies + in-kernel metrics epilogue, over persistent flat buckets.

One HBM round-trip for the entire Algorithm 2 update: each grid step loads
(8,128)-aligned VMEM tiles of the strategy's bucket-resident state (see
``repro.core.bucketing``), runs the full EMA + bias-corrected update +
Grow/Mul MCF pipeline in fp32 VPU registers with explicit round-to-nearest
onto the bf16 grid, and stores the tiles back. Per-strategy state tiles:

  A       θ, m, v                      (all bf16)
  B       θ, m, v, δθ                  (bf16)
  C       θ, m, v-hi, v-lo, δθ         (bf16; v is an MCF expansion)
  KAHAN   θ, m, v, c                   (bf16; c = compensation buffer)
  SR      θ, m, v                      (bf16; + counter-based noise bits)
  D⁻/D    θ (bf16), m, v fp32 (+ fp32 master for D)

The **metrics epilogue** accumulates the Paper Def. 3.3 diagnostics in the
same HBM pass: per grid step each of ⟨Δθ,Δθ̂⟩, ‖Δθ‖², ‖Δθ̂‖², lost-count,
‖g‖² is folded to one (8, 128) partial tile (``fold_rows``: the block's
sublane tiles added in a pinned order) and written as a tile-legal
(5·8, 128) output block; the tiny cross-tile reduction happens in the
wrapper — EDQ costs zero extra passes over HBM.

**Stochastic rounding** is counter-based (bucketing.sr_noise_bits): 16 noise
bits per element derived from hash(seed, element-index) — no threaded key,
so the kernel stays a pure elementwise pass; the identical pure-jnp
definition is used by ``ref.py``, making kernel and oracle bit-identical by
construction. The element index is BUCKET-GLOBAL: a ZeRO-sharded caller
passes ``elem_offset`` (this shard's start position inside the full bucket,
``axis_index · padded/n_dp``) so every shard draws the exact noise bits the
unsharded step would — SR + ZeRO is bit-identical to SR + replicated by
construction (DESIGN.md §4).

Numeric discipline matches repro.core.mcf exactly (the ref.py oracle):
every bf16 rounding is an explicit round-to-nearest-even onto the bf16 grid
(``_rn``). Mosaic has no ``reduce_precision`` lowering, so the kernel
rounds with integer ops on the f32 bit pattern — bit-identical to
``reduce_precision(x, 8, 7)`` and, like it, opaque to any convert-pair
simplification. Interpret mode executes the same ops, so CPU validation
covers the exact arithmetic the TPU performs. Option-D arithmetic runs in
plain fp32 exactly like the library path.

Scalars (lr, bias corrections, SR seed/offset) ride in SMEM; every VMEM
block is a whole number of (16, 128) bf16 tiles or spans its array
(``choose_block_rows``), as Mosaic requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bucketing
from repro.kernels import resolve_interpret

LANES = 128       # TPU VPU lane count: last dim of every tile
SUBLANES = 8      # (8, 128) is the f32 VMEM native tile
PACKED_ROWS = 16  # (16, 128) is the packed bf16 tile
BLOCK_ROWS = 256  # rows per grid step → (256, 128) tiles, 64 KiB bf16 each
N_PARTIALS = 5    # metrics partials: dot, un2, en2, lost, gn2

# bucket-state fields each strategy reads AND writes, in tile order
_FIELDS = {
    "A": ("theta", "m", "vhi"),
    "B": ("theta", "m", "vhi", "delta"),
    "C": ("theta", "m", "vhi", "vlo", "delta"),
    "KAHAN": ("theta", "m", "vhi", "delta"),
    "SR": ("theta", "m", "vhi"),
    "D-": ("theta", "m", "vhi"),
    "D": ("theta", "m", "vhi", "master"),
}


def state_fields(strategy: str) -> tuple:
    return _FIELDS[strategy]


def field_dtype(field: str, strategy: str):
    """Storage dtype of a bucket-state field (bf16 component family vs the
    fp32 optimizer states of option D)."""
    if field == "master" or (strategy in ("D-", "D") and field in ("m", "vhi")):
        return jnp.float32
    return jnp.bfloat16


def choose_block_rows(rows: int, block_rows: int = BLOCK_ROWS) -> int:
    """Rows per grid step — shared by the kernel wrapper and the ref oracle
    so metric partial tiling (and therefore f32 summation order) is
    identical in both. A bucket of at most ``block_rows`` rows is one block
    (a block spanning its array is always tile-legal); otherwise the
    largest divisor ≤ block_rows that is a multiple of the packed bf16 tile
    (16 rows), else of the f32 tile (8 rows), else the whole array.
    ``BucketPolicy``'s default padding makes every bucket a multiple of
    BLOCK_ROWS rows, so the real layout always takes full blocks."""
    if rows <= block_rows:
        return rows
    for tile in (PACKED_ROWS, SUBLANES):
        for br in range(block_rows - block_rows % tile, 0, -tile):
            if rows % br == 0:
                return br
    return rows


def fold_rows(x):
    """(R, 128) → (8, 128): the R/8 sublane tiles summed left to right.
    Static tile-aligned slices and elementwise adds only, so the order is
    pinned (XLA may not reassociate explicit adds) and it lowers in Mosaic;
    ``ref.py`` replays the same sequence for bit-identical partials."""
    acc = x[0:SUBLANES]
    for j in range(SUBLANES, x.shape[0], SUBLANES):
        acc = acc + x[j:j + SUBLANES]
    return acc


def _rn(x):
    """Round-to-nearest-even onto the bf16 grid, staying f32 — integer ops
    on the bit pattern (add half an ulp minus the tie bit, truncate), equal
    to ``lax.reduce_precision(x, 8, 7)`` for every non-NaN input."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    tie = (bits >> 16) & jnp.uint32(1)
    bits = (bits + jnp.uint32(0x7FFF) + tie) & jnp.uint32(0xFFFF0000)
    return jnp.where(x != x, x, jax.lax.bitcast_convert_type(bits,
                                                             jnp.float32))


def _rn_const(x) -> float:
    """``_rn`` of a trace-time constant (β's, 1−β), on the host."""
    return float(np.float32(x).astype(jnp.bfloat16).astype(np.float32))


def _two_sum(a, b):
    x = _rn(a + b)
    bv = _rn(x - a)
    av = _rn(x - bv)
    return x, _rn(_rn(b - bv) + _rn(a - av))


def _fast2sum(a, b):
    x = _rn(a + b)
    return x, _rn(b - _rn(x - a))


def _grow(hi, lo, a):
    u, v = _two_sum(hi, a)
    return _fast2sum(u, _rn(lo + v))


def _mul_expansion(a_hi, a_lo, b_hi, b_lo):
    prod = a_hi * b_hi                    # exact in f32 (bf16 inputs)
    x = _rn(prod)
    e = _rn(prod - x)
    cross = _rn(_rn(a_hi * b_lo) + _rn(a_lo * b_hi))
    e = _rn(e + cross)
    return _fast2sum(x, e)


def collage_update_kernel(
        *refs, b1: float, b2: float, eps: float, wd: float, strategy: str,
        pt_decay: bool, compute_metrics: bool, block_rows: int):
    """One grid step over a (block_rows, 128) tile of the bucket.

    refs layout: (1,) SMEM scalars lr, bc1, bc2 (f32) [, seed,
    elem_offset (u32, SR only)] · g · state-field tiles · state-field
    output tiles · [metrics partial tiles]."""
    fields = _FIELDS[strategy]
    it = iter(refs)
    lr, bc1, bc2 = next(it)[0], next(it)[0], next(it)[0]
    seed_ref = next(it) if strategy == "SR" else None
    offset_ref = next(it) if strategy == "SR" else None
    g_ref = next(it)
    in_refs = {f: next(it) for f in fields}
    out_refs = {f: next(it) for f in fields}
    metrics_ref = next(it) if compute_metrics else None

    f32 = jnp.float32
    g = g_ref[...].astype(f32)
    theta = in_refs["theta"][...].astype(f32)
    m = in_refs["m"][...].astype(f32)
    vhi = in_refs["vhi"][...].astype(f32)
    # weight decay inside the summed update (Alg. 2 l.12) unless the
    # PyTorch-style separate-decay ablation is selected (App. D Eq. 4).
    wd_upd = 0.0 if pt_decay else wd

    if strategy in ("D-", "D"):
        # fp32 optimizer states, plain f32 arithmetic (no rounding emulation)
        m_new = f32(b1) * m + f32(1.0 - b1) * g
        vhi_new = f32(b2) * vhi + f32(1.0 - b2) * g * g
        mhat = m_new / bc1
        vhat = vhi_new / bc2
        if strategy == "D":
            w = in_refs["master"][...]
            upd = -lr * (mhat / (jnp.sqrt(vhat) + eps) + wd_upd * w)
            w_new = w + upd                       # fp32 master update
            theta_new = _rn(w_new)                # RN onto the bf16 grid
            out_refs["master"][...] = w_new
        else:
            upd = -lr * (mhat / (jnp.sqrt(vhat) + eps) + wd_upd * theta)
            theta_new = _rn(theta + _rn(upd))     # bf16 ⊕ → lost arithmetic
        eff = theta_new - theta
        out_refs["theta"][...] = theta_new.astype(jnp.bfloat16)
        out_refs["m"][...] = m_new
        out_refs["vhi"][...] = vhi_new
    else:
        # bf16 component family: strict-FPU discipline (DESIGN.md §3)
        cb1, c1m = _rn_const(b1), _rn_const(1.0 - b1)
        cb2, c2m = _rn_const(b2), _rn_const(1.0 - b2)
        m_new = _rn(_rn(cb1 * m) + _rn(c1m * g))
        g2 = _rn(g * g)

        if strategy == "C":
            vlo = in_refs["vlo"][...].astype(f32)
            b2hi = _rn_const(b2)
            b2lo = _rn_const(np.float32(b2) - np.float32(b2hi))
            ph, plo = _mul_expansion(b2hi, b2lo, vhi, vlo)
            vhi_new, vlo_new = _grow(ph, plo, _rn(c2m * g2))
            vhat = (vhi_new + vlo_new) / bc2
            out_refs["vlo"][...] = vlo_new.astype(jnp.bfloat16)
        else:  # β₂ cast to bf16 (the paper's failure mode, kept faithful)
            vhi_new = _rn(_rn(cb2 * vhi) + _rn(c2m * g2))
            vhat = vhi_new / bc2

        mhat = m_new / bc1
        upd = -lr * (mhat / (jnp.sqrt(vhat) + eps) + wd_upd * theta)
        upd16 = _rn(upd)

        if strategy == "A":
            base = theta
            if pt_decay:
                # a scalar has no bit-pattern rounding in Mosaic: round
                # the lane-broadcast factor instead (same value per lane)
                factor = _rn(jnp.full(g.shape, 1.0 - lr * f32(wd), f32))
                base = _rn(theta * factor)
            theta_new = _rn(base + upd16)
            eff = theta_new - theta
        elif strategy == "SR":
            i = pl.program_id(0)
            base_idx = offset_ref[0] \
                + (i * block_rows * LANES).astype(jnp.uint32)
            row = jax.lax.broadcasted_iota(jnp.uint32, g.shape, 0)
            col = jax.lax.broadcasted_iota(jnp.uint32, g.shape, 1)
            idx = base_idx + row * jnp.uint32(LANES) + col
            noise = bucketing.sr_noise_bits(idx, seed_ref[0])
            theta_new = bucketing.stochastic_round_bits(theta + upd, noise)
            eff = theta_new - theta
        elif strategy == "KAHAN":
            c = in_refs["delta"][...].astype(f32)
            upd_c = _rn(upd16 + c)
            theta_new = _rn(theta + upd_c)
            c_new = _rn(upd_c - _rn(theta_new - theta))
            eff = theta_new - theta
            out_refs["delta"][...] = c_new.astype(jnp.bfloat16)
        else:  # B / C: Grow Δθ into the (θ, δθ) expansion
            delta = in_refs["delta"][...].astype(f32)
            theta_new, delta_new = _grow(theta, delta, upd16)
            # Δθ̂ per-component (exact in f32; see core.collage._leaf_step)
            eff = (theta_new - theta) + (delta_new - delta)
            out_refs["delta"][...] = delta_new.astype(jnp.bfloat16)

        out_refs["theta"][...] = theta_new.astype(jnp.bfloat16)
        out_refs["m"][...] = m_new.astype(jnp.bfloat16)
        out_refs["vhi"][...] = vhi_new.astype(jnp.bfloat16)

    if compute_metrics:
        # partial-reduction epilogue: same tile, zero extra HBM traffic.
        # fold_rows (not jnp.sum) pins the accumulation order so the
        # partials match the ref oracle bit-for-bit.
        lost = ((jnp.abs(upd) > 0) & (eff == 0)).astype(jnp.float32)
        for k, q in enumerate((upd * eff, upd * upd, eff * eff, lost, g * g)):
            metrics_ref[k * SUBLANES:(k + 1) * SUBLANES, :] = fold_rows(q)


def sum_partial_tiles(tiles):
    """(grid·5·8, 128) kernel partial tiles → the 5 metric partials, each
    a det_sum over its grid·8·128 entries (shared with ref.py)."""
    t = tiles.reshape(-1, N_PARTIALS, SUBLANES * LANES)
    return tuple(bucketing.det_sum(t[:, k]) for k in range(N_PARTIALS))


@functools.partial(jax.jit, static_argnames=(
    "b1", "b2", "eps", "wd", "strategy", "pt_decay", "compute_metrics",
    "interpret", "block_rows"))
def collage_bucket_update(state: dict, g, lr, bc1, bc2, seed=None,
                          elem_offset=None, *,
                          b1=0.9, b2=0.999, eps=1e-8, wd=0.0, strategy="C",
                          pt_decay=False, compute_metrics=False,
                          interpret=None, block_rows=BLOCK_ROWS):
    """Fused update of ONE flat bucket: ``state`` maps the strategy's field
    names (see ``state_fields``) to 1-D arrays of identical length N
    (N % 128 == 0 — the bucketing layout pads). Returns ``(new_state,
    partials)`` where partials is a (5,) f32 metrics vector (dot, ‖Δθ‖²,
    ‖Δθ̂‖², lost-count, ‖g‖²) or None.

    ``elem_offset`` (SR only, default 0): this array's element-0 position
    inside the FULL bucket — a ZeRO shard passes its flat-axis start so the
    counter-based noise stream indexes elements bucket-globally.
    ``interpret``: None → from the platform (``repro.kernels``)."""
    fields = _FIELDS[strategy]
    assert set(state) == set(fields), (sorted(state), fields)
    n = g.shape[0]
    assert n % LANES == 0, n
    rows = n // LANES
    br = choose_block_rows(rows, block_rows)
    grid = (rows // br,)

    def t2(x):
        return x.reshape(rows, LANES)

    tile = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(
        collage_update_kernel, b1=b1, b2=b2, eps=eps, wd=wd,
        strategy=strategy, pt_decay=pt_decay,
        compute_metrics=compute_metrics, block_rows=br)

    # one (1,) SMEM array per scalar: stacking them would put a
    # concatenate into the steady-state step (DESIGN.md §5)
    scalars = [jnp.reshape(x, (1,)).astype(jnp.float32)
               for x in (lr, bc1, bc2)]
    if strategy == "SR":
        assert seed is not None, "SR needs a seed scalar"
        if elem_offset is None:
            elem_offset = 0
        scalars += [jnp.reshape(jnp.asarray(x), (1,)).astype(jnp.uint32)
                    for x in (seed, elem_offset)]
    inputs = scalars + [t2(g)] + [t2(state[f]) for f in fields]
    in_specs = [smem] * len(scalars) + [tile] * (1 + len(fields))

    out_shape = [jax.ShapeDtypeStruct((rows, LANES),
                                      field_dtype(f, strategy))
                 for f in fields]
    out_specs = [tile] * len(fields)
    if compute_metrics:
        assert br % SUBLANES == 0, (br, "metrics fold needs whole f32 tiles")
        prow = N_PARTIALS * SUBLANES
        out_shape.append(
            jax.ShapeDtypeStruct((grid[0] * prow, LANES), jnp.float32))
        out_specs.append(pl.BlockSpec((prow, LANES), lambda i: (i, 0)))

    # every state field is updated in place: with the train step's state
    # donated, the bucket never exists twice in HBM
    first = len(scalars) + 1
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={first + k: k for k in range(len(fields))},
        interpret=resolve_interpret(interpret),
        name="collage_update",
    )(*inputs)

    new_state = {f: outs[k].reshape(n) for k, f in enumerate(fields)}
    partials = None
    if compute_metrics:
        # tuple of scalars (not a stacked vector): keeps the steady-state
        # step free of even scalar-sized concatenate ops
        partials = sum_partial_tiles(outs[len(fields)])
    return new_state, partials


def collage_update(g, theta, delta, m, vhi, vlo, lr, bc1, bc2, *,
                   b1=0.9, b2=0.999, eps=1e-8, wd=0.0, strategy="C",
                   interpret=None, block_rows=BLOCK_ROWS):
    """Legacy fixed-signature entrypoint (strategies A/B/C): apply the fused
    update to 1-D bf16 arrays of identical length N (N % 128 == 0). Unused
    buffers for the strategy (δθ for A, v-lo for A/B) pass through."""
    fields = _FIELDS[strategy]
    full = {"theta": theta, "m": m, "vhi": vhi, "vlo": vlo, "delta": delta}
    state = {f: full[f] for f in fields}
    new_state, _ = collage_bucket_update(
        state, g, lr, bc1, bc2, b1=b1, b2=b2, eps=eps, wd=wd,
        strategy=strategy, interpret=interpret, block_rows=block_rows)
    out = dict(full, **new_state)
    return (out["theta"], out["delta"], out["m"], out["vhi"], out["vlo"])
