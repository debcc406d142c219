"""Pure-jnp oracle for the fused Collage-AdamW kernel: literally the
non-fused per-leaf update from repro.core.collage applied to flat bucket
arrays — the kernel must be bit-identical to the library semantics, for all
six strategies AND the StepMetrics partials.

Metrics partials are computed with the same (block_rows, 128) tiling and
the same fold/sum helpers the kernel uses (``choose_block_rows``,
``fold_rows``, ``sum_partial_tiles`` are shared) so the f32 partial-sum
order — and therefore every bit of the reduction — matches the in-kernel
epilogue.
The stochastic-rounding noise stream is the shared counter-based definition
in ``repro.core.bucketing`` (bit-identical by construction).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import bucketing, mcf
from repro.core.mcf import Expansion
from repro.kernels.collage_update.collage_update import (
    BLOCK_ROWS, LANES, choose_block_rows, fold_rows, state_fields,
    sum_partial_tiles)


def collage_bucket_update_ref(state: dict, g, lr, bc1, bc2, seed=None,
                              elem_offset=None, *,
                              b1=0.9, b2=0.999, eps=1e-8, wd=0.0,
                              strategy="C", pt_decay=False,
                              compute_metrics=False,
                              block_rows=BLOCK_ROWS, tiled_metrics=True):
    """Oracle for ``collage_bucket_update``: same signature/returns.

    ``tiled_metrics=True`` (oracle mode) mirrors the kernel's per-tile
    partials bit-for-bit; ``False`` computes the same partials with
    ordinary fused ``jnp.sum`` — O(1) ops for production-size buckets, equal
    to the tiled result up to f32 summation order. ``elem_offset`` shifts
    the SR noise index the same way the kernel's scalar does (ZeRO shards
    index elements bucket-globally)."""
    fields = state_fields(strategy)
    assert set(state) == set(fields), (sorted(state), fields)
    f32 = jnp.float32
    fpu = mcf.fpu(jnp.bfloat16)
    n = g.shape[0]
    assert n % LANES == 0, n

    theta = state["theta"]
    m = state["m"]
    vhi = state["vhi"]
    g32 = g.astype(f32)
    theta32 = theta.astype(f32)
    wd_upd = 0.0 if pt_decay else wd
    new = {}

    if strategy in ("D-", "D"):
        m_new = b1 * m + (1.0 - b1) * g32
        v_new = b2 * vhi + (1.0 - b2) * g32 * g32
        mhat = m_new / bc1
        vhat = v_new / bc2
        if strategy == "D":
            w = state["master"]
            upd32 = -lr * (mhat / (jnp.sqrt(vhat) + eps) + wd_upd * w)
            w_new = w + upd32
            new_p32 = fpu.rn(w_new)
            new["master"] = w_new
        else:
            upd32 = -lr * (mhat / (jnp.sqrt(vhat) + eps) + wd_upd * theta32)
            new_p32 = fpu.add(theta32, fpu.rn(upd32))
        eff = new_p32 - theta32
        new["theta"] = fpu.store(new_p32)
        new["m"], new["vhi"] = m_new, v_new
    else:
        cb1, c1m = fpu.rn(f32(b1)), fpu.rn(f32(1 - b1))
        cb2, c2m = fpu.rn(f32(b2)), fpu.rn(f32(1 - b2))
        m32 = fpu.add(fpu.mul(cb1, fpu.load(m)), fpu.mul(c1m, g32))
        g2 = fpu.mul(g32, g32)
        if strategy == "C":
            b2e = mcf.from_float(b2, jnp.bfloat16, vhi.shape)
            v = mcf.grow(mcf.mul(b2e, Expansion(vhi, state["vlo"])),
                         fpu.store(fpu.mul(c2m, g2)))
            new["vhi"], new["vlo"] = v.hi, v.lo
            vhat = v.value(f32) / bc2
        else:
            v32 = fpu.add(fpu.mul(cb2, fpu.load(vhi)), fpu.mul(c2m, g2))
            new["vhi"] = fpu.store(v32)
            vhat = v32 / bc2
        mhat = m32 / bc1
        upd32 = -lr * (mhat / (jnp.sqrt(vhat) + eps) + wd_upd * theta32)
        upd16_32 = fpu.rn(upd32)
        new["m"] = fpu.store(m32)

        if strategy == "A":
            base32 = theta32
            if pt_decay:
                factor = fpu.rn(1.0 - lr * f32(wd))
                base32 = fpu.mul(theta32, factor)
            new_p32 = fpu.add(base32, upd16_32)
            eff = new_p32 - theta32
            new["theta"] = fpu.store(new_p32)
        elif strategy == "SR":
            assert seed is not None, "SR needs a seed scalar"
            idx = jnp.arange(n, dtype=jnp.uint32)
            if elem_offset is not None:
                idx = jnp.asarray(elem_offset).astype(jnp.uint32) + idx
            noise = bucketing.sr_noise_bits(idx, seed)
            new_p32 = bucketing.stochastic_round_bits(theta32 + upd32, noise)
            eff = new_p32 - theta32
            new["theta"] = fpu.store(new_p32)
        elif strategy == "KAHAN":
            c = state["delta"]
            upd_c = fpu.add(upd16_32, fpu.load(c))
            new_p32 = fpu.add(theta32, upd_c)
            new_c32 = fpu.sub(upd_c, fpu.sub(new_p32, theta32))
            eff = new_p32 - theta32
            new["theta"] = fpu.store(new_p32)
            new["delta"] = fpu.store(new_c32)
        else:  # B / C
            delta = state["delta"]
            e = mcf.grow(Expansion(theta, delta), fpu.store(upd16_32))
            eff = (fpu.load(e.hi) - theta32) + (fpu.load(e.lo)
                                                - fpu.load(delta))
            new["theta"], new["delta"] = e.hi, e.lo

    partials = None
    if compute_metrics:
        partials = _metric_partials(upd32, eff, g32, block_rows) \
            if tiled_metrics else _metric_partials_fast(upd32, eff, g32)
    return new, partials


def _metric_partials_fast(upd, eff, g32):
    return (jnp.sum(upd * eff), jnp.sum(upd * upd), jnp.sum(eff * eff),
            jnp.sum(((jnp.abs(upd) > 0) & (eff == 0)).astype(jnp.float32)),
            jnp.sum(g32 * g32))


def _metric_partials(upd, eff, g32, block_rows):
    """Tiled partial sums matching the in-kernel epilogue bit-for-bit: each
    (block_rows, 128) tile folded to (8, 128) by ``fold_rows`` (vectorized
    over tiles here), then the same wrapper-side ``sum_partial_tiles``."""
    n = upd.shape[0]
    rows = n // LANES
    br = choose_block_rows(rows, block_rows)
    grid = rows // br

    def tiles(x):   # (grid, br, 128) → fold over axis 1 → (grid, 8, 128)
        x = x.reshape(grid, br, LANES).transpose(1, 0, 2)
        return fold_rows(x).transpose(1, 0, 2)

    lost = ((jnp.abs(upd) > 0) & (eff == 0)).astype(jnp.float32)
    parts = [tiles(q) for q in (upd * eff, upd * upd, eff * eff, lost,
                                g32 * g32)]
    return sum_partial_tiles(jnp.stack(parts, axis=1))


# jitted oracle: un-jitted (eager) execution skips XLA's fusion-context
# mul-add contraction and can drift 1 ulp from any compiled realization of
# the same formula (kernel OR jit) on boundary elements — see DESIGN.md §3.
jitted_ref = jax.jit(
    collage_bucket_update_ref,
    static_argnames=("b1", "b2", "eps", "wd", "strategy", "pt_decay",
                     "compute_metrics", "block_rows", "tiled_metrics"))


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd",
                                             "strategy"))
def collage_update_ref(g, theta, delta, m, vhi, vlo, lr, bc1, bc2, *,
                       b1=0.9, b2=0.999, eps=1e-8, wd=0.0, strategy="C"):
    """Legacy fixed-signature oracle (A/B/C); unused buffers pass through."""
    fields = state_fields(strategy)
    full = {"theta": theta, "m": m, "vhi": vhi, "vlo": vlo, "delta": delta}
    state = {f: full[f] for f in fields}
    new, _ = collage_bucket_update_ref(
        state, g, lr, bc1, bc2, b1=b1, b2=b2, eps=eps, wd=wd,
        strategy=strategy)
    out = dict(full, **new)
    return (out["theta"], out["delta"], out["m"], out["vhi"], out["vlo"])
