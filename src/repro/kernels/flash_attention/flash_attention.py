"""FlashAttention Pallas-TPU kernels: forward + custom-VJP backward
(causal + sliding-window, GQA) — the training-path subsystem that removes
the O(L²) score buffer from BOTH passes (DESIGN.md §7).

Forward (``_fwd_kernel``): grid = (batch, q_heads, Lq/BLK_Q); each program
streams KV blocks of BLK_K with the online-softmax recurrence entirely in
VMEM — scores never touch HBM. Besides the output O it emits the row
log-sum-exp LSE = m + log(l), the only softmax statistic the backward pass
needs (saving the (L, L) probability matrix is exactly what flash forbids).

Backward: two kernels, both recomputing scores in VMEM from (Q, K, LSE):

  * ``_dq_kernel`` — q-block grid (batch, q_heads, Lq/BLK_Q): for each
    query block, stream key blocks, p = exp(s − lse), ds = p·(dO·Vᵀ − D),
    accumulate dQ += ds·K.
  * ``_dkv_kernel`` — k-block grid (batch, kv_heads, Lk/BLK_K, group):
    for each key block, stream query blocks, accumulate dV += pᵀ·dO and
    dK += dsᵀ·Q. The innermost ``group`` grid dim revisits the same dK/dV
    output block for every query head of the GQA group (grouped index-maps
    — KV heads are never replicated in memory in either pass), summing the
    per-q-head contributions in place.

``D = rowsum(dO ∘ O)`` (the standard recomputation trick: the dP→dS
softmax Jacobian term ⟨dPᵢ, Pᵢ⟩ equals ⟨dOᵢ, Oᵢ⟩) is computed once outside
the kernels — an O(L·dh) elementwise pass, not a materialized score.

``flash_mha`` wraps forward+backward in a ``jax.custom_vjp``: causal,
sliding-window and GQA, arbitrary (odd) L via zero-padding to the block
multiple with an in-kernel valid-length mask. ``interpret=None`` resolves
to interpret-mode off TPU (``repro.kernels.resolve_interpret``), so the
same entry point runs tier-1 CI on CPU and compiles to Mosaic on device.
Oracle = ``ref.attention_ref`` (full masked softmax), forward AND
``jax.grad`` swept in tests/test_flash_vjp.py.

Every kernel walks its tiles with ``_tile_loop``: only the tiles that
straddle the causal diagonal, the window's lower edge or the valid length
build the mask (``_tile_ranges``), and the others run in unrolled groups
so the scheduler overlaps one tile's chain of products and softmax with
the next's. bf16 inputs reach the MXU as bf16 tiles (``_dot``); any f32
input keeps every tile f32. DESIGN.md §7 says how the MXU is fed.

Row statistics (LSE, D) cross the kernel boundary as (B, H, L, 128) f32,
the row value replicated across the 128 lanes: a per-row block is then a
tile-legal (blk, 128) slab, and ``_col`` reads its first lane as the
(blk, 1) column that broadcasts over a score tile. Outside the kernels
they are kept as compact (B, H, L) — only the kernel operands are wide.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -1e30
F32 = jnp.float32
BF16 = jnp.bfloat16
STAT_LANES = 128   # lane width of the replicated LSE / D kernel operands

# contraction forms of a 2-D product: the MXU reads a transposed right
# operand natively, so Kᵀ and Vᵀ are never materialised
NN = (((1,), (0,)), ((), ()))    # a · b
NT = (((1,), (1,)), ((), ()))    # a · bᵀ
TN = (((0,), (0,)), ((), ()))    # aᵀ · b
UNROLL = (8, 4, 2) # group sizes of a kernel's unmasked tiles (PERF.md §6)


def _wide(x):
    """(B, H, L) row statistic → (B, H, L, 128) kernel operand."""
    return jnp.broadcast_to(x[..., None], x.shape + (STAT_LANES,))


def _col(ref_block):
    """(blk, 128) replicated statistic block → (blk, 1) column."""
    return ref_block[:, 0:1]


def _mxu_dtype(*xs):
    """Operand dtype of the kernels' products: bf16 when every input is
    bf16, else f32 (every tile up-cast, as the f32 path always was)."""
    return BF16 if all(x.dtype == BF16 for x in xs) else F32


def _dot(a, b, dims):
    """One MXU product of two tiles of the operand dtype, f32 result.

    An f32 side (P, dS) is cast to the operand dtype first. For bf16 that
    is one round-to-nearest cast, which is what the v5e MXU does to an f32
    operand at Mosaic's default precision (one bf16 pass, PERF.md §6): the
    bf16 path computes the f32 path's products from half the bytes."""
    return jax.lax.dot_general(a.astype(b.dtype), b, dims,
                               preferred_element_type=F32)


def _band_lo_block(qi, blk_q: int, blk_k: int, window: int):
    """First key-block index inside the sliding-window band for query block
    ``qi``. The lowest position any query in the block attends is
    qi·blk_q − window + 1 (kpos ≤ qpos − window is masked), so the correct
    floor-divide at the band edge is on (… + 1) — dividing qi·blk_q − window
    visits one extra fully-masked block per program."""
    return jnp.maximum(qi * blk_q - window + 1, 0) // blk_k


def _mask(s_shape, q0, k0, *, causal: bool, window: int, valid_len: int):
    """Invalid-pair mask for a (blk_q, blk_k) tile at offsets (q0, k0)."""
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s_shape, 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s_shape, 1)
    bad = jnp.zeros(s_shape, bool)
    if causal:
        bad |= kpos > qpos
    if window:
        bad |= kpos <= qpos - window
    if valid_len:
        bad |= kpos >= valid_len
    return bad


def _tile_ranges(i, *, over_keys: bool, blk_q: int, blk_k: int,
                 seq_len: int, causal: bool, window: int, valid_len: int):
    """The tiles one program visits, as (lo, a, b, hi): it loops over
    [lo, hi); the tiles in [a, b) hold no masked pair and skip the mask,
    those in [lo, a) and [b, hi) straddle the causal diagonal, the
    window's lower edge or ``valid_len`` and build it.

    ``over_keys``: ``i`` is a query block and the tiles are key blocks
    (forward, dQ); else ``i`` is a key block and the tiles are query
    blocks (dK/dV). Works on Python ints and on traced scalars alike."""
    if over_keys:
        q0 = i * blk_q
        lo = _band_lo_block(i, blk_q, blk_k, window) if window else 0
        # causal: skip key blocks strictly after this query block
        hi = pl.cdiv(q0 + blk_q, blk_k) if causal else seq_len // blk_k
        # key block ≥ a: every key is above every query's band edge
        a = pl.cdiv(jnp.maximum(q0 + blk_q - window, 0), blk_k) \
            if window else lo
        b = hi
        if causal:           # last key of the block ≤ first query
            b = jnp.minimum(b, (q0 + 1) // blk_k)
        if valid_len:        # every key of the block < valid_len
            b = jnp.minimum(b, valid_len // blk_k)
    else:
        k0 = i * blk_k
        nq = seq_len // blk_q
        # causal: no query before this key block attends into it; window:
        # no query past the band's upper edge does either
        lo = k0 // blk_q if causal else 0
        hi = jnp.minimum(nq, (k0 + blk_k + window - 2) // blk_q + 1) \
            if window else nq
        # query block ≥ a: its first query ≥ the key block's last key
        a = pl.cdiv(k0 + blk_k - 1, blk_q) if causal else lo
        # query block < b: its last query within the band of the first key
        b = jnp.minimum(hi, (k0 + window) // blk_q) if window else hi
        if valid_len:        # a key block reaching valid_len: all masked
            b = jnp.where(k0 + blk_k <= valid_len, b, lo)
    a = jnp.clip(a, lo, hi)
    return lo, a, jnp.clip(b, a, hi), hi


def _tile_loop(body, ranges, carry):
    """Run ``body(j, carry, masked=...)`` over a program's tiles in order:
    the masked head, the unmasked middle, the masked tail. The middle runs
    in groups of ``UNROLL[0]`` tiles an iteration, what is left in groups
    of the next size, the last tiles one at a time. One tile alone is a
    chain of dependent products, reductions and exponentials whose latency
    sets its time; the scheduler overlaps the chains of a group."""
    lo, a, b, hi = ranges
    masked = functools.partial(body, masked=True)
    plain = functools.partial(body, masked=False)
    carry = jax.lax.fori_loop(lo, a, masked, carry)
    for size in UNROLL:
        def group(t, c, size=size, start=a):
            for u in range(size):
                c = plain(start + size * t + u, c)
            return c
        n = (b - a) // size
        carry = jax.lax.fori_loop(0, n, group, carry)
        a = a + size * n
    carry = jax.lax.fori_loop(a, b, plain, carry)
    return jax.lax.fori_loop(b, hi, masked, carry)


# --------------------------------------------------------------- forward --
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, blk_q: int,
                blk_k: int, seq_len: int, causal: bool, window: int,
                scale: float, valid_len: int, mxu):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(mxu)                          # (blk_q, dh)
    m = jnp.full((blk_q, 1), NEG_INF, F32)               # row max (column)
    l = jnp.zeros((blk_q, 1), F32)                       # row sum (column)
    acc = jnp.zeros((blk_q, q.shape[-1]), F32)

    def body(kj, carry, *, masked):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(kj * blk_k, blk_k), :].astype(mxu)
        v = v_ref[0, 0, pl.ds(kj * blk_k, blk_k), :].astype(mxu)
        s = _dot(q, k, NT) * scale                        # (blk_q, blk_k)
        if masked:
            s = jnp.where(_mask(s.shape, qi * blk_q, kj * blk_k,
                                causal=causal, window=window,
                                valid_len=valid_len), NEG_INF, s)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=1, keepdims=True)
        acc_new = acc * corr + _dot(p, v, NN)
        return m_new, l_new, acc_new

    m, l, acc = _tile_loop(body, _tile_ranges(
        qi, over_keys=True, blk_q=blk_q, blk_k=blk_k, seq_len=seq_len,
        causal=causal, window=window, valid_len=valid_len), (m, l, acc))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    # fully-masked (padded) rows: m never left NEG_INF (l is NOT a valid
    # detector — every masked tile contributes p = exp(NEG_INF − NEG_INF)
    # = 1 to it). Park their LSE at +big so the backward recomputation
    # exp(NEG_INF − lse) is exactly 0 instead of exp(0) = 1.
    lse = jnp.where(m > NEG_INF * 0.5, m + jnp.log(jnp.maximum(l, 1e-30)),
                    jnp.float32(-NEG_INF))
    lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _fwd_call(q, k, v, *, causal, window, blk_q, blk_k, valid_len,
              interpret):
    B, H, L, dh = q.shape
    group = H // k.shape[1]
    scale = dh ** -0.5
    kernel = functools.partial(_fwd_kernel, blk_q=blk_q, blk_k=blk_k,
                               seq_len=L, causal=causal, window=window,
                               scale=scale, valid_len=valid_len,
                               mxu=_mxu_dtype(q, k, v))
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, H, L // blk_q),
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, dh), lambda b, h, i: (b, h, i, 0)),
            # GQA: kv head = q head // group; full-length K/V block resident
            pl.BlockSpec((1, 1, L, dh), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, L, dh), lambda b, h, i: (b, h // group, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk_q, dh), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, blk_q, STAT_LANES),
                         lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, H, L, STAT_LANES), F32)],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return o, lse[..., 0]


# -------------------------------------------------------------- backward --
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               blk_q: int, blk_k: int, seq_len: int, causal: bool,
               window: int, scale: float, valid_len: int, mxu):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(mxu)                          # (blk_q, dh)
    do = do_ref[0, 0].astype(mxu)
    lse = _col(lse_ref[0, 0])                            # (blk_q, 1)
    delta = _col(delta_ref[0, 0])

    def body(kj, acc, *, masked):
        k = k_ref[0, 0, pl.ds(kj * blk_k, blk_k), :].astype(mxu)
        v = v_ref[0, 0, pl.ds(kj * blk_k, blk_k), :].astype(mxu)
        s = _dot(q, k, NT) * scale
        if masked:
            s = jnp.where(_mask(s.shape, qi * blk_q, kj * blk_k,
                                causal=causal, window=window,
                                valid_len=valid_len), NEG_INF, s)
        p = jnp.exp(s - lse)                             # masked → exactly 0
        dp = _dot(do, v, NT)                             # (blk_q, blk_k)
        ds = p * (dp - delta)
        return acc + _dot(ds, k, NN)

    acc = _tile_loop(body, _tile_ranges(
        qi, over_keys=True, blk_q=blk_q, blk_k=blk_k, seq_len=seq_len,
        causal=causal, window=window, valid_len=valid_len),
        jnp.zeros((blk_q, q.shape[-1]), F32))
    dq_ref[0, 0] = acc * scale


def _dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                dk_ref, dv_ref, *, blk_q: int, blk_k: int, seq_len: int,
                causal: bool, window: int, scale: float, valid_len: int,
                mxu):
    kj = pl.program_id(2)
    g = pl.program_id(3)                                 # GQA group member
    k = k_ref[0, 0].astype(mxu)                          # (blk_k, dh)
    v = v_ref[0, 0].astype(mxu)
    dh = k.shape[-1]

    def body(qi, carry, *, masked):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(qi * blk_q, blk_q), :].astype(mxu)
        do = do_ref[0, 0, pl.ds(qi * blk_q, blk_q), :].astype(mxu)
        lse = _col(lse_ref[0, 0, pl.ds(qi * blk_q, blk_q), :])
        delta = _col(delta_ref[0, 0, pl.ds(qi * blk_q, blk_q), :])
        s = _dot(q, k, NT) * scale                       # (blk_q, blk_k)
        if masked:
            s = jnp.where(_mask(s.shape, qi * blk_q, kj * blk_k,
                                causal=causal, window=window,
                                valid_len=valid_len), NEG_INF, s)
        p = jnp.exp(s - lse)
        dv = dv + _dot(p, do, TN)
        dp = _dot(do, v, NT)
        ds = p * (dp - delta)
        dk = dk + _dot(ds, q, TN)
        return dk, dv

    dk, dv = _tile_loop(body, _tile_ranges(
        kj, over_keys=False, blk_q=blk_q, blk_k=blk_k, seq_len=seq_len,
        causal=causal, window=window, valid_len=valid_len),
        (jnp.zeros((blk_k, dh), F32), jnp.zeros((blk_k, dh), F32)))
    dk = dk * scale

    # the ``group`` grid dim revisits this output block once per q head of
    # the GQA group — first visit overwrites, later visits accumulate
    @pl.when(g == 0)
    def _():
        dk_ref[0, 0] = dk
        dv_ref[0, 0] = dv

    @pl.when(g > 0)
    def _():
        dk_ref[0, 0] += dk
        dv_ref[0, 0] += dv


def _bwd_call(q, k, v, o, lse, do, *, causal, window, blk_q, blk_k,
              valid_len, interpret):
    B, H, L, dh = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    scale = dh ** -0.5
    # D-trick: one O(L·dh) elementwise pass, fused by XLA — never a score
    delta = _wide((do.astype(F32) * o.astype(F32)).sum(-1))
    lse = _wide(lse)
    kw = dict(blk_q=blk_q, blk_k=blk_k, seq_len=L, causal=causal,
              window=window, scale=scale, valid_len=valid_len,
              mxu=_mxu_dtype(q, k, v, do))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=(B, H, L // blk_q),
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, dh), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, L, dh), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, L, dh), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, blk_q, dh), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, blk_q, STAT_LANES),
                         lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, blk_q, STAT_LANES),
                         lambda b, h, i: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, dh), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, F32),
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid=(B, Hkv, L // blk_k, group),
        in_specs=[
            # grouped index-maps: q head = kv head · group + g
            pl.BlockSpec((1, 1, L, dh),
                         lambda b, hk, j, g: (b, hk * group + g, 0, 0)),
            pl.BlockSpec((1, 1, L, dh),
                         lambda b, hk, j, g: (b, hk * group + g, 0, 0)),
            pl.BlockSpec((1, 1, L, STAT_LANES),
                         lambda b, hk, j, g: (b, hk * group + g, 0, 0)),
            pl.BlockSpec((1, 1, L, STAT_LANES),
                         lambda b, hk, j, g: (b, hk * group + g, 0, 0)),
            pl.BlockSpec((1, 1, blk_k, dh), lambda b, hk, j, g: (b, hk, j, 0)),
            pl.BlockSpec((1, 1, blk_k, dh), lambda b, hk, j, g: (b, hk, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk_k, dh), lambda b, hk, j, g: (b, hk, j, 0)),
            pl.BlockSpec((1, 1, blk_k, dh), lambda b, hk, j, g: (b, hk, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(k.shape, F32),
                   jax.ShapeDtypeStruct(v.shape, F32)],
        interpret=interpret,
        name="flash_dkv",
    )(q, do, lse, delta, k, v)
    return dq, dk, dv


# ----------------------------------------------------------- custom VJP ---
def _pad_len(L: int, blk_q: int, blk_k: int) -> int:
    m = math.lcm(blk_q, blk_k)
    return -(-L // m) * m


def _pad_seq(x, Lp: int):
    L = x.shape[2]
    if L == Lp:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, Lp - L), (0, 0)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_mha(q, k, v, causal, window, blk_q, blk_k, interpret):
    o, _ = _mha_fwd(q, k, v, causal, window, blk_q, blk_k, interpret)
    return o


def _mha_fwd(q, k, v, causal, window, blk_q, blk_k, interpret):
    L = q.shape[2]
    Lp = _pad_len(L, blk_q, blk_k)
    valid = L if Lp != L else 0          # 0 = no padding → no extra mask
    o, lse = _fwd_call(_pad_seq(q, Lp), _pad_seq(k, Lp), _pad_seq(v, Lp),
                       causal=causal, window=window, blk_q=blk_q,
                       blk_k=blk_k, valid_len=valid, interpret=interpret)
    o = o[:, :, :L]
    return o, (q, k, v, o, lse)


def _mha_bwd(causal, window, blk_q, blk_k, interpret, res, do):
    q, k, v, o, lse = res
    L = q.shape[2]
    Lp = _pad_len(L, blk_q, blk_k)
    valid = L if Lp != L else 0
    dq, dk, dv = _bwd_call(
        _pad_seq(q, Lp), _pad_seq(k, Lp), _pad_seq(v, Lp),
        _pad_seq(o, Lp), lse, _pad_seq(do, Lp),
        causal=causal, window=window, blk_q=blk_q, blk_k=blk_k,
        valid_len=valid, interpret=interpret)
    return (dq[:, :, :L].astype(q.dtype), dk[:, :, :L].astype(k.dtype),
            dv[:, :, :L].astype(v.dtype))


_flash_mha.defvjp(_mha_fwd, _mha_bwd)


def flash_mha(q, k, v, *, causal=True, window=0, blk_q=128, blk_k=128,
              interpret=None):
    """Differentiable flash attention (the training/prefill entry point).

    q: (B, H, L, dh); k/v: (B, Hkv, L, dh) with H % Hkv == 0 (GQA — KV
    heads are never replicated, in either pass). Returns (B, H, L, dh) in
    q.dtype. Any L: inputs are zero-padded to the block multiple and the
    kernels mask positions ≥ L. ``window`` > 0 keeps only the causal band
    kpos ∈ (qpos − window, qpos]. Both forward and backward stream KV/Q
    blocks through VMEM — no O(L²) intermediate in the lowered program
    (asserted by benchmarks/attention.py on the L=4096 train step)."""
    B, H, L, dh = q.shape
    Hkv = k.shape[1]
    assert H % Hkv == 0, (H, Hkv)
    assert k.shape == v.shape == (B, Hkv, L, dh), (q.shape, k.shape, v.shape)
    return _flash_mha(q, k, v, bool(causal), int(window), int(blk_q),
                      int(blk_k), resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "blk_q", "blk_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, blk_q=128, blk_k=128,
                    interpret=None):
    """Forward-only convenience wrapper (serving path ≥8k). Same kernel as
    ``flash_mha`` — kept as a jitted entry point for direct callers."""
    return flash_mha(q, k, v, causal=causal, window=window, blk_q=blk_q,
                     blk_k=blk_k, interpret=interpret)
