"""Stack assembly: scan-over-layer-groups for train / prefill / decode.

Each ``Group(repeats, period)`` of the config's stack program lowers to ONE
``lax.scan`` whose xs are the layer-stacked params (and, for decode, the
layer-stacked caches, emitting updated caches as ys). HLO size is O(#groups)
regardless of depth — required both for this container's single-core compile
budget and for real-TPU compile times at 62+ layers.

Activation sharding: model code is mesh-agnostic; ``shard_ctx`` (set by the
launcher) applies ``with_sharding_constraint`` at block boundaries.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.numpy as jnp

from repro.configs.base import Group, ModelConfig, Sub
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import rwkv as rwkv_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (ACC, mlp_apply, mlp_init, rms_norm,
                                 rms_norm_init)
from repro.train import scopes

# ---------------------------------------------------------------------------
# ambient activation-sharding context (no-op outside pjit launch)
_SHARD_FN = contextvars.ContextVar("repro_shard_fn", default=None)


@contextlib.contextmanager
def activation_sharding(fn):
    tok = _SHARD_FN.set(fn)
    try:
        yield
    finally:
        _SHARD_FN.reset(tok)


def shard_act(x, kind="seq"):
    fn = _SHARD_FN.get()
    return fn(x, kind) if fn is not None else x


# ------------------------------------------------------------------- init --
def sub_init(key, sub: Sub, cfg: ModelConfig, dtype):
    k_norm, k_body = jax.random.split(key)
    p = {"norm": rms_norm_init(cfg.d_model, dtype)}
    if sub.kind in ("attn", "cross_attn"):
        p.update(attn.attn_init(k_body, cfg, dtype))
    elif sub.kind == "mlp":
        p.update(mlp_init(k_body, cfg.d_model, cfg.d_ff, cfg.act, dtype))
    elif sub.kind == "moe":
        p.update(moe_lib.moe_init(k_body, cfg, dtype))
    elif sub.kind == "mamba":
        p.update(ssm_lib.mamba_init(k_body, cfg, dtype))
    elif sub.kind == "rwkv_tmix":
        p.update(rwkv_lib.rwkv_tmix_init(k_body, cfg, dtype))
    elif sub.kind == "rwkv_cmix":
        p.update(rwkv_lib.rwkv_cmix_init(k_body, cfg, dtype))
    else:
        raise ValueError(sub.kind)
    return p


def group_init(key, group: Group, cfg: ModelConfig, dtype):
    def layer(k):
        ks = jax.random.split(k, len(group.period))
        return {f"sub{i}": sub_init(ks[i], s, cfg, dtype)
                for i, s in enumerate(group.period)}
    return jax.vmap(layer)(jax.random.split(key, group.repeats))


# ---------------------------------------------------------------- forward --
def _residual(p, x, cfg, fn):
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    return fn(h)


_SCOPES = {"attn": scopes.ATTENTION, "cross_attn": scopes.ATTENTION,
           "mlp": scopes.MLP, "moe": scopes.MLP}


def sub_apply(p, x, sub: Sub, cfg: ModelConfig, memory=None, positions=None):
    """Returns (x_out, aux_loss)."""
    scope = _SCOPES.get(sub.kind)
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        return _sub_apply(p, x, sub, cfg, memory, positions)


def _sub_apply(p, x, sub: Sub, cfg: ModelConfig, memory, positions):
    aux = jnp.zeros((), ACC)
    if sub.kind == "attn":
        impl = cfg.attention_impl
        if sub.causal and attn.use_flash(cfg, x.shape[1]):
            # flash train/prefill path (DESIGN.md §7): Pallas custom-VJP
            # kernels for global AND banded-local layers above the length
            # threshold — no O(L²) score buffer in either pass
            out = _residual(p, x, cfg, lambda h: attn.kernel_flash_attention(
                p, h, cfg, causal=True, window=sub.window,
                positions=positions))
        elif sub.window and impl in ("banded", "flash") and sub.causal:
            out = _residual(p, x, cfg, lambda h: attn.banded_attention(
                p, h, cfg, window=sub.window, positions=positions))
        elif impl == "flash" and sub.causal:
            out = _residual(p, x, cfg, lambda h: attn.flash_attention(
                p, h, cfg, causal=True, window=sub.window,
                positions=positions))
        else:
            out = _residual(p, x, cfg, lambda h: attn.full_attention(
                p, h, cfg, causal=sub.causal, window=sub.window,
                positions=positions,
                kv_positions=positions))
    elif sub.kind == "cross_attn":
        out = _residual(p, x, cfg, lambda h: attn.full_attention(
            p, h, cfg, causal=False, x_kv=memory))
    elif sub.kind == "mlp":
        out = _residual(p, x, cfg, lambda h: mlp_apply(p, h, cfg.act))
    elif sub.kind == "moe":
        h = rms_norm(x, p["norm"], cfg.norm_eps)
        out, aux = moe_lib.moe_apply(p, h, cfg)
    elif sub.kind == "mamba":
        out = _residual(p, x, cfg, lambda h: ssm_lib.mamba_apply(p, h, cfg))
    elif sub.kind == "rwkv_tmix":
        out = _residual(p, x, cfg, lambda h: rwkv_lib.rwkv_tmix_apply(p, h, cfg))
    elif sub.kind == "rwkv_cmix":
        out = _residual(p, x, cfg, lambda h: rwkv_lib.rwkv_cmix_apply(p, h, cfg))
    else:
        raise ValueError(sub.kind)
    return shard_act(x + out), aux


def group_apply(params, x, group: Group, cfg: ModelConfig, memory=None,
                positions=None, remat: str = "none"):
    """Training/prefill forward through one scanned group."""

    def body(carry, layer_params):
        h, aux = carry
        for i, s in enumerate(group.period):
            h, a = sub_apply(layer_params[f"sub{i}"], h, s, cfg,
                             memory=memory, positions=positions)
            aux = aux + a
        return (h, aux), None

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), ACC)), params)
    return x, aux


# ----------------------------------------------------------------- decode --
def _freeze_rows(new, old, active):
    """Per-row select between the advanced and the previous cache: retired
    slots (continuous batching) must not mutate their carried state. Only
    used for the SMALL recurrent states (mamba h/conv, rwkv S/last_x —
    O(B·d) leaves); the attention KV write is masked at the scatter site
    instead (attn.decode_attention), where a full-cache select would be
    O(B·S·d) per token."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(
            active.reshape(active.shape + (1,) * (n.ndim - 1)), n, o),
        new, old)


def sub_decode(p, x, sub: Sub, cfg: ModelConfig, cache, pos, memory=None,
               active=None):
    """One-token step. Returns (x_out, new_cache_or_None).

    ``active (B,) bool``: slot-masked decode — rows with False keep their
    cache/state bit-identical (their computed output is discarded by the
    caller); None = every row live (the closed-batch fast path, unchanged
    lowering)."""
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    if sub.kind == "attn":
        out, nc = attn.decode_attention(p, h, cfg, cache, pos,
                                        window=sub.window, active=active)
    elif sub.kind == "cross_attn":
        out = attn.cross_decode(p, h, cfg, cache)
        nc = cache
    elif sub.kind == "mlp":
        out, nc = mlp_apply(p, h, cfg.act), None
    elif sub.kind == "moe":
        out, _ = moe_lib.moe_apply(p, h, cfg)
        nc = None
    elif sub.kind == "mamba":
        out, nc = ssm_lib.mamba_decode(p, h, cfg, cache)
        if active is not None:
            nc = _freeze_rows(nc, cache, active)
    elif sub.kind == "rwkv_tmix":
        out, nc = rwkv_lib.rwkv_tmix_decode(p, h, cfg, cache)
        if active is not None:
            nc = _freeze_rows(nc, cache, active)
    elif sub.kind == "rwkv_cmix":
        out, nc = rwkv_lib.rwkv_cmix_decode(p, h, cfg, cache)
        if active is not None:
            nc = _freeze_rows(nc, cache, active)
    else:
        raise ValueError(sub.kind)
    return x + out, nc


def group_decode(params, x, group: Group, cfg: ModelConfig, caches, pos,
                 memory=None, active=None):
    """Scan over layers carrying x; xs = (params, caches); ys = new caches."""

    def body(h, inp):
        layer_params, layer_cache = inp
        new_cache = {}
        for i, s in enumerate(group.period):
            key = f"sub{i}"
            h, nc = sub_decode(layer_params[key], h, s, cfg,
                               layer_cache.get(key), pos, memory=memory,
                               active=active)
            if key in layer_cache:
                new_cache[key] = nc if nc is not None else layer_cache[key]
        return h, new_cache

    x, new_caches = jax.lax.scan(body, x, (params, caches))
    return x, new_caches


def sub_verify(p, x, sub: Sub, cfg: ModelConfig, cache, pos, memory=None,
               active=None):
    """Width-W verify step (speculative decoding): x (B, W, D) is the
    current token + draft proposals. Same contract as ``sub_decode`` but
    every sublayer processes all W positions in one pass; attention writes
    the W new KV rows and masks each query to its own causal horizon.
    Recurrent mixers are structurally unrollable only forward — their state
    cannot roll back on rejection — so they are a capability error at the
    engine layer and a hard error here."""
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    if sub.kind == "attn":
        out, nc = attn.verify_attention(p, h, cfg, cache, pos,
                                        window=sub.window, active=active)
    elif sub.kind == "cross_attn":
        out = attn.cross_decode(p, h, cfg, cache)
        nc = cache
    elif sub.kind == "mlp":
        out, nc = mlp_apply(p, h, cfg.act), None
    elif sub.kind == "moe":
        out, _ = moe_lib.moe_apply(p, h, cfg)
        nc = None
    else:
        raise ValueError(
            f"verify step unsupported for recurrent sublayer {sub.kind!r}: "
            f"SSM/RWKV state has no structural rollback")
    return x + out, nc


def group_verify(params, x, group: Group, cfg: ModelConfig, caches, pos,
                 memory=None, active=None):
    """Scan over layers at width W — the verify-mode twin of
    ``group_decode`` (same xs/ys cache protocol)."""

    def body(h, inp):
        layer_params, layer_cache = inp
        new_cache = {}
        for i, s in enumerate(group.period):
            key = f"sub{i}"
            h, nc = sub_verify(layer_params[key], h, s, cfg,
                               layer_cache.get(key), pos, memory=memory,
                               active=active)
            if key in layer_cache:
                new_cache[key] = nc if nc is not None else layer_cache[key]
        return h, new_cache

    x, new_caches = jax.lax.scan(body, x, (params, caches))
    return x, new_caches


def group_init_cache(group: Group, cfg: ModelConfig, batch, cache_len, dtype,
                     memory_len: int = 0):
    """Zero caches stacked over repeats. Only caching subs get entries."""
    def one_layer():
        c = {}
        for i, s in enumerate(group.period):
            if s.kind == "attn":
                c[f"sub{i}"] = attn.init_kv_cache(cfg, batch, cache_len, dtype)
            elif s.kind == "cross_attn":
                c[f"sub{i}"] = attn.init_kv_cache(cfg, batch, memory_len, dtype)
            elif s.kind == "mamba":
                c[f"sub{i}"] = ssm_lib.mamba_init_state(cfg, batch, dtype)
            elif s.kind == "rwkv_tmix":
                c[f"sub{i}"] = rwkv_lib.rwkv_tmix_init_state(cfg, batch, dtype)
            elif s.kind == "rwkv_cmix":
                c[f"sub{i}"] = {"last_x": jnp.zeros((batch, cfg.d_model), dtype)}
        return c
    one = one_layer()
    return jax.tree_util.tree_map(
        lambda z: jnp.zeros((group.repeats,) + z.shape, z.dtype), one)


# ---------------------------------------------------------------- prefill --
def group_prefill(params, x, group: Group, cfg: ModelConfig, cache_len,
                  memory=None, positions=None):
    """Forward + cache construction: ys emit each layer's cache."""
    B, L, _ = x.shape
    dtype = x.dtype

    def body(carry, layer_params):
        h = carry
        cache = {}
        for i, s in enumerate(group.period):
            key = f"sub{i}"
            p = layer_params[key]
            if s.kind == "attn":
                hn = rms_norm(h, p["norm"], cfg.norm_eps)
                q, k, v = attn._qkv(
                    p, hn, hn, cfg,
                    positions if positions is not None else
                    jnp.broadcast_to(jnp.arange(L)[None], (B, L)),
                    positions if positions is not None else
                    jnp.broadcast_to(jnp.arange(L)[None], (B, L)))
                kc = attn.init_kv_cache(cfg, B, cache_len, dtype)
                cache[key] = {
                    "k": jax.lax.dynamic_update_slice(kc["k"], k.astype(dtype),
                                                      (0, 0, 0, 0)),
                    "v": jax.lax.dynamic_update_slice(kc["v"], v.astype(dtype),
                                                      (0, 0, 0, 0))}
                h, _ = sub_apply(p, h, s, cfg, positions=positions)
            elif s.kind == "cross_attn":
                hn = rms_norm(h, p["norm"], cfg.norm_eps)
                cache[key] = attn.cross_kv(p, memory, cfg)
                h, _ = sub_apply(p, h, s, cfg, memory=memory)
            elif s.kind in ("mamba", "rwkv_tmix", "rwkv_cmix"):
                h, state = _mixer_prefill(p, h, s, cfg)
                cache[key] = state
            else:
                h, _ = sub_apply(p, h, s, cfg, memory=memory,
                                 positions=positions)
        return h, cache

    x, caches = jax.lax.scan(body, x, params)
    return x, caches


def _mixer_prefill(p, x, sub: Sub, cfg):
    """Run the parallel path AND return the decode state at position L-1."""
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    if sub.kind == "mamba":
        out = ssm_lib.mamba_apply(p, h, cfg)
        state = _mamba_state_after(p, h, cfg)
    elif sub.kind == "rwkv_tmix":
        out = rwkv_lib.rwkv_tmix_apply(p, h, cfg)
        state = _rwkv_state_after(p, h, cfg)
    else:  # rwkv_cmix
        out = rwkv_lib.rwkv_cmix_apply(p, h, cfg)
        state = {"last_x": h[:, -1]}
    return x + out, state


def _mamba_state_after(p, x, cfg):
    """Final SSM state after consuming x (recomputed chunked — cheap).

    The state must reflect EXACTLY the L real tokens, so (unlike the
    pad-and-slice output path) an off-chunk tail is advanced with one exact
    partial-chunk step — pad tokens must never enter the carried state."""
    B, L, _ = x.shape
    xs, z, dt, a, b_ssm, c_ssm, conv_state = ssm_lib._ssm_inputs(p, x, cfg)
    ck = min(cfg.ssm_chunk, L)
    nc = L // ck                                 # full chunks
    d_in = xs.shape[-1]
    xs_f = xs.astype(ACC)

    def advance(h0, dt_k, b_k, xs_k):
        a_bar = jnp.exp(dt_k[..., None] * a)
        b_bar = (dt_k * xs_k)[..., None] * b_k[:, :, None, :]
        acc_a, acc_b = jax.lax.associative_scan(
            lambda l, r: (r[0] * l[0], r[0] * l[1] + r[1]), (a_bar, b_bar),
            axis=1)
        return acc_a[:, -1] * h0 + acc_b[:, -1]

    def chunk_body(h0, idx):
        sl = lambda t: jax.lax.dynamic_slice_in_dim(t, idx * ck, ck, axis=1)
        return advance(h0, sl(dt), sl(b_ssm), sl(xs_f)), None

    h = jnp.zeros((B, d_in, cfg.ssm_d_state), ACC)
    h, _ = jax.lax.scan(chunk_body, h, jnp.arange(nc))
    if L % ck:                                   # exact partial-chunk tail
        t0 = nc * ck
        h = advance(h, dt[:, t0:], b_ssm[:, t0:], xs_f[:, t0:])
    K = cfg.ssm_conv_width
    # conv tail: last K-1 pre-activation inputs (zero-extended left for
    # prompts shorter than the conv receptive field)
    xz = jnp.split(jnp.matmul(x, p["in_proj"],
                              preferred_element_type=ACC).astype(x.dtype), 2, -1)[0]
    conv = xz[:, -(K - 1):]
    if L < K - 1:
        conv = jnp.concatenate(
            [jnp.zeros((B, K - 1 - L, d_in), conv.dtype), conv], axis=1)
    return {"h": h, "conv": conv}


def _rwkv_state_after(p, x, cfg):
    """Final WKV state after consuming x; exact partial-chunk tail as in
    ``_mamba_state_after``."""
    B, L, d = x.shape
    hd = cfg.rwkv_head_dim
    H = d // hd
    r, k, v, g, logw, last = rwkv_lib._tmix_inputs(p, x, cfg)
    C = min(cfg.rwkv_chunk, L)
    nc = L // C                                  # full chunks

    def advance(S, kk, vk, lw):
        cum = jnp.cumsum(lw, axis=1)
        decay_all = jnp.exp(cum[:, -1])
        k_hat = kk * jnp.exp(cum[:, -1][:, None] - cum)
        return decay_all[..., None] * S + jnp.einsum("bjhd,bjhe->bhde",
                                                     k_hat, vk)

    def to_chunks(t):
        return t[:, :nc * C].reshape(B, nc, C, H, hd).swapaxes(0, 1)

    kc, vc, wc = map(to_chunks, (k, v, logw))

    def chunk_body(S, inp):
        return advance(S, *inp), None

    S0 = jnp.zeros((B, H, hd, hd), ACC)
    S, _ = jax.lax.scan(chunk_body, S0, (kc, vc, wc))
    if L % C:                                    # exact partial-chunk tail
        t0 = nc * C
        S = advance(S, k[:, t0:], v[:, t0:], logw[:, t0:])
    return {"S": S, "last_x": x[:, -1]}
