"""Pallas kernel validation (interpret=True on CPU) vs pure-jnp oracles,
swept over shapes / dtypes / strategies / block sizes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.collage import CollageAdamW
from repro.core.precision import PrecisionPolicy, Strategy
from repro.kernels.collage_update.collage_update import collage_update
from repro.kernels.collage_update.ref import collage_update_ref
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


def _flat(key, n, scale=1.0, dtype=jnp.bfloat16):
    return (jax.random.normal(key, (n,), jnp.float32) * scale).astype(dtype)


class TestCollageUpdateKernel:
    @pytest.mark.parametrize("n", [128, 1024, 8192, 128 * 513])
    @pytest.mark.parametrize("strategy", ["A", "B", "C"])
    def test_matches_ref(self, n, strategy):
        ks = jax.random.split(jax.random.PRNGKey(n + len(strategy)), 6)
        g = _flat(ks[0], n, 1e-2)
        theta = _flat(ks[1], n, 100.0)
        delta = _flat(ks[2], n, 1e-3)
        m = _flat(ks[3], n, 1e-2)
        vhi = jnp.abs(_flat(ks[4], n, 1e-3))
        vlo = _flat(ks[5], n, 1e-6)
        args = (g, theta, delta, m, vhi, vlo,
                jnp.float32(1e-3), jnp.float32(0.1), jnp.float32(0.05))
        kw = dict(b1=0.9, b2=0.999, eps=1e-8, wd=0.1, strategy=strategy)
        outs_k = collage_update(*args, **kw, interpret=True)
        outs_r = collage_update_ref(*args, **kw)
        for got, want, name in zip(outs_k, outs_r,
                                   ["theta", "delta", "m", "vhi", "vlo"]):
            np.testing.assert_array_equal(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                err_msg=f"{strategy}/{name} (n={n})")

    def test_rn_bit_trick_equals_reduce_precision(self):
        """The kernel's integer-op bf16 rounding (Mosaic has no
        reduce_precision) ≡ lax.reduce_precision(x, 8, 7) on every f32
        class: random bit patterns, ties, subnormals, overflow to inf."""
        from repro.kernels.collage_update.collage_update import _rn
        bits = jax.random.bits(jax.random.PRNGKey(0), (1 << 20,),
                               jnp.uint32)
        edge = jnp.array([0, 0x80000000, 0x00008000, 0x00018000, 0x3F808000,
                          0x3F818000, 0x7F7FFFFF, 0x7F800000, 0xFF800000,
                          0x00000001, 0x807FFFFF, 0x7F7F8000], jnp.uint32)
        x = jax.lax.bitcast_convert_type(jnp.concatenate([bits, edge]),
                                         jnp.float32)
        got = jax.jit(_rn)(x)
        want = jax.jit(lambda v: jax.lax.reduce_precision(v, 8, 7))(x)
        nan = np.isnan(np.asarray(want))
        np.testing.assert_array_equal(np.isnan(np.asarray(got)), nan)
        np.testing.assert_array_equal(
            np.asarray(jax.lax.bitcast_convert_type(got, jnp.uint32))[~nan],
            np.asarray(jax.lax.bitcast_convert_type(want, jnp.uint32))[~nan])

    @pytest.mark.parametrize("block_rows", [8, 64, 256])
    def test_block_shape_sweep(self, block_rows):
        n = 4096
        ks = jax.random.split(jax.random.PRNGKey(7), 6)
        args = (_flat(ks[0], n, 1e-2), _flat(ks[1], n, 10.0),
                _flat(ks[2], n, 1e-4), _flat(ks[3], n, 1e-2),
                jnp.abs(_flat(ks[4], n, 1e-3)), _flat(ks[5], n, 1e-6),
                jnp.float32(1e-3), jnp.float32(0.1), jnp.float32(0.05))
        base = collage_update(*args, strategy="C", interpret=True)
        got = collage_update(*args, strategy="C", interpret=True,
                             block_rows=block_rows)
        for b, g in zip(base, got):
            np.testing.assert_array_equal(np.asarray(b, np.float32),
                                          np.asarray(g, np.float32))

    def test_fused_step_matches_unfused_optimizer(self):
        """End-to-end: CollageAdamW(use_fused_kernel=True) ≡ library path."""
        params = {"a": _flat(jax.random.PRNGKey(0), 1000, 50.0),
                  "b": _flat(jax.random.PRNGKey(1), 300, 5.0).reshape(30, 10)}
        grads = {"a": _flat(jax.random.PRNGKey(2), 1000, 1e-2),
                 "b": _flat(jax.random.PRNGKey(3), 300, 1e-2).reshape(30, 10)}
        for strat in (Strategy.B_COLLAGE_LIGHT, Strategy.C_COLLAGE_PLUS):
            pol = PrecisionPolicy(strategy=strat)
            ref_opt = CollageAdamW(1e-3, b2=0.999, weight_decay=0.1, policy=pol)
            fus_opt = CollageAdamW(1e-3, b2=0.999, weight_decay=0.1, policy=pol,
                                   use_fused_kernel=True)
            state_r = ref_opt.init(params)
            state_f = fus_opt.init(params)
            pr, pf = params, params
            for g in [grads, grads]:
                pr, state_r, _ = ref_opt.step(g, pr, state_r)
                pf, state_f, _ = fus_opt.step(g, pf, state_f)
            for k in params:
                np.testing.assert_array_equal(
                    np.asarray(pr[k], np.float32), np.asarray(pf[k], np.float32),
                    err_msg=f"{strat}/{k}")
                np.testing.assert_array_equal(
                    np.asarray(state_r.delta[k], np.float32),
                    np.asarray(state_f.delta[k], np.float32))


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("L,dh,H,Hkv", [(256, 64, 4, 4), (256, 64, 4, 2),
                                            (512, 128, 2, 1), (256, 32, 8, 2)])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    def test_causal_matches_ref(self, L, dh, H, Hkv, dtype):
        ks = jax.random.split(jax.random.PRNGKey(L + dh), 3)
        q = (jax.random.normal(ks[0], (2, H, L, dh), jnp.float32) * 0.5).astype(dtype)
        k = (jax.random.normal(ks[1], (2, Hkv, L, dh), jnp.float32) * 0.5).astype(dtype)
        v = (jax.random.normal(ks[2], (2, Hkv, L, dh), jnp.float32) * 0.5).astype(dtype)
        got = flash_attention(q, k, v, causal=True, interpret=True)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=0.05, atol=0.02)

    @pytest.mark.parametrize("window", [64, 128])
    def test_windowed(self, window):
        ks = jax.random.split(jax.random.PRNGKey(9), 3)
        q = (jax.random.normal(ks[0], (1, 2, 256, 64), jnp.float32) * 0.5
             ).astype(jnp.bfloat16)
        k = (jax.random.normal(ks[1], (1, 2, 256, 64), jnp.float32) * 0.5
             ).astype(jnp.bfloat16)
        v = (jax.random.normal(ks[2], (1, 2, 256, 64), jnp.float32) * 0.5
             ).astype(jnp.bfloat16)
        got = flash_attention(q, k, v, causal=True, window=window,
                              interpret=True, blk_q=64, blk_k=64)
        want = attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=0.05, atol=0.02)

    @pytest.mark.parametrize("blk", [64, 128, 256])
    def test_block_sweep(self, blk):
        ks = jax.random.split(jax.random.PRNGKey(11), 3)
        q = (jax.random.normal(ks[0], (1, 2, 256, 64), jnp.float32)).astype(jnp.bfloat16)
        k = (jax.random.normal(ks[1], (1, 2, 256, 64), jnp.float32)).astype(jnp.bfloat16)
        v = (jax.random.normal(ks[2], (1, 2, 256, 64), jnp.float32)).astype(jnp.bfloat16)
        got = flash_attention(q, k, v, causal=True, blk_q=blk, blk_k=blk,
                              interpret=True)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=0.05, atol=0.02)
