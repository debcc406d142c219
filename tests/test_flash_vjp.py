"""Flash-attention training path: custom-VJP Pallas kernels vs the masked
oracle (kernels/flash_attention, models/attention.py dispatch).

  * kernel-level: forward AND ``jax.grad`` vs ``ref.attention_ref`` swept
    over causal × sliding-window × GQA × odd-L (block padding) in fp32
    (tight tolerance) and bf16; the bf16 kernels (bf16 MXU operands)
    against the same kernels on f32 up-casts of the same values;
  * structure: the edge-tile split of each program's loop against a brute
    force mask, and the dtypes of every product inside the kernels;
  * model-level: full train loss/grads and prefill with
    ``cfg.flash_min_len`` set ≡ the masked baseline, including the
    banded-local gemma3 pattern (windowed layers dispatch too);
  * engine-level: dp=8 sharded train step with flash enabled ≡ the
    single-device flash step (subprocess with 8 virtual host devices).
"""
import os
import subprocess
import sys
import textwrap

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data.synthetic import make_batch_fn
from repro.kernels.flash_attention.flash_attention import (
    _band_lo_block, _mha_bwd, _mha_fwd, _tile_ranges, flash_attention,
    flash_mha)
from repro.kernels.flash_attention.ref import attention_ref
from repro.models.model import build_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _qkv(key, B, H, Hkv, L, dh, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    mk = lambda k, h: (jax.random.normal(k, (B, h, L, dh), jnp.float32)
                       * 0.5).astype(dtype)
    return mk(ks[0], H), mk(ks[1], Hkv), mk(ks[2], Hkv)


# --------------------------------------------------------------------------
# kernel level
# --------------------------------------------------------------------------

SWEEP = [
    # L, H, Hkv, dh, causal, window   (odd L exercises the block padding)
    (128, 4, 4, 32, True, 0),
    (96, 4, 2, 16, True, 0),          # GQA + odd L
    (200, 4, 1, 32, True, 0),         # group 4, odd L
    (256, 2, 1, 64, True, 64),        # sliding window + GQA
    (200, 4, 2, 32, True, 48),        # window + GQA + odd L
    (64, 2, 2, 16, True, 16),         # window smaller than the block
    (128, 2, 1, 32, False, 0),        # non-causal (encoder-style)
    (100, 2, 2, 16, False, 0),        # non-causal + padding
    (192, 2, 1, 32, False, 48),       # non-causal + window (distinct
    #                                   loop-bound paths in all 3 kernels)
]


class TestFlashVJP:
    @pytest.mark.parametrize("L,H,Hkv,dh,causal,window", SWEEP)
    def test_fwd_and_grads_match_oracle_fp32(self, L, H, Hkv, dh, causal,
                                             window):
        B = 2
        q, k, v = _qkv(jax.random.PRNGKey(L + H + window), B, H, Hkv, L, dh)
        w = jax.random.normal(jax.random.PRNGKey(7), (B, H, L, dh))

        def f(q, k, v):
            return (flash_mha(q, k, v, causal=causal, window=window,
                              blk_q=64, blk_k=64, interpret=True) * w).sum()

        def r(q, k, v):
            return (attention_ref(q, k, v, causal=causal, window=window)
                    * w).sum()

        got = flash_mha(q, k, v, causal=causal, window=window,
                        blk_q=64, blk_k=64, interpret=True)
        want = attention_ref(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)
        gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, ("dq", "dk", "dv")):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
                err_msg=f"{name} (L={L}, H={H}/{Hkv}, causal={causal}, "
                        f"window={window})")

    @pytest.mark.parametrize("dtype", [jnp.bfloat16])
    def test_grads_bf16(self, dtype):
        q, k, v = _qkv(jax.random.PRNGKey(3), 2, 4, 2, 128, 32, dtype)

        def loss(fn):
            return lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum()

        gf = jax.grad(loss(lambda q, k, v: flash_mha(
            q, k, v, causal=True, blk_q=64, blk_k=64, interpret=True)),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(lambda q, k, v: attention_ref(
            q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=0.05, atol=0.05)

    @pytest.mark.parametrize("causal,window", [(True, 0), (False, 0),
                                               (True, 100)])
    def test_unrolled_tile_groups_match_oracle(self, causal, window):
        """Blocks of 16 over L 256: programs with up to 15 unmasked tiles
        run every group size of ``UNROLL`` and the single tiles after
        them, in all three kernels; fp32 against the oracle, forward and
        ``jax.grad``."""
        q, k, v = _qkv(jax.random.PRNGKey(21), 1, 2, 1, 256, 16)
        w = jax.random.normal(jax.random.PRNGKey(8), q.shape)
        kw = dict(causal=causal, window=window)
        f = lambda q, k, v: (flash_mha(q, k, v, blk_q=16, blk_k=16,
                                       interpret=True, **kw) * w).sum()
        r = lambda q, k, v: (attention_ref(q, k, v, **kw) * w).sum()
        np.testing.assert_allclose(
            np.asarray(flash_mha(q, k, v, blk_q=16, blk_k=16, interpret=True,
                                 **kw)),
            np.asarray(attention_ref(q, k, v, **kw)), rtol=2e-4, atol=2e-5)
        for a, b, name in zip(jax.grad(f, argnums=(0, 1, 2))(q, k, v),
                              jax.grad(r, argnums=(0, 1, 2))(q, k, v),
                              ("dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5, err_msg=name)

    def test_tiny_L_pads_to_one_block(self):
        """L far below the block size: zero-padding + valid-len mask."""
        q, k, v = _qkv(jax.random.PRNGKey(5), 1, 2, 2, 13, 16)
        got = flash_mha(q, k, v, causal=True, blk_q=128, blk_k=128,
                        interpret=True)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_padded_row_lse_parks_at_big(self):
        """Fully-masked (padded) rows must publish LSE = +1e30, so the
        backward recomputation exp(NEG_INF − lse) is exactly 0 — the
        invariant any future per-chunk LSE merge (sequence parallelism /
        HBM streaming) relies on. Guarding on l would NOT detect them:
        masked tiles contribute p = exp(NEG_INF − NEG_INF) = 1 to l."""
        L = 40                                  # pads to one 128 block
        q, k, v = _qkv(jax.random.PRNGKey(2), 1, 2, 2, L, 16)
        # causal + window: padded rows beyond L + window are fully masked
        _, (_, _, _, _, lse) = _mha_fwd(q, k, v, True, 8, 128, 128, True)
        lse = np.asarray(lse)
        assert (lse[:, :, :L] < 1e29).all()     # real rows: finite stats
        assert (lse[:, :, L + 8:] == 1e30).all(), lse[0, 0, L + 8:]

    def test_band_lo_block_floor_divide(self):
        """The sliding-window block skip: first visited key block must
        contain kpos = qpos_min − window + 1 — the old (qpos_min − window)
        floor-divide visited one extra fully-masked block at band edges,
        and a wrong-direction error would SKIP live keys."""
        blk_q = blk_k = 64
        for qi in range(8):
            for window in (1, 63, 64, 65, 128, 129):
                lo = int(_band_lo_block(jnp.int32(qi), blk_q, blk_k, window))
                first_valid = max(qi * blk_q - window + 1, 0)
                assert lo == first_valid // blk_k, (qi, window, lo)
                # no live key below the first visited block …
                assert first_valid >= lo * blk_k
                # … and the first visited block DOES hold a live key
                assert first_valid < (lo + 1) * blk_k

    def test_windowed_fwd_at_band_edge_blocks(self):
        """window aligned so the band edge lands exactly on a block
        boundary (the floor-divide edge the satellite fix targets)."""
        for window in (63, 64, 65):
            q, k, v = _qkv(jax.random.PRNGKey(window), 1, 2, 2, 256, 32)
            got = flash_mha(q, k, v, causal=True, window=window,
                            blk_q=64, blk_k=64, interpret=True)
            want = attention_ref(q, k, v, causal=True, window=window)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=2e-5, err_msg=str(window))

    def test_forward_only_wrapper(self):
        """The serving entry point (jitted, fwd-only) still matches."""
        q, k, v = _qkv(jax.random.PRNGKey(11), 1, 4, 2, 256, 32,
                       jnp.bfloat16)
        got = flash_attention(q, k, v, causal=True, interpret=True)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=0.05, atol=0.02)


BF16_CASES = [
    # L, H, Hkv, dh, window   (blocks of 64, causal)
    (256, 4, 1, 64, 0),               # GQA 4:1
    (256, 4, 2, 128, 0),              # GQA 2:1, head dim 128
    (256, 2, 1, 64, 64),              # band edge on a block boundary
    (256, 2, 2, 128, 65),             # band edge one past it
    (200, 4, 2, 64, 0),               # odd L: valid_len mask
    (200, 2, 1, 128, 48),             # odd L + window, head dim 128
]


def _assert_bf16_close(got, want, name):
    """This file's bf16 tolerance, with the absolute part scaled to the
    largest value (1% of it; the bf16 rounding of P and dS gives ≤ 0.45%
    over ``BF16_CASES``)."""
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), want, rtol=0.05,
        atol=0.01 * np.abs(want).max(), err_msg=name)


class TestBf16Operands:
    @pytest.mark.parametrize("L,H,Hkv,dh,window", BF16_CASES)
    def test_bf16_kernels_agree_with_f32_path(self, L, H, Hkv, dh, window):
        """The bf16 kernels feed the MXU bf16 tiles: QKᵀ and dO·Vᵀ exactly
        (bf16 × bf16 products are exact in f32), and the f32 side of P·V,
        Pᵀ·dO, dS·K and dSᵀ·Q cast to bf16 once — on the v5e Mosaic's
        default f32 contract is itself one bf16 pass (PERF.md), so this is
        a plain cast, not a three-part split. Against the same kernels on
        f32 up-casts of the same values (a true f32 product here on the
        CPU), O and the gradients agree to this file's bf16 tolerance,
        and LSE, which only QKᵀ feeds, to f32 accumulation order."""
        B, blk = 1, 64
        q, k, v = _qkv(jax.random.PRNGKey(L + dh + window), B, H, Hkv, L,
                       dh, jnp.bfloat16)
        do = (jax.random.normal(jax.random.PRNGKey(9), (B, H, L, dh))
              * 0.5).astype(jnp.bfloat16)
        up = lambda xs: tuple(x.astype(jnp.float32) for x in xs)

        o, vjp = jax.vjp(lambda q, k, v: flash_mha(
            q, k, v, causal=True, window=window, blk_q=blk, blk_k=blk,
            interpret=True), q, k, v)
        grads = vjp(do)
        o32, (*_, lse32) = _mha_fwd(*up((q, k, v)), True, window, blk, blk,
                                    True)
        _assert_bf16_close(o, o32, "o")
        _, res = _mha_fwd(q, k, v, True, window, blk, blk, True)
        lse = res[-1]
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse32),
                                   rtol=1e-6, atol=1e-6, err_msg="lse")
        # the backward on f32 up-casts of the same residuals (O, LSE) and dO
        grads32 = _mha_bwd(True, window, blk, blk, True, up(res[:4]) + (lse,),
                           do.astype(jnp.float32))
        for g, g32, name in zip(grads, grads32, ("dq", "dk", "dv")):
            assert g.dtype == jnp.bfloat16
            _assert_bf16_close(g, g32, name)

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    def test_kernel_products_take_input_dtype(self, dtype):
        """Every product inside the three kernels takes operands of the
        input dtype with an f32 result, and no kernel transposes a tile
        itself (the contraction's dimension numbers carry Kᵀ, Vᵀ, Pᵀ,
        dSᵀ). Fixed by shape and dtype at trace time: this is the bf16
        path's engagement check."""
        q, k, v = _qkv(jax.random.PRNGKey(0), 1, 4, 2, 256, 64, dtype)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: flash_mha(q, k, v, causal=True, window=64,
                                      interpret=False).astype(
                jnp.float32).sum(), argnums=(0, 1, 2)))(q, k, v)
        kernels = {}
        for eqn in _eqns(jaxpr.jaxpr):
            if eqn.primitive.name == "pallas_call":
                kernels[eqn.params["name"]] = list(_eqns(eqn.params["jaxpr"]))
        assert set(kernels) == {"flash_fwd", "flash_dq", "flash_dkv"}
        for name, eqns in kernels.items():
            dots = [e for e in eqns if e.primitive.name == "dot_general"]
            assert dots, name
            for e in dots:
                assert [x.aval.dtype for x in e.invars] == [dtype] * 2, \
                    (name, e)
                assert e.outvars[0].aval.dtype == jnp.float32, (name, e)
            assert not [e for e in eqns if e.primitive.name == "transpose"], \
                name


def _eqns(jaxpr):
    """Every equation of a jaxpr, into the bodies of loops and branches
    (not into nested kernels' own jaxprs)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _tile_split_cases():
    for L, blk_q, blk_k in [(256, 64, 64), (384, 128, 64), (384, 64, 128),
                            (512, 128, 128)]:
        for causal in (True, False):
            for window in (0, 1, 16, 63, 64, 65, 100, 128, 129, 200):
                for valid_len in (0, L - 64, L - 13, L - 100):
                    yield L, blk_q, blk_k, causal, window, valid_len


class TestTileSplit:
    def test_edge_tiles_hold_every_masked_pair(self):
        """Brute force: for every program of every kernel direction, no tile
        of the unmasked range holds a masked pair, every tile holding one
        is in a masked range, and every tile holding a live pair is
        visited. Only the masked ranges build the iota/compare/select."""
        for L, blk_q, blk_k, causal, window, valid_len in _tile_split_cases():
            qpos = np.arange(L)[:, None]
            kpos = np.arange(L)[None, :]
            bad = np.zeros((L, L), bool)
            if causal:
                bad |= kpos > qpos
            if window:
                bad |= kpos <= qpos - window
            if valid_len:
                bad |= kpos >= valid_len
            for over_keys, n_prog, n_tile in (
                    (True, L // blk_q, L // blk_k),
                    (False, L // blk_k, L // blk_q)):
                for i in range(n_prog):
                    lo, a, b, hi = (int(x) for x in _tile_ranges(
                        i, over_keys=over_keys, blk_q=blk_q, blk_k=blk_k,
                        seq_len=L, causal=causal, window=window,
                        valid_len=valid_len))
                    case = (L, blk_q, blk_k, causal, window, valid_len,
                            over_keys, i, (lo, a, b, hi))
                    assert 0 <= lo <= a <= b <= hi <= n_tile, case
                    for j in range(n_tile):
                        qi, kj = (i, j) if over_keys else (j, i)
                        tile = bad[qi * blk_q:(qi + 1) * blk_q,
                                   kj * blk_k:(kj + 1) * blk_k]
                        if a <= j < b:
                            assert not tile.any(), (case, j)
                        if not lo <= j < hi:
                            assert tile.all(), (case, j)

    @pytest.mark.parametrize("L,share", [(4096, (32, 528)),
                                         (2048, (16, 136))])
    def test_masked_tile_share_of_the_cells(self, L, share):
        """granite (L 4096) and internlm2 (L 2048), causal, blocks of 128:
        only the diagonal tile of each program builds the mask, in the
        forward and dQ (over key blocks) and in dK/dV (over query
        blocks)."""
        for over_keys in (True, False):
            masked = visited = 0
            for i in range(L // 128):
                lo, a, b, hi = (int(x) for x in _tile_ranges(
                    i, over_keys=over_keys, blk_q=128, blk_k=128, seq_len=L,
                    causal=True, window=0, valid_len=0))
                masked += (a - lo) + (hi - b)
                visited += hi - lo
            assert (masked, visited) == share, over_keys


# --------------------------------------------------------------------------
# model level
# --------------------------------------------------------------------------

def _models(arch: str, f32: bool = True):
    cfg = get_config(arch, smoke=True)
    if f32:
        cfg = dataclasses.replace(cfg, dtype="float32")
    masked = build_model(cfg)
    flash = build_model(dataclasses.replace(cfg, flash_min_len=16,
                                            flash_block=32))
    return masked, flash


class TestModelDispatch:
    @pytest.mark.parametrize("arch", ["gpt-tiny", "gemma3-27b",
                                      "granite-3-2b"])
    def test_train_loss_and_grads_match_masked(self, arch):
        """cfg.flash_min_len dispatch ≡ masked baseline: loss and every
        parameter gradient (fp32 model, fp32 tolerance). gemma3 covers the
        banded-local pattern — windowed layers dispatch to flash too."""
        masked, flash = _models(arch)
        L = 48                                   # odd vs flash_block=32
        batch = make_batch_fn(masked.cfg, ShapeConfig("t", L, 2, "train"))(0)
        params = masked.init(jax.random.PRNGKey(0))
        (l0, _), g0 = jax.value_and_grad(
            lambda p: masked.loss(p, batch), has_aux=True)(params)
        (l1, _), g1 = jax.value_and_grad(
            lambda p: flash.loss(p, batch), has_aux=True)(params)
        assert abs(float(l0) - float(l1)) < 1e-5, (arch, float(l0), float(l1))
        for (path, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(g0),
                jax.tree_util.tree_leaves_with_path(g1)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5,
                err_msg=f"{arch}{jax.tree_util.keystr(path)}")

    def test_prefill_matches_masked(self):
        """Prefill (serve path) logits + KV caches under flash dispatch."""
        masked, flash = _models("gpt-tiny")
        batch = {"tokens": make_batch_fn(
            masked.cfg, ShapeConfig("t", 40, 2, "train"))(0)["tokens"]}
        params = masked.init(jax.random.PRNGKey(1))
        lg0, st0 = masked.prefill(params, batch, 64)
        lg1, st1 = flash.prefill(params, batch, 64)
        np.testing.assert_allclose(np.asarray(lg0), np.asarray(lg1),
                                   rtol=1e-4, atol=1e-4)
        for a, b in zip(jax.tree_util.tree_leaves(st0.layers),
                        jax.tree_util.tree_leaves(st1.layers)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=1e-4, atol=1e-4)

    def test_short_sequences_keep_masked_path(self):
        """Below flash_min_len the dispatch must NOT change the program —
        bit-identical logits to the masked model."""
        cfg = dataclasses.replace(get_config("gpt-tiny", smoke=True),
                                  flash_min_len=64)
        masked = build_model(dataclasses.replace(cfg, flash_min_len=0))
        gated = build_model(cfg)
        batch = make_batch_fn(cfg, ShapeConfig("t", 32, 2, "train"))(0)
        params = masked.init(jax.random.PRNGKey(0))
        a, _ = masked.forward(params, batch)
        b, _ = gated.forward(params, batch)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# engine level (dp=8 shard_map, subprocess for the virtual device count)
# --------------------------------------------------------------------------

class TestShardedFlash:
    def test_dp8_sharded_step_matches_single_device(self):
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        code = textwrap.dedent("""
            import dataclasses
            import jax, numpy as np
            from repro.configs import get_config
            from repro.configs.base import ShapeConfig
            from repro.core.collage import CollageAdamW
            from repro.core.precision import PrecisionPolicy, Strategy
            from repro.data.synthetic import make_batch_fn
            from repro.models.model import build_model
            from repro.train import sharded, train_loop

            mesh = jax.make_mesh((8,), ("data",))
            cfg = dataclasses.replace(get_config("gpt-tiny", smoke=True),
                                      dtype="float32", flash_block=32)
            model = build_model(cfg)
            batch_fn = make_batch_fn(cfg, ShapeConfig("t", 48, 16, "train"))
            opt = CollageAdamW(1e-3, b2=0.95, policy=PrecisionPolicy(
                strategy=Strategy.C_COLLAGE_PLUS))
            # flash_min_len threads through BOTH step builders
            ref_step = jax.jit(train_loop.make_train_step(
                model, opt, flash_min_len=16))
            step = sharded.make_sharded_train_step(
                model, opt, mesh, flash_min_len=16)
            s = train_loop.init_state(model, opt, jax.random.PRNGKey(0))
            sd = sharded.device_put_state(
                sharded.init_state(model, opt, jax.random.PRNGKey(0), mesh),
                mesh)
            for i in range(2):
                s, mref = ref_step(s, batch_fn(i))
                sd, m = step(sd, batch_fn(i))
                assert abs(float(mref["loss"]) - float(m["loss"])) < 1e-4, \\
                    (i, float(mref["loss"]), float(m["loss"]))
            a = np.concatenate([np.asarray(x, np.float32).ravel()
                                for x in jax.tree_util.tree_leaves(s.params)])
            b = np.concatenate([np.asarray(x, np.float32).ravel()
                                for x in jax.tree_util.tree_leaves(sd.params)])
            assert np.abs(a - b).max() < 5e-4, np.abs(a - b).max()
            print("FLASH_DP8_OK")
        """)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, env=env,
                             timeout=600)
        assert out.returncode == 0, \
            f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
        assert "FLASH_DP8_OK" in out.stdout
