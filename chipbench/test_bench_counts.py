"""The operation and byte counters against counts made by hand at the
shapes of the two configurations as they run, and the family's counts
against the yardstick's."""
import json

import pytest

from chipbench import spec, weights
from chipbench.counts import collage_update, flash, model_flops

DENSE = spec.load_family("dense_gqa")


def dims(name):
    return DENSE.dims_of(json.loads(
        (spec.HERE / "configs" / f"{name}.json").read_text()))


GRANITE = dims("granite-3-2b")        # 8 layers, tied
INTERNLM2 = dims("internlm2-1.8b")    # 10 layers, untied


def test_granite_model_flops_by_hand():
    # per layer: wq, wo 2048x2048; wk, wv 2048x512; 3 x 2048x8192
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192
    assert per_layer == 60_817_408
    assert model_flops.matmul_params(GRANITE) == 8 * 60_817_408 + 2048 * 49155
    # causal attention at 4096: a token attends to 2048.5 keys on average
    attn = 8 * 4 * 32 * 64 * 2048.5
    fwd = 2 * 587_208_704 + attn
    assert model_flops.forward_per_token(GRANITE, 4096) == fwd
    assert model_flops.train_per_token(GRANITE, 4096) == 3_926_003_712


def test_internlm2_model_flops_by_hand():
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    assert per_layer == 62_914_560
    n = 10 * per_layer + 2048 * 92544          # the head; lookup is free
    assert model_flops.matmul_params(INTERNLM2) == n == 818_675_712
    attn = 10 * 4 * 16 * 128 * 1024.5
    assert model_flops.train_per_token(INTERNLM2, 2048) == 3 * (2 * n + attn)
    assert model_flops.train_per_token(INTERNLM2, 2048) == 5_163_835_392


def test_parameter_counts():
    norms = lambda d: d.layers * 2 * d.d + d.d
    granite, internlm2 = DENSE.shapes(GRANITE), DENSE.shapes(INTERNLM2)
    assert weights.n_params(granite) == 587_208_704 + norms(GRANITE)
    assert weights.n_params(internlm2) == (818_675_712 + 92544 * 2048
                                           + norms(INTERNLM2))
    assert weights.n_params(internlm2) == 1_008_248_832


@pytest.mark.parametrize("d,L", [(GRANITE, 4096), (INTERNLM2, 2048)])
def test_family_flops_are_the_yardsticks(d, L):
    assert (DENSE.train_flops_per_token(d, L)
            == model_flops.train_per_token(d, L))
    w = DENSE.flash_widths(d)
    assert w == flash.Widths(d.heads, d.kv_heads, d.head_dim, d.head_dim)


@pytest.mark.parametrize("d,L,rows,fwd", [
    # rows * heads * 4 * dh * L(L+1)/2
    (GRANITE, 4096, 3, 3 * 32 * 4 * 64 * 8_390_656),
    (INTERNLM2, 2048, 1, 16 * 4 * 128 * 2_098_176),
])
def test_flash_ops_by_hand(d, L, rows, fwd):
    assert flash.pairs(L) == L * (L + 1) // 2
    dh = d.head_dim
    assert flash.fwd_ops(rows, d.heads, dh, dh, L) == fwd
    # backward: 5 products of the triangle against the forward's 2
    assert flash.bwd_ops(rows, d.heads, dh, dh, L) == fwd * 5 // 2


def test_flash_bytes_by_hand():
    # granite, one row: q and o are 32 x 4096 x 64 bf16; k and v 8 heads
    q = 32 * 4096 * 64 * 2
    kv = 8 * 4096 * 64 * 2
    lse = 32 * 4096 * 4
    assert flash.fwd_bytes(1, 32, 8, 64, 64, 4096) == 2 * q + 2 * kv + lse
    assert (flash.bwd_bytes(1, 32, 8, 64, 64, 4096)
            == 4 * q + 4 * kv + 2 * lse)
    # compute-bound at both shapes on a v5e
    p = spec.peaks("TPU v5 lite")
    for d, L in ((GRANITE, 4096), (INTERNLM2, 2048)):
        dh = d.head_dim
        ops_t = flash.fwd_ops(1, d.heads, dh, dh, L) / p["bf16_flops_per_s"]
        b_t = (flash.fwd_bytes(1, d.heads, d.kv_heads, dh, dh, L)
               / p["hbm_bytes_per_s"])
        assert ops_t > b_t


# the counts as they were with one head width for Q, K and V
def _fwd_ops_one_width(rows, heads, dh, L):
    return rows * heads * 4 * dh * flash.pairs(L)


def _bwd_ops_one_width(rows, heads, dh, L):
    return rows * heads * 10 * dh * flash.pairs(L)


def _fwd_bytes_one_width(rows, heads, kv_heads, dh, L):
    q, kv = rows * heads * L * dh * 2, rows * kv_heads * L * dh * 2
    return 2 * q + 2 * kv + rows * heads * L * 4


def _bwd_bytes_one_width(rows, heads, kv_heads, dh, L):
    q, kv = rows * heads * L * dh * 2, rows * kv_heads * L * dh * 2
    return 4 * q + 4 * kv + 2 * rows * heads * L * 4


@pytest.mark.parametrize("rows,heads,kv_heads,dh,L", [
    (3, 32, 8, 64, 4096), (1, 16, 8, 128, 2048), (5, 32, 8, 64, 4096),
    (2, 4, 2, 16, 128), (1, 16, 16, 192, 8192)])
def test_flash_counts_at_one_width_are_the_old_ones(rows, heads, kv_heads,
                                                     dh, L):
    assert (flash.fwd_ops(rows, heads, dh, dh, L)
            == _fwd_ops_one_width(rows, heads, dh, L))
    assert (flash.bwd_ops(rows, heads, dh, dh, L)
            == _bwd_ops_one_width(rows, heads, dh, L))
    assert (flash.fwd_bytes(rows, heads, kv_heads, dh, dh, L)
            == _fwd_bytes_one_width(rows, heads, kv_heads, dh, L))
    assert (flash.bwd_bytes(rows, heads, kv_heads, dh, dh, L)
            == _bwd_bytes_one_width(rows, heads, kv_heads, dh, L))


def test_flash_counts_at_two_widths_by_hand():
    # MLA-style: QK 192 (128 + 64 rotary), V 128; 16 heads, one row of 8192
    P = 8192 * 8193 // 2
    assert flash.fwd_ops(1, 16, 192, 128, 8192) == 16 * P * (2 * 192
                                                             + 2 * 128)
    assert flash.bwd_ops(1, 16, 192, 128, 8192) == 16 * P * (6 * 192
                                                             + 4 * 128)
    L = 8192
    q, o, k, v = (16 * L * 192 * 2, 16 * L * 128 * 2, 16 * L * 192 * 2,
                  16 * L * 128 * 2)
    lse = 16 * L * 4
    assert flash.fwd_bytes(1, 16, 16, 192, 128, L) == q + o + k + v + lse
    # read q, o, do, k, v, lse, D; write dq, dk, dv
    assert (flash.bwd_bytes(1, 16, 16, 192, 128, L)
            == 2 * q + 2 * o + 2 * k + 2 * v + 2 * lse)


def test_collage_update_bytes_by_hand():
    # strategy C: theta, delta, m, v-hi, v-lo in bf16, read and written;
    # the bf16 gradient read: 22 bytes an element
    assert collage_update.bytes_moved(1, [2] * 5, 2) == 22
    # granite's one bucket, padded to whole (256, 128) blocks
    elems = -(-weights.n_params(DENSE.shapes(GRANITE)) // 32768) * 32768
    assert elems == 587_268_096
    assert collage_update.bytes_moved(elems, [2] * 5, 2) == 12_919_898_112
    # strategy D: bf16 theta, f32 m, v and master
    assert collage_update.bytes_moved(1, [2, 4, 4, 4], 2) == 30
