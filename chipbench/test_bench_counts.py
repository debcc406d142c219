"""The operation and byte counters against counts made by hand at the
shapes of the two configurations as they run."""
import json

import pytest

from chipbench import spec, weights
from chipbench.counts import collage_update, flash, model_flops
from chipbench.reference import dense_gqa


def dims(name):
    return dense_gqa.dims_of(json.loads(
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
    assert weights.n_params(GRANITE) == 587_208_704 + norms(GRANITE)
    assert weights.n_params(INTERNLM2) == (818_675_712 + 92544 * 2048
                                           + norms(INTERNLM2))
    assert weights.n_params(INTERNLM2) == 1_008_248_832


@pytest.mark.parametrize("d,L,rows,fwd", [
    # rows * heads * 4 * dh * L(L+1)/2
    (GRANITE, 4096, 3, 3 * 32 * 4 * 64 * 8_390_656),
    (INTERNLM2, 2048, 1, 16 * 4 * 128 * 2_098_176),
])
def test_flash_ops_by_hand(d, L, rows, fwd):
    assert flash.pairs(L) == L * (L + 1) // 2
    assert flash.fwd_ops(rows, d.heads, d.head_dim, L) == fwd
    # backward: 5 products of the triangle against the forward's 2
    assert flash.bwd_ops(rows, d.heads, d.head_dim, L) == fwd * 5 // 2


def test_flash_bytes_by_hand():
    # granite, one row: q and o are 32 x 4096 x 64 bf16; k and v 8 heads
    q = 32 * 4096 * 64 * 2
    kv = 8 * 4096 * 64 * 2
    lse = 32 * 4096 * 4
    assert flash.fwd_bytes(1, 32, 8, 64, 4096) == 2 * q + 2 * kv + lse
    assert flash.bwd_bytes(1, 32, 8, 64, 4096) == 4 * q + 4 * kv + 2 * lse
    # compute-bound at both shapes on a v5e
    p = spec.peaks("TPU v5 lite")
    for d, L in ((GRANITE, 4096), (INTERNLM2, 2048)):
        ops_t = flash.fwd_ops(1, d.heads, d.head_dim, L) / p["bf16_flops_per_s"]
        b_t = (flash.fwd_bytes(1, d.heads, d.kv_heads, d.head_dim, L)
               / p["hbm_bytes_per_s"])
        assert ops_t > b_t


def test_collage_update_bytes_by_hand():
    # strategy C: theta, delta, m, v-hi, v-lo in bf16, read and written;
    # the bf16 gradient read: 22 bytes an element
    assert collage_update.bytes_moved(1, [2] * 5, 2) == 22
    # granite's one bucket, padded to whole (256, 128) blocks
    elems = -(-weights.n_params(GRANITE) // 32768) * 32768
    assert elems == 587_268_096
    assert collage_update.bytes_moved(elems, [2] * 5, 2) == 12_919_898_112
    # strategy D: bf16 theta, f32 m, v and master
    assert collage_update.bytes_moved(1, [2, 4, 4, 4], 2) == 30
