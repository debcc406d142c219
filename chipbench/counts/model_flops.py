"""Model FLOPs of one trained token of a dense GQA decoder.

Forward: two operations per multiply-add of every matrix product (the
attention projections, the SwiGLU MLP and the head; the embedding lookup is
none), and the attention scores and weighted values of the causal triangle,
a query at position t attending to t + 1 keys. Training counts the forward
three times (forward, and a backward of twice its operations); recomputed
operations are not counted."""
from __future__ import annotations


def matmul_params(dm) -> int:
    q = dm.heads * dm.head_dim
    kv = dm.kv_heads * dm.head_dim
    per_layer = dm.d * q + 2 * dm.d * kv + q * dm.d + 3 * dm.d * dm.ff
    return dm.layers * per_layer + dm.d * dm.vocab


def forward_per_token(dm, seq_len: int) -> float:
    mean_keys = (seq_len + 1) / 2
    attn = dm.layers * 4 * dm.heads * dm.head_dim * mean_keys
    return 2 * matmul_params(dm) + attn


def train_per_token(dm, seq_len: int) -> float:
    return 3 * forward_per_token(dm, seq_len)
