"""Operations and bytes of one call of the flash attention kernels.

Counted over the unmasked causal triangle, P = L (L + 1) / 2 query-key
pairs per head, with two operations per multiply-add:

* forward (``flash_fwd``): the scores Q Kᵀ and the weighted values P V,
  4 · dh per pair;
* backward (``flash_dq`` with ``flash_dkv``): the scores recomputed, dP =
  dO Vᵀ, dV = Pᵀ dO, dQ = dS K and dK = dSᵀ Q, 10 · dh per pair: what the
  algorithm needs, however the kernels split it.

Bytes are the least each pass must move: its bf16 inputs and outputs
once, and the float32 row statistics (log-sum-exp, and D in the backward).
GQA heads share their key/value head, so K and V count per kv head."""
from __future__ import annotations


def pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def fwd_ops(rows: int, heads: int, head_dim: int, seq_len: int) -> float:
    return rows * heads * 4 * head_dim * pairs(seq_len)


def bwd_ops(rows: int, heads: int, head_dim: int, seq_len: int) -> float:
    return rows * heads * 10 * head_dim * pairs(seq_len)


def fwd_bytes(rows, heads, kv_heads, head_dim, seq_len) -> float:
    q = rows * heads * seq_len * head_dim * 2
    kv = rows * kv_heads * seq_len * head_dim * 2
    return 2 * q + 2 * kv + rows * heads * seq_len * 4   # q, o; k, v; lse


def bwd_bytes(rows, heads, kv_heads, head_dim, seq_len) -> float:
    q = rows * heads * seq_len * head_dim * 2
    kv = rows * kv_heads * seq_len * head_dim * 2
    # read q, o, do, k, v, lse, D; write dq, dk, dv
    return 4 * q + 4 * kv + 2 * rows * heads * seq_len * 4
