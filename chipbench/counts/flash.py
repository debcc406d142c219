"""Operations and bytes of one call of the flash attention kernels.

Counted over the unmasked causal triangle, P = L (L + 1) / 2 query-key
pairs per head, with two operations per multiply-add, for query/key width
dqk and value width dv:

* forward (``flash_fwd``): the scores Q Kᵀ and the weighted values P V,
  2 · (dqk + dv) per pair;
* backward (``flash_dq`` with ``flash_dkv``): the scores recomputed (dqk),
  dP = dO Vᵀ (dv), dV = Pᵀ dO (dv), dQ = dS K (dqk) and dK = dSᵀ Q (dqk),
  2 · (3 · dqk + 2 · dv) per pair: what the algorithm needs, however the
  kernels split it.

Bytes are the least each pass must move: its bf16 inputs and outputs
once, and the float32 row statistics (log-sum-exp, and D in the backward).
GQA heads share their key/value head, so K and V count per kv head."""
from __future__ import annotations

from typing import NamedTuple


class Widths(NamedTuple):
    """The attention widths a family gives the flash counts."""
    heads: int
    kv_heads: int
    qk_dim: int
    v_dim: int


def pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def fwd_ops(rows: int, heads: int, qk_dim: int, v_dim: int,
            seq_len: int) -> float:
    return rows * heads * 2 * (qk_dim + v_dim) * pairs(seq_len)


def bwd_ops(rows: int, heads: int, qk_dim: int, v_dim: int,
            seq_len: int) -> float:
    return rows * heads * 2 * (3 * qk_dim + 2 * v_dim) * pairs(seq_len)


def _bf16_per_row(heads, kv_heads, qk_dim, v_dim, seq_len):
    """(bf16 bytes of one row's q, o; of its k, v)."""
    return (heads * seq_len * (qk_dim + v_dim) * 2,
            kv_heads * seq_len * (qk_dim + v_dim) * 2)


def fwd_bytes(rows, heads, kv_heads, qk_dim, v_dim, seq_len) -> float:
    qo, kv = _bf16_per_row(heads, kv_heads, qk_dim, v_dim, seq_len)
    # read q, k, v; write o, lse
    return rows * (qo + kv + heads * seq_len * 4)


def bwd_bytes(rows, heads, kv_heads, qk_dim, v_dim, seq_len) -> float:
    qo, kv = _bf16_per_row(heads, kv_heads, qk_dim, v_dim, seq_len)
    # read q, o, do, k, v, lse, D; write dq, dk, dv
    return rows * (2 * qo + 2 * kv + 2 * heads * seq_len * 4)
