"""Precision strategies (Paper Table 2) as first-class, selectable policy.

Every training entrypoint takes ``--precision {A,B,C,D,D-MW,KAHAN,SR}``.
Bytes/parameter accounting mirrors Paper Table 2 / Fig. 1 (right) and is
measured (not assumed) in benchmarks/table2_memory.py.
"""
from __future__ import annotations

import dataclasses
import enum

import jax.numpy as jnp


class Strategy(str, enum.Enum):
    """Precision strategy options, Paper §5 (+ App. B baselines)."""

    A_BF16 = "A"              # plain bf16 AdamW (option A)
    B_COLLAGE_LIGHT = "B"     # + MCF expansion on params          (ours)
    C_COLLAGE_PLUS = "C"      # + MCF expansion on v and beta2     (ours)
    D_MINUS_MW = "D-MW"       # fp32 optim states, no master weights
    D_MIXED_MW = "D"          # fp32 optim states + fp32 master weights (SOTA baseline)
    KAHAN = "KAHAN"           # Kahan-compensated bf16 (Zamirai et al. 2020)
    SR = "SR"                 # stochastic-rounding bf16 (App. B)

    @property
    def uses_expansion_params(self) -> bool:
        return self in (Strategy.B_COLLAGE_LIGHT, Strategy.C_COLLAGE_PLUS)

    @property
    def uses_expansion_second_moment(self) -> bool:
        return self is Strategy.C_COLLAGE_PLUS

    @property
    def optim_dtype(self):
        if self in (Strategy.D_MINUS_MW, Strategy.D_MIXED_MW):
            return jnp.float32
        return None  # component dtype of the policy

    @property
    def uses_master_weights(self) -> bool:
        return self is Strategy.D_MIXED_MW


# Paper Table 2: state bytes per parameter (param+grad, optim states, MCF/MW).
BYTES_PER_PARAM = {
    Strategy.A_BF16: 8,            # 2θ+2g + 2m+2v
    Strategy.B_COLLAGE_LIGHT: 10,  # + 2δθ
    Strategy.C_COLLAGE_PLUS: 12,   # + 2δθ + 2δv
    Strategy.D_MINUS_MW: 12,       # 2θ+2g + 4m+4v
    Strategy.D_MIXED_MW: 16,       # + 4 master
    Strategy.KAHAN: 10,            # + 2c (same as light — App. D equivalence)
    Strategy.SR: 8,
}


@dataclasses.dataclass(frozen=True)
class BucketPolicy:
    """Knobs for the bucketed multi-tensor engine (DESIGN.md §5).

    ``enabled``: keep params + ALL optimizer state as persistent flat
    buckets (core.bucketing) so the step is one fused launch per bucket.
    ``max_bucket_elems``: split buckets above this element count — bounds
    per-launch VMEM working set and gives the scheduler parallelism; None
    means one bucket per dtype.
    ``pad_multiple``: flat-axis padding granularity; must be a multiple of
    128 (VPU lanes). The default is one full (256, 128) fused-kernel block
    (``bucketing.BLOCK_PAD``). Shard-aware callers pass
    distributed.sharding.bucket_pad_multiple so buckets divide the FSDP
    axis exactly.
    """

    enabled: bool = False
    max_bucket_elems: int | None = None
    pad_multiple: int = 256 * 128     # bucketing.BLOCK_PAD


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """End-to-end numeric policy for a training/serving run."""

    strategy: Strategy = Strategy.C_COLLAGE_PLUS
    param_dtype: jnp.dtype = jnp.bfloat16      # stored params / grads / acts
    accum_dtype: jnp.dtype = jnp.float32       # GEMM accumulation (MXU native)
    softmax_dtype: jnp.dtype = jnp.float32     # attention softmax / norms
    # weight-decay placement: "fused" = inside the summed update (Alg. 2 l.12,
    # the Collage-correct choice); "pytorch" = separate (1-αλ)θ step (App. D
    # Eq. 4 — demonstrably lost arithmetic in bf16, kept for ablation).
    wd_mode: str = "fused"
    # bucketed multi-tensor engine layout knobs (core.bucketing)
    bucketing: BucketPolicy = BucketPolicy()

    @property
    def bytes_per_param(self) -> int:
        return BYTES_PER_PARAM[self.strategy]


def parse_strategy(name: str) -> Strategy:
    name = name.upper().replace("_", "-")
    aliases = {"D-MW": Strategy.D_MINUS_MW, "DMW": Strategy.D_MINUS_MW,
               "LIGHT": Strategy.B_COLLAGE_LIGHT, "PLUS": Strategy.C_COLLAGE_PLUS}
    if name in aliases:
        return aliases[name]
    return Strategy(name)
