"""Architecture registry: ``--arch <id>`` resolution for every entrypoint."""
import dataclasses

from repro.configs import (codeqwen1_5_7b, gemma3_27b, gpt, granite_3_2b,
                           internlm2_1_8b, internvl2_1b,
                           jamba_1_5_large_398b, moonshot_v1_16b_a3b,
                           qwen3_moe_30b_a3b, rwkv6_1_6b, seamless_m4t_medium)
from repro.configs.base import SHAPES, Group, ModelConfig, RunConfig, ShapeConfig, Sub

ARCHS = {
    "seamless-m4t-medium": seamless_m4t_medium,
    "granite-3-2b": granite_3_2b,
    "internlm2-1.8b": internlm2_1_8b,
    "codeqwen1.5-7b": codeqwen1_5_7b,
    "gemma3-27b": gemma3_27b,
    "qwen3-moe-30b-a3b": qwen3_moe_30b_a3b,
    "moonshot-v1-16b-a3b": moonshot_v1_16b_a3b,
    "jamba-1.5-large-398b": jamba_1_5_large_398b,
    "internvl2-1b": internvl2_1b,
    "rwkv6-1.6b": rwkv6_1_6b,
    # the paper's own models
    "gpt-125m": gpt, "gpt-tiny": gpt,
}

ASSIGNED = [k for k in ARCHS if not k.startswith("gpt")]


def get_config(arch: str, smoke: bool = False):
    arch = arch.replace("_", "-")
    if arch.startswith("gpt"):
        if smoke:
            return gpt.SMOKE
        return {"gpt-tiny": gpt.GPT_TINY, "gpt-125m": gpt.GPT_125M,
                "gpt-1.3b": gpt.GPT_1_3B, "gpt-2.7b": gpt.GPT_2_7B,
                "gpt-6.7b": gpt.GPT_6_7B, "gpt-30b": gpt.GPT_30B}[arch]
    mod = ARCHS[arch]
    return mod.SMOKE if smoke else mod.CONFIG


def with_layers(cfg: ModelConfig, n_layers: int | None) -> ModelConfig:
    """Cut the decoder to ``n_layers`` (None keeps the config's depth) —
    the one-chip depth cut that keeps every published width. The count
    must be a whole number of layer periods (jamba's attention period,
    gemma's local:global period; 1 for a dense stack)."""
    if n_layers is None:
        return cfg
    period = cfg.attn_every if cfg.family == "hybrid" \
        else (cfg.local_global_period or 1)
    if n_layers <= 0 or n_layers % period:
        raise ValueError(f"--layers {n_layers}: {cfg.name} needs a positive "
                         f"multiple of its {period}-layer period")
    return dataclasses.replace(cfg, n_layers=n_layers)


__all__ = ["ARCHS", "ASSIGNED", "SHAPES", "get_config", "with_layers",
           "ModelConfig", "RunConfig", "ShapeConfig", "Group", "Sub"]
