"""The dense GQA decoder family (granite-3.0, InternLM2): what the harness
needs to know of it, found by the configuration's ``"family"``.

Its tensors are named and laid out as the plain reference
(``reference/dense_gqa.py``) takes them: per-layer tensors stacked on a
leading layer axis. The program holds the same tensors as one scanned
group of layers, ``sub0`` the attention and ``sub1`` the MLP, each with its
RMSNorm. Matrices are normal with standard deviation fan_in^-1/2, the
embedding and the untied head 0.02, and the RMSNorm offsets zero."""
from __future__ import annotations

from chipbench.counts import flash, model_flops
from chipbench.reference.dense_gqa import Dims, dims_of, run  # noqa: F401

ATTN = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_down")

# the program's --smoke widths, for the CPU tests
SMOKE = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "intermediate_size": 128,
         "vocab_size": 256, "num_hidden_layers": 2}


def shapes(dm: Dims) -> dict:
    """name → (shape, standard deviation; 0 for the zero-initialised)."""
    d, h, hk, dh, f, V, n = (dm.d, dm.heads, dm.kv_heads, dm.head_dim,
                             dm.ff, dm.vocab, dm.layers)
    out = {
        "embed": ((V, d), 0.02),
        "attn_norm": ((n, d), 0.0),
        "wq": ((n, d, h * dh), d ** -0.5),
        "wk": ((n, d, hk * dh), d ** -0.5),
        "wv": ((n, d, hk * dh), d ** -0.5),
        "wo": ((n, h * dh, d), (h * dh) ** -0.5),
        "mlp_norm": ((n, d), 0.0),
        "w_gate": ((n, d, f), d ** -0.5),
        "w_up": ((n, d, f), d ** -0.5),
        "w_down": ((n, f, d), f ** -0.5),
        "final_norm": ((d,), 0.0),
    }
    if not dm.tied:
        out["lm_head"] = ((d, V), 0.02)
    return out


def to_program(w: dict) -> dict:
    """Named tensors → the program's parameter tree (``Model.init``)."""
    layer = {"sub0": {"norm": w["attn_norm"], **{k: w[k] for k in ATTN}},
             "sub1": {"norm": w["mlp_norm"], **{k: w[k] for k in MLP}}}
    p = {"embed": w["embed"],
         "decoder": {"groups": [layer], "final_norm": w["final_norm"]}}
    if "lm_head" in w:
        p["lm_head"] = w["lm_head"]
    return p


def from_program(p: dict) -> dict:
    (layer,) = p["decoder"]["groups"]
    w = {"embed": p["embed"], "final_norm": p["decoder"]["final_norm"],
         "attn_norm": layer["sub0"]["norm"], "mlp_norm": layer["sub1"]["norm"],
         **{k: layer["sub0"][k] for k in ATTN},
         **{k: layer["sub1"][k] for k in MLP}}
    if "lm_head" in p:
        w["lm_head"] = p["lm_head"]
    return w


def check_widths(cfg, opt, dm: Dims, traffic: dict) -> dict:
    """{quantity: (program's, files')} where the program's config and
    optimizer differ from the configuration and traffic files."""
    pairs = {
        "hidden_size": (cfg.d_model, dm.d),
        "num_attention_heads": (cfg.n_heads, dm.heads),
        "num_key_value_heads": (cfg.n_kv_heads, dm.kv_heads),
        "head_dim": (cfg.head_dim_, dm.head_dim),
        "intermediate_size": (cfg.d_ff, dm.ff),
        "vocab_size": (cfg.vocab_size, dm.vocab),
        "num_hidden_layers": (cfg.n_layers, dm.layers),
        "tie_word_embeddings": (cfg.tie_embeddings, dm.tied),
        "rope_theta": (cfg.rope_theta, dm.rope_theta),
        "rms_norm_eps": (cfg.norm_eps, dm.eps),
        "b1": (opt.b1, traffic["b1"]),
        "eps": (opt.eps, traffic["eps"]),
    }
    return {k: v for k, v in pairs.items() if v[0] != v[1]}


def train_flops_per_token(dm: Dims, seq_len: int) -> float:
    return model_flops.train_per_token(dm, seq_len)


def flash_widths(dm: Dims) -> flash.Widths:
    return flash.Widths(dm.heads, dm.kv_heads, dm.head_dim, dm.head_dim)
