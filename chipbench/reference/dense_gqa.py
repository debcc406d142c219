"""Plain reference for the dense GQA decoder: loss and gradients in float32
``jax.numpy`` under ``default_matmul_precision("highest")``, stepped by the
one AdamW reference of every family (``reference/adamw.py``).

The block is the published Llama-style decoder that granite-3.0 and
InternLM2 share: pre-RMSNorm, rotary embedding on half-split pairs,
grouped-query causal attention (query head j reads key/value head
j // (heads / kv_heads)), SwiGLU MLP, final RMSNorm, and a tied or untied
head; the loss is the mean next-token cross entropy over every row. RMSNorm
weights are stored as offsets from one (weight = 1 + s), so a zero tensor
is the identity scale.

It imports nothing of the system under test. To fit one chip at the timed
sizes it takes one row at a time, attends and takes the head's softmax in
blocks of positions, and recomputes each layer in the backward pass; where
the cell has several chips, each takes its share of the rows and the sums
are added. None of that changes the arithmetic beyond float32 summation
order.

Weights are a dict of arrays named as the dense family's table
(``chipbench/families/dense_gqa.py``) names them:
``embed`` (V, d), per-layer tensors stacked on a leading layer axis
(``attn_norm``, ``wq``, ``wk``, ``wv``, ``wo``, ``mlp_norm``, ``w_gate``,
``w_up``, ``w_down``), ``final_norm`` and, untied, ``lm_head`` (d, V)."""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from chipbench.reference import adamw

F32 = jnp.float32
Q_BLOCK = 512          # queries per attention block
HEAD_BLOCK = 512       # positions per block of the head and its softmax


class Dims(NamedTuple):
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    layers: int
    eps: float
    rope_theta: float
    tied: bool


def dims_of(config: dict) -> Dims:
    """The sizes of a configuration file (Hugging Face key names)."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    return Dims(d=d, heads=h, kv_heads=config["num_key_value_heads"],
                head_dim=config.get("head_dim") or d // h,
                ff=config["intermediate_size"], vocab=config["vocab_size"],
                layers=config["num_hidden_layers"],
                eps=float(config["rms_norm_eps"]),
                rope_theta=float(config["rope_theta"]),
                tied=bool(config["tie_word_embeddings"]))


def rms_norm(x, s, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + s)


def rope(x, theta):
    """x: (L, heads, dh) → rotated by position, pairs (i, i + dh/2)."""
    L, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = jnp.arange(L, dtype=F32)[:, None] * inv          # (L, dh/2)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(p, x, dm: Dims):
    """Causal GQA over one row x (L, d), computed in blocks of queries."""
    L = x.shape[0]
    g = dm.heads // dm.kv_heads
    q = rope((x @ p["wq"]).reshape(L, dm.heads, dm.head_dim), dm.rope_theta)
    k = rope((x @ p["wk"]).reshape(L, dm.kv_heads, dm.head_dim),
             dm.rope_theta)
    v = (x @ p["wv"]).reshape(L, dm.kv_heads, dm.head_dim)
    qb = min(Q_BLOCK, L)
    assert L % qb == 0, (L, qb)
    q = q.reshape(L // qb, qb, dm.kv_heads, g, dm.head_dim)
    scale = dm.head_dim ** -0.5

    @jax.checkpoint
    def block(args):
        i, qi = args
        s = jnp.einsum("qkgd,skd->kgqs", qi, k) * scale
        qpos = i * qb + jnp.arange(qb)[:, None]
        kpos = jnp.arange(L)[None, :]
        s = jnp.where(kpos > qpos, -jnp.inf, s)
        pr = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", pr, v).reshape(qb, -1)

    out = jax.lax.map(block, (jnp.arange(L // qb), q)).reshape(L, -1)
    return out @ p["wo"]


def layer(x, p, dm: Dims):
    x = x + attention(p, rms_norm(x, p["attn_norm"], dm.eps), dm)
    h = rms_norm(x, p["mlp_norm"], dm.eps)
    return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
              "w_up", "w_down")


def row_ce_sum(w, tokens, dm: Dims):
    """Summed next-token cross entropy of one row (L,) of token ids."""
    x = w["embed"][tokens]
    stack = {k: w[k] for k in LAYER_KEYS}

    @jax.checkpoint
    def body(x, p):
        return layer(x, p, dm), None

    x, _ = jax.lax.scan(body, x, stack)
    h = rms_norm(x, w["final_norm"], dm.eps)
    head = w["embed"].T if dm.tied else w["lm_head"]
    L = tokens.shape[0]
    hb = min(HEAD_BLOCK, L)
    assert L % hb == 0, (L, hb)
    # position t predicts token t + 1; the last position predicts nothing
    nxt = jnp.concatenate([tokens[1:], jnp.zeros((1,), tokens.dtype)])
    valid = (jnp.arange(L) < L - 1).astype(F32)

    @jax.checkpoint
    def block(args):
        hi, ti, vi = args
        logp = jax.nn.log_softmax(hi @ head, axis=-1)
        return -(jnp.take_along_axis(logp, ti[:, None], axis=-1)[:, 0]
                 * vi).sum()

    return jax.lax.map(block, (h.reshape(L // hb, hb, -1),
                               nxt.reshape(L // hb, hb),
                               valid.reshape(L // hb, hb))).sum()


def _rows_ce(w, tokens, dm: Dims):
    """Summed cross entropy of the local rows (b, L)."""
    return jax.lax.map(lambda t: row_ce_sum(w, t, dm), tokens).sum()


def _rows_ce_grad(w, tokens, dm: Dims):
    """(summed cross entropy, its gradient) of the local rows (b, L)."""
    vg = jax.value_and_grad(row_ce_sum)
    if tokens.shape[0] == 1:
        return vg(w, tokens[0], dm)

    def body(carry, t):
        tot, acc = carry
        l, g = vg(w, t, dm)
        return (tot + l, jax.tree_util.tree_map(jnp.add, acc, g)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, w)
    return jax.lax.scan(body, (jnp.zeros((), F32), zero), tokens)[0]


def _over_rows(fn, mesh):
    """``fn`` on each device's rows of the batch, summed over devices."""
    def body(w, tokens):
        return jax.lax.psum(fn(w, tokens), "rows")
    return jax.shard_map(body, mesh=mesh, in_specs=(P(), P("rows", None)),
                         out_specs=P(), check_vma=False)


@functools.partial(jax.jit, static_argnums=(2, 3))
def loss(w, tokens, dm: Dims, mesh: Mesh):
    """Mean next-token cross entropy of a batch (B, L) whose rows are
    spread over ``mesh``'s one axis, ``rows``."""
    with jax.default_matmul_precision("highest"):
        tot = _over_rows(lambda w, t: _rows_ce(w, t, dm), mesh)(w, tokens)
    return tot / (tokens.shape[0] * (tokens.shape[1] - 1))


@functools.partial(jax.jit, static_argnums=(2, 3))
def loss_and_grad(w, tokens, dm: Dims, mesh: Mesh):
    """(mean loss, its gradient) of a batch (B, L), one row at a time."""
    with jax.default_matmul_precision("highest"):
        tot, grad = _over_rows(lambda w, t: _rows_ce_grad(w, t, dm),
                               mesh)(w, tokens)
    n = tokens.shape[0] * (tokens.shape[1] - 1)
    return tot / n, jax.tree_util.tree_map(lambda g: g / n, grad)


def run(make_w0, batches, opt: adamw.AdamW, dm: Dims, mesh: Mesh) -> dict:
    """Two AdamW steps from the weights ``make_w0()`` on ``batches[0]`` and
    ``batches[1]`` and the loss on ``batches[2]``: the losses of the three
    steps, the first gradient's norm per tensor and the change after two
    steps. ``make_w0`` makes the weights anew when they are needed, so
    that they are not held while the gradients are."""
    theta = adamw.to_f32(make_w0())
    l1, g1 = loss_and_grad(theta, batches[0], dm, mesh)
    grad_norms = adamw.leaf_norms(g1)
    theta = adamw.step(theta, [g1], 1, opt)
    l2, g2 = loss_and_grad(theta, batches[1], dm, mesh)
    theta = adamw.step(theta, [g1, g2], 2, opt)
    del g1, g2
    l3 = loss(theta, batches[2], dm, mesh)
    return {"losses": [float(l1), float(l2), float(l3)],
            "grad_norms": grad_norms,
            "change_norms": adamw.change_norms(theta, make_w0())}
