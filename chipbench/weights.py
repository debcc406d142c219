"""Weights of a dense GQA decoder, made on the device from the seed.

One jitted call makes every tensor, in the type it is trained in (the
configuration's ``torch_dtype``), so the program under test and the plain
reference start from the same values: the reference widens them to
float32 exactly. The rounding to that type is explicit: inside a larger
jitted computation that widens the tensors again, XLA may drop a plain
narrowing conversion (its excess precision), and the values would then not
be the trained ones. Names and layouts are those of
``chipbench/reference/dense_gqa.py``; per-layer tensors are stacked on a
leading layer axis. Matrices are normal with standard deviation
fan_in^-1/2, the embedding and the untied head 0.02, and the RMSNorm
offsets zero."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference.dense_gqa import Dims


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


@functools.partial(jax.jit, static_argnums=(1, 2))
def generate(key, dm: Dims, dtype) -> dict:
    """Every tensor of ``shapes(dm)``."""
    return {name: tensor(key, dm, name, dtype) for name in shapes(dm)}


def tensor(key, dm: Dims, name: str, dtype):
    """One tensor of ``shapes(dm)``, from its own fold of ``key``: its
    index among the sorted names. Made inside another computation, it
    takes the same values as in ``generate``."""
    table = shapes(dm)
    shape, std = table[name]
    if std == 0.0:
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, sorted(table).index(name))
    x = jax.random.normal(k, shape, jnp.float32) * std
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(
        x, exponent_bits=fi.nexp, mantissa_bits=fi.nmant).astype(dtype)


def n_params(dm: Dims) -> int:
    return sum(math.prod(shape) for shape, _ in shapes(dm).values())
