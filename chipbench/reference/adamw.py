"""Plain reference of the optimizer, one for every model family: AdamW in
float32 ``jax.numpy`` over a dict of named tensors, and the per-tensor
norms the output check compares. It imports nothing of the system under
test."""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


class AdamW(NamedTuple):
    """Decoupled weight decay inside the update; linear warm-up, then
    cosine decay to ``min_ratio`` of the peak rate."""
    lr: float
    warmup: int
    total: int
    b1: float
    b2: float
    eps: float
    weight_decay: float
    min_ratio: float = 0.1

    def rate(self, t: int) -> float:
        if t < self.warmup:
            return self.lr * t / max(self.warmup, 1)
        prog = min(max((t - self.warmup) / max(self.total - self.warmup, 1),
                       0.0), 1.0)
        return self.lr * (self.min_ratio + (1 - self.min_ratio) * 0.5
                          * (1 + math.cos(math.pi * prog)))


@functools.partial(jax.jit, donate_argnums=(0,))
def _apply(theta, grads, lr, bc1, bc2, hp):
    """θ after one AdamW step whose moments come from ``grads``: a list of
    the steps' gradients, oldest first (the moments start at zero)."""
    b1, b2, eps, wd = hp

    def leaf(th, *gs):
        m = jnp.zeros_like(th)
        v = jnp.zeros_like(th)
        for g in gs:
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
        upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * th
        return th - lr * upd

    return jax.tree_util.tree_map(leaf, theta, *grads)


def step(theta, grads: list, t: int, opt: AdamW):
    """Step ``t`` (1-based) of AdamW given every gradient so far."""
    hp = (opt.b1, opt.b2, opt.eps, opt.weight_decay)
    return _apply(theta, grads, jnp.float32(opt.rate(t)),
                  jnp.float32(1 - opt.b1 ** t), jnp.float32(1 - opt.b2 ** t),
                  hp)


def leaf_norms(tree) -> dict:
    """float32 L2 norm of every named tensor."""
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(F32)))))
            for k, v in tree.items()}


@jax.jit
def _diff_norms(a, b):
    return {k: jnp.sqrt(jnp.sum(jnp.square(a[k].astype(F32)
                                           - b[k].astype(F32))))
            for k in a}


def change_norms(theta, theta0) -> dict:
    """Per tensor, the L2 norm of θ − θ₀."""
    return {k: float(v) for k, v in _diff_norms(theta, theta0).items()}


@jax.jit
def to_f32(w):
    return {k: v.astype(F32) for k, v in w.items()}
