"""Token batches for a training cell, made on the device from the seed.

A counter-based Zipf–Markov stream, the generator of the repository's
``data/synthetic.py`` kept here so that the yardstick's data cannot change
with the program: each of ``n_states`` states (hashed from the last two
tokens) draws the next token from its own ``top`` candidates of the
vocabulary with Zipf(``zipf_a``) probabilities. The traffic file gives the
parameters; every seed gets the same sizes, and every row of every batch
its own key, so rows differ."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7))
def batches(key, vocab: int, rows: int, seq_len: int, count: int,
            n_states: int, top: int, zipf_a: float) -> tuple:
    """``count`` batches of (rows, seq_len) int32 token ids."""
    k_tab, k_rows = jax.random.split(key)
    cand = jax.vmap(lambda k: jax.random.permutation(k, vocab)[:top])(
        jax.random.split(k_tab, n_states)).astype(jnp.int32)
    ranks = jnp.arange(1, top + 1, dtype=jnp.float32) ** (-zipf_a)
    cum = jnp.cumsum(ranks / ranks.sum())

    def row(k):
        k0, k1, k2 = jax.random.split(k, 3)

        def body(carry, u):
            s1, s2 = carry
            state = (s1 * 31 + s2) % n_states
            idx = jnp.minimum(jnp.searchsorted(cum, u), top - 1)
            tok = cand[state, idx]
            return (s2, tok % n_states), tok

        init = (jax.random.randint(k0, (), 0, n_states),
                jax.random.randint(k1, (), 0, n_states))
        return jax.lax.scan(body, init,
                            jax.random.uniform(k2, (seq_len,)))[1]

    toks = jax.vmap(row)(jax.random.split(k_rows, count * rows))
    return tuple(toks.reshape(count, rows, seq_len))


def for_traffic(key, vocab: int, traffic: dict, count: int) -> tuple:
    c = traffic["corpus"]
    return batches(key, vocab, traffic["rows_per_chip"] * traffic["dp"],
                   traffic["seq_len"], count, c["n_states"], c["top"],
                   float(c["zipf_a"]))
