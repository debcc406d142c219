"""Faults planted under the timed path, to show that the output check
catches them (``test_bench_faults.py`` on the CPU, ``calibrate.py`` on the
chip). None of them runs in a benchmark run.

* ``unchanged``: the step returns its state as it got it.
* ``half_batch``: half of the batch is left out and the mean loss taken
  over the rest (the program leaves out labels below zero); with one row,
  half of its positions.
* ``exchange``: the gradient reduction between chips is left out: each
  chip keeps its own rows' gradient for its shard (set before the program
  is built, since it changes the compiled step)."""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp


def unchanged(prog):
    step = prog.step

    def broken(state, batch):
        copy = jax.tree_util.tree_map(jnp.copy, state)
        return state, step(copy, batch)[1]
    prog.step = broken


def drop_half(labels):
    B, L = labels.shape
    if B > 1:
        keep = (jnp.arange(B) < B // 2)[:, None]
    else:
        keep = (jnp.arange(L) < L // 2)[None, :]
    return jnp.where(keep, labels, -1)


def half_batch(prog):
    step = prog.step
    masked = jax.jit(drop_half)

    def broken(state, batch):
        return step(state, {**batch, "labels": masked(batch["labels"])})
    prog.step = broken


@contextlib.contextmanager
def exchange():
    """While open, ``jax.lax.psum_scatter`` keeps each device's own shard,
    scaled as a sum over the axis would be, and exchanges nothing."""
    real = jax.lax.psum_scatter

    def local(x, axis_name, *, scatter_dimension=0, tiled=False):
        n = jax.lax.psum(1, axis_name)
        i = jax.lax.axis_index(axis_name)
        size = x.shape[scatter_dimension] // n
        part = jax.lax.dynamic_slice_in_dim(x, i * size, size,
                                            scatter_dimension)
        if not tiled:
            part = jnp.squeeze(part, scatter_dimension)
        return part * n

    jax.lax.psum_scatter = local
    try:
        yield
    finally:
        jax.lax.psum_scatter = real
