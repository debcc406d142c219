"""Weights of a model, made on the device from the seed.

A family's table (``shapes(dims)`` of ``chipbench/families/<family>.py``:
name → (shape, standard deviation)) says what to make. One jitted call
makes every tensor, in the type it is trained in (the configuration's
``torch_dtype``), so the program under test and the plain reference start
from the same values: the reference widens them to float32 exactly. Each
tensor is normal with its standard deviation, from its own fold of the
key: its index among the sorted names; a standard deviation of 0 makes
zeros. The rounding to the trained type is explicit: inside a larger
jitted computation that widens the tensors again, XLA may drop a plain
narrowing conversion (its excess precision), and the values would then
not be the trained ones."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def generate(key, table: dict, dtype) -> dict:
    """Every tensor of ``table``."""
    return _generate(key, tuple(sorted(table.items())), dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _generate(key, items: tuple, dtype) -> dict:
    table = dict(items)
    return {name: tensor(key, table, name, dtype) for name in table}


def tensor(key, table: dict, name: str, dtype):
    """One tensor of ``table``. Made inside another computation, it takes
    the same values as in ``generate``."""
    shape, std = table[name]
    if std == 0.0:
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, sorted(table).index(name))
    x = jax.random.normal(k, shape, jnp.float32) * std
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(
        x, exponent_bits=fi.nexp, mantissa_bits=fi.nmant).astype(dtype)


def n_params(table: dict) -> int:
    return sum(math.prod(shape) for shape, _ in table.values())
