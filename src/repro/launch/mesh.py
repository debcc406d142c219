"""Production mesh construction (single-pod 16×16 = 256 chips; multi-pod
2×16×16 = 512 chips). A FUNCTION, not a module constant — importing this
module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``. Since JAX 0.9 the default
    is ``Explicit`` axes, which the shard_map engine and the GSPMD paths
    (``.at[]`` gathers, un-meshed jit) do not use. Every mesh the program
    builds goes through here."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_mesh(dp: int, tp: int, pods: int = 1):
    """Arbitrary small meshes (tests / examples)."""
    if pods > 1:
        return auto_mesh((pods, dp, tp), ("pod", "data", "model"))
    return auto_mesh((dp, tp), ("data", "model"))


# TPU v5e-like hardware model for the roofline (§Roofline constants).
HW = {
    "peak_flops_bf16": 197e12,     # per chip
    "hbm_bw": 819e9,               # bytes/s per chip
    "ici_bw": 50e9,                # bytes/s per link (~per chip per direction)
    "hbm_per_chip": 16e9,          # capacity, for fit checks
}
