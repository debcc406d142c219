"""Pallas TPU kernels of the training hot path (collage_update, flash).

``resolve_interpret`` is the one place that decides how a kernel runs:
compiled by Mosaic on a TPU, interpreted by the Pallas HLO interpreter on
every other backend (tier-1 tests on the CPU). Every kernel entry point
takes ``interpret=None`` and resolves it here."""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret=None) -> bool:
    """``None`` → interpret everywhere but a TPU; an explicit bool wins
    (tests force ``True`` on the CPU)."""
    return (not on_tpu()) if interpret is None else bool(interpret)
