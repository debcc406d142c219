"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against its own limit from ``chipbench/limits``:

* ``loss_gap``: over the first three steps, the largest relative gap
  between the program's loss and the reference's.
* ``grad_gap``: the first gradient as the optimizer got it, read from its
  first moment after one step (m₁ = (1 − β₁)·g in the strategy's storage
  arithmetic), against the reference's gradient.
* ``change_gap``: the parameters' change θ₂ − θ₀ after two steps, against
  the reference's.

The last two are taken by the worst tensor: the gap between the program's
norm and the reference's, over the larger of the reference's norm of that
tensor and the median tensor's. A tensor whose reference gradient is under
a thousandth of the median tensor's moves by round-off alone and is left
out of ``change_gap``."""
from __future__ import annotations

import math
import statistics

ZERO_GRAD = 1e-3


def worst_norm_gap(prog: dict, ref: dict, keep=None) -> tuple:
    """(largest gap, its tensor) over the tensors in ``keep`` (all)."""
    names = sorted(ref if keep is None else keep)
    med = statistics.median(ref[k] for k in ref)
    worst, which = 0.0, None
    for k in names:
        den = max(ref[k], med)
        gap = abs(prog[k] - ref[k]) / den if den > 0 else math.inf
        if not math.isfinite(prog[k]):
            gap = math.inf
        if which is None or gap > worst:
            worst, which = gap, k
    return worst, which


def moving(ref_grad_norms: dict) -> list:
    med = statistics.median(ref_grad_norms.values())
    return [k for k, v in ref_grad_norms.items() if v >= ZERO_GRAD * med]


def readings(prog: dict, ref: dict) -> dict:
    """The three numbers compared, with the tensor that set each."""
    loss_gap = max(abs(p - r) / abs(r) if math.isfinite(p) else math.inf
                   for p, r in zip(prog["losses"], ref["losses"]))
    grad_gap, grad_at = worst_norm_gap(prog["grad_norms"], ref["grad_norms"])
    change_gap, change_at = worst_norm_gap(
        prog["change_norms"], ref["change_norms"],
        keep=moving(ref["grad_norms"]))
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap,
            "at": {"grad_gap": grad_at, "change_gap": change_at}}


def judge(values: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) for every limited number that
    the run read."""
    checks = {k: {"value": values[k], "limit": limits[k]}
              for k in limits if not k.startswith("_") and k in values}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
