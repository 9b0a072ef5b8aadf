"""Whether the timed path trained correctly: the program's first steps
against the plain reference's, number by number, each against its limit.

- ``loss_gap``: over the checked steps, the largest |loss - reference| as a
  share of the reference's loss;
- ``grad_norm_gap``: over the leaves, the largest gap between the norm of
  the program's first gradient as its optimizer got it (Adam's first moment
  after one step, over 1 - beta1) and the reference's, as a share of the
  reference leaf's norm or of the median leaf's, whichever is larger;
- ``update_norm_gap``: the same for the change of each leaf over the checked
  steps. Leaves whose reference gradient is under a thousandth of the median
  leaf's are left out: their gradient is rounding (a key bias under softmax)
  and Adam moves them by it alone.
"""
from __future__ import annotations

import statistics

NUMBERS = ("loss_gap", "grad_norm_gap", "update_norm_gap")
NEGLIGIBLE = 1e-3


def _worst(prog: dict, ref: dict, names) -> tuple[float, str]:
    med = statistics.median(ref[n] for n in names)
    gaps = {n: abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30) for n in names}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def numbers(prog: dict, ref: dict) -> dict:
    """prog / ref: {"losses": [...], "grad1": {leaf: norm},
    "change": {leaf: norm}}."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(prog["losses"], ref["losses"], strict=True))
    leaves = sorted(ref["grad1"])
    g, g_leaf = _worst(prog["grad1"], ref["grad1"], leaves)
    med = statistics.median(ref["grad1"].values())
    moved = [n for n in leaves if ref["grad1"][n] >= NEGLIGIBLE * med]
    u, u_leaf = _worst(prog["change"], ref["change"], moved)
    return dict(loss_gap=loss_gap, grad_norm_gap=g, update_norm_gap=u,
                worst_grad_leaf=g_leaf, worst_update_leaf=u_leaf,
                left_out=[n for n in leaves if n not in moved])


def decide(nums: dict, limits: dict) -> tuple[bool, dict]:
    """Every number at or under its limit; a number that is not finite
    fails."""
    report = {}
    ok = True
    for k in NUMBERS:
        v, lim = nums[k], limits[k]["limit"]
        good = v == v and v <= lim
        ok &= good
        report[k] = dict(value=v, limit=lim)
    return ok, report
