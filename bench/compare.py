"""The comparison that decides ``correct`` for a training cell.

The program's first three steps (taken in set-up through the window's own
call) are compared with the reference's three steps from the same start
on the same rows.  Three numbers, each held to a limit of its own:

* ``loss_rel``: the largest relative gap between the program's and the
  reference's negative bound over the three steps;
* ``grad1_norm``: the first gradient as the optimiser got it, recovered
  from the program's Adam state after one step (m1 / (1 - b1)); per leaf
  the gap between the two norms, over the larger of the reference leaf's
  norm and the median leaf's norm; the worst leaf;
* ``change_norm``: the same measure of the parameters' change after the
  three steps, over the leaves whose reference first gradient is at least
  a thousandth of the median leaf's (a leaf with no gradient moves under
  Adam by round-off alone).

A reading that is not finite fails its limit.
"""
from __future__ import annotations

import math

import numpy as np

NAMES = ("loss_rel", "grad1_norm", "change_norm")


def _norms(tree) -> list[float]:
    import jax

    return [float(np.linalg.norm(np.asarray(a, np.float64)))
            for a in jax.tree.leaves(tree)]


def _worst_leaf_gap(prog: list[float], ref: list[float],
                    keep: list[bool]) -> float:
    med = float(np.median(ref))
    gaps = [abs(p - r) / max(r, med)
            for p, r, k in zip(prog, ref, keep) if k]
    return max(gaps) if gaps else 0.0


def readings(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` as ``reference.training.train`` returns them
    (the program's leaves cut to the reference's rows)."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    g_ref = _norms(ref["grad1"])
    grad = _worst_leaf_gap(_norms(prog["grad1"]), g_ref, [True] * len(g_ref))
    med = float(np.median(g_ref))
    moved = [g >= 1e-3 * med for g in g_ref]
    change = _worst_leaf_gap(_norms(prog["change"]), _norms(ref["change"]),
                             moved)
    return {"loss_rel": loss, "grad1_norm": grad, "change_norm": change}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``; a missing or non-finite
    value fails, and is given as null (JSON has no NaN)."""
    out, ok = {}, True
    for name, limit in limits.items():
        v = values.get(name)
        v = float("nan") if v is None else float(v)
        finite = math.isfinite(v)
        out[name] = {"value": v if finite else None, "limit": limit}
        ok = ok and finite and v <= limit
    return ok, out
