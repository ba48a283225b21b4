"""The comparison that decides ``correct``: the program's readings of
its first updates against the reference's.

Three numbers, each with a limit of its own (kept in the configuration
file under ``limits``):

* ``loss_gap``: the largest relative gap between a loss the program
  reported and the reference's loss of the same step, over every step
  of every timed epoch;
* ``grad_gap``: the first update's clipped gradient, as the optimizer
  got it, by the worst leaf: the gap between the program's norm of that
  leaf and the reference's, over the larger of the reference's norm of
  that leaf and of the median leaf;
* ``change_gap``: each parameter's change over the first updates, by
  the worst leaf in the same way, leaving out leaves whose reference
  gradient is under a thousandth of the median leaf's (they move by
  round-off alone under Adam).
"""

from __future__ import annotations

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
STILL = 1e-3        # a leaf whose gradient is under this share of the median


def leaf_norms(tree) -> dict[str, float]:
    """Norm of every leaf of a nested dict/list tree, by its path."""
    out: dict[str, float] = {}

    def walk(node, path):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(node[k], f"{path}.{k}" if path else k)
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(v, f"{path}[{i}]")
        else:
            out[path] = float(np.linalg.norm(np.asarray(node, np.float64)))

    walk(tree, "")
    return out


def worst_leaf_gap(prog: dict[str, float], ref: dict[str, float],
                   leaves: list[str]) -> float:
    med = float(np.median([ref[k] for k in leaves]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves)


def compare(epoch_losses: list[list[float]], prog_grad, prog_change,
            ref) -> dict[str, float]:
    """The three numbers; ``ref`` is a ``reference.Readings``."""
    r = np.asarray(ref.losses, np.float64)
    loss_gap = max(float(np.max(np.abs(np.asarray(l, np.float64) - r)
                                / np.abs(r))) for l in epoch_losses)
    rg = leaf_norms(ref.first_grad)
    pg = leaf_norms(prog_grad)
    if set(rg) != set(pg):
        raise ValueError(f"parameter trees differ: {sorted(pg)} vs "
                         f"{sorted(rg)}")
    leaves = sorted(rg)
    med = float(np.median([rg[k] for k in leaves]))
    moving = [k for k in leaves if rg[k] >= STILL * med]
    return {"loss_gap": loss_gap,
            "grad_gap": worst_leaf_gap(pg, rg, leaves),
            "change_gap": worst_leaf_gap(leaf_norms(prog_change),
                                         leaf_norms(ref.change), moving)}


def verdict(numbers: dict[str, float], limits: dict[str, float]) -> bool:
    """True when every number is finite and within its limit."""
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in NUMBERS)
