"""The comparisons that decide ``correct``. Every number compared is a gap
that is 0 for a perfect match and has a limit in the cell's file
(``benchmark/cells/<cell>.json``), set from readings on the chip
(``PERF.md`` section 2).

Norms are compared leaf by leaf, a leaf being one layer's slice of one
weight (or the embedding, or a final-norm vector): the gap between the
program's norm and the reference's, against the reference's norm of that
leaf or of the median leaf, whichever is larger — some gradients are all
but zero.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

Norms = Dict[str, np.ndarray]       # leaf name -> [layers] or scalar


def flat(norms: Norms) -> Tuple[list, np.ndarray]:
    names, values = [], []
    for key in sorted(norms):
        arr = np.atleast_1d(np.asarray(norms[key], np.float64))
        for i, v in enumerate(arr):
            names.append(f"{key}[{i}]" if arr.size > 1 else key)
            values.append(v)
    return names, np.asarray(values)


def negligible_leaves(ref_grad: Norms, ratio: float = 1e-3) -> np.ndarray:
    """Leaves whose reference gradient is nought to rounding (under a
    thousandth of the median leaf's): under Adam they move by round-off
    alone, so the params' change does not count them."""
    _, g = flat(ref_grad)
    return g < ratio * np.median(g)


def worst_leaf_gap(prog: Norms, ref: Norms, skip: Optional[np.ndarray] = None
                   ) -> Tuple[float, str]:
    names, p = flat(prog)
    names_r, r = flat(ref)
    if names != names_r:
        raise ValueError("program and reference name different leaves")
    floor = np.median(r)
    gap = np.abs(p - r) / np.maximum(r, floor)
    if skip is not None:
        gap = np.where(skip, 0.0, gap)
    gap = np.where(np.isfinite(gap), gap, np.inf)
    i = int(np.argmax(gap))
    return float(gap[i]), names[i]


def loss_gap(prog_losses, ref_losses) -> float:
    p, r = np.asarray(prog_losses, np.float64), np.asarray(ref_losses, np.float64)
    gap = np.abs(p - r) / np.abs(r)
    return float(np.where(np.isfinite(gap), gap, np.inf).max())


def train_checks(prog: Dict[str, object], ref: Dict[str, object],
                 limits: Dict[str, float]):
    """``prog`` / ``ref``: ``losses`` [steps], ``grad`` norms of the first
    gradient, ``change`` norms of the params' change after the first
    steps. Returns (name, value, limit) for every number that has a limit."""
    skip = negligible_leaves(ref["grad"])
    values = {
        "loss_gap": loss_gap(prog["losses"], ref["losses"]),
        "grad_norm_gap": worst_leaf_gap(prog["grad"], ref["grad"])[0],
        "change_norm_gap": worst_leaf_gap(prog["change"], ref["change"], skip)[0],
    }
    return [(k, values[k], float(limits[k])) for k in values if k in limits]
