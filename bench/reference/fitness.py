"""Plain reference fitness, as the paper and Karoo GP define each kernel
(lower is better), computed in float64 over the whole dataset:

    r        sum |pred - y|
    mse      sum (pred - y)^2
    c        -(number of rows whose label round(pred), clipped to
             0 .. n_classes - 1, equals y); a NaN prediction makes the
             tree invalid (+inf)
    pearson  1 - r^2 of pred against y; any non-finite prediction makes
             the tree invalid (+inf)

A NaN fitness becomes +inf: such a tree may never win.

`fitness_range` bounds the fitness a float32 program may publish, from
the per-row bounds of `interval.bounds`; `gap` measures a published
fitness against the reference, or against that range where given.
"""
from __future__ import annotations

import math

import numpy as np


def fitness(kernel: str, preds, y, n_classes: int = 2) -> float:
    p = np.asarray(preds, np.float64)
    y = np.asarray(y, np.float64)
    with np.errstate(all="ignore"):
        if kernel == "r":
            out = float(np.abs(p - y).sum())
        elif kernel == "mse":
            out = float(np.square(p - y).sum())
        elif kernel == "c":
            if np.isnan(p).any():
                return math.inf
            lab = np.clip(np.round(np.nan_to_num(p)), 0, n_classes - 1)
            out = -float((lab == y).sum())
        elif kernel == "pearson":
            if not np.isfinite(p).all():
                return math.inf
            dx = p - p.mean()
            dy = y - y.mean()
            den = (dx * dx).sum() * (dy * dy).sum()
            r2 = float(np.square((dx * dy).sum()) / den) if den > 0 else 0.0
            out = 1.0 - r2
        else:
            raise ValueError(f"no reference for fitness kernel {kernel!r}")
    return math.inf if math.isnan(out) else out


def _labels(p, n_classes):
    return np.clip(np.round(np.nan_to_num(np.asarray(p, np.float64))), 0,
                   n_classes - 1)


def fitness_range(kernel: str, lo, hi, maybe_nan, y, n_classes: int = 2):
    """(least, most, may_be_inf) fitness over every prediction within the
    per-row bounds [lo, hi] (NaN bounds: the row can only be NaN), or
    None for a kernel without a range (compared with the point
    reference alone). `c`: the label is monotone in the prediction, so a
    row is a sure hit where both bounds label it y, and a possible one
    where y lies between their labels; one row that may be NaN lets the
    tree be invalid, one that can only be NaN makes it so."""
    if kernel != "c":
        return None
    y = np.asarray(y, np.float64)
    empty = np.isnan(np.asarray(lo, np.float64))
    if empty.any():
        return math.inf, math.inf, True
    a, b = _labels(lo, n_classes), _labels(hi, n_classes)
    sure = float(((a == y) & (b == y)).sum())
    maybe = float(((a <= y) & (y <= b)).sum())
    return -maybe, -sure, bool(np.asarray(maybe_nan).any())


def gap(program: float, reference: float, within=None) -> float:
    """How far a published fitness lies from the reference's, as a share
    of the reference (of 1 where the reference is under 1 in size).
    Equal infinities agree; one infinite side is an infinite gap. With
    `within`, a `fitness_range`, the distance is to the nearest fitness
    in that range, and none inside it."""
    if within is not None:
        least, most, may_be_inf = within
        if math.isinf(program) or math.isnan(program):
            return 0.0 if program == math.inf and may_be_inf else math.inf
        if math.isinf(least):
            return math.inf
        off = max(least - program, program - most, 0.0)
        return off / max(min(abs(least), abs(most)), 1.0)
    if math.isinf(program) or math.isinf(reference) or math.isnan(program):
        return 0.0 if program == reference else math.inf
    return abs(program - reference) / max(abs(reference), 1.0)
