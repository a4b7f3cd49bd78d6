"""KAT-7 RFI-flagging stand-in, copied from the program's
`data/datasets.py` so that no later change to the program can change
what the benchmark feeds it: nonlinear binary labels over
standard-normal per-channel statistics, made from the seed."""
from __future__ import annotations

import numpy as np


def make(seed: int, rows: int, features: int, classes: int,
         informative: int = 6):
    if classes != 2:
        raise ValueError(f"kat7 labels are binary, the configuration "
                         f"states {classes} classes")
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, features).astype(np.float32)
    w = rng.randn(informative).astype(np.float32)
    z = ((X[:, :informative] * w).sum(-1) + 0.5 * X[:, 0] * X[:, 1]
         - 0.3 * np.abs(X[:, 2]))
    y = (z > np.median(z)).astype(np.float32)
    return X, y
