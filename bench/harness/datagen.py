"""Data generators of the benchmark, copied from the program's
`data/datasets.py`, so that no later change to the program can change
what the benchmark feeds it. Everything is made from a seed.
"""
from __future__ import annotations

import numpy as np


def kat7(rows: int, seed: int, feats: int = 9, informative: int = 6):
    """KAT-7 RFI-flagging stand-in: nonlinear binary labels over
    standard-normal per-channel statistics."""
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, feats).astype(np.float32)
    w = rng.randn(informative).astype(np.float32)
    z = ((X[:, :informative] * w).sum(-1) + 0.5 * X[:, 0] * X[:, 1]
         - 0.3 * np.abs(X[:, 2]))
    y = (z > np.median(z)).astype(np.float32)
    return X, y


BY_NAME = {"kat7": kat7}


def seeds(seed: int, n: int) -> list[int]:
    """`n` 31-bit seeds drawn from any whole number `seed` (the driver's
    seeds exceed 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(n)
    return [int(w) & 0x7FFFFFFF for w in words]
