"""Seeds the drivers share. The data itself comes from the generator a
configuration names (`bench/datasets/<generator>.py`)."""
from __future__ import annotations

import numpy as np


def seeds(seed: int, n: int) -> list[int]:
    """`n` 31-bit seeds drawn from any whole number `seed` (the driver's
    seeds exceed 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(n)
    return [int(w) & 0x7FFFFFFF for w in words]
