"""Traffic generation from a seed: numpy only, no program code.

Every helper takes a ``numpy.random.Generator`` made from ``--seed``, so the
same seed gives the same inputs.  ``ycsb_zipfian`` is a copy of the helper
in ``chip_smoke.py`` (YCSB's ZipfianGenerator, Gray et al. 1994).
"""

from __future__ import annotations

import numpy as np

# odd multiplier (2^32 / golden ratio): t -> (t + 1) * _MIX is a bijection on
# uint32 with no zero for t < 2^32 - 1, so every tick XORs a distinct mask
_MIX = 0x9E3779B9


def ycsb_zipfian(n: int, size: int, rng, theta: float = 0.99) -> np.ndarray:
    """YCSB's ZipfianGenerator (Gray et al. 1994): ids in [0, n), 0 hottest."""
    zetan = np.sum(1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta)
    zeta2 = 1.0 + 0.5**theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(size)
    uz = u * zetan
    ids = (n * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    ids = np.where(uz < 1.0 + 0.5**theta, 1, ids)
    ids = np.where(uz < 1.0, 0, ids)
    return np.minimum(ids, n - 1).astype(np.int32)


def keys(spec: dict, rows: int, size: int, rng) -> np.ndarray:
    """int32 tenant keys in [0, rows) drawn as ``spec`` says:
    ``{"dist": "zipfian", "theta": 0.99}``."""
    if spec["dist"] != "zipfian":
        raise ValueError(f"unknown key distribution {spec['dist']!r}")
    return ycsb_zipfian(rows, size, rng, float(spec["theta"]))


def items(size: int, rng) -> np.ndarray:
    """Uniform 32-bit items."""
    return rng.integers(0, 1 << 32, size, dtype=np.uint32)


def tick_mask(t: int) -> np.uint32:
    """The XOR mask that makes tick ``t``'s items differ from every other
    tick's: a bijection of the pool, so items stay uniform."""
    return np.uint32(((t + 1) * _MIX) & 0xFFFFFFFF)

