"""Uniform key ids: every id of ``[0, n_ids)`` equally likely."""
from __future__ import annotations

import numpy as np


def draw(rng: np.random.Generator, n_ids: int, size: int,
         params: dict) -> np.ndarray:
    """-> ``size`` ids as int64, drawn with ``rng``."""
    del params
    return rng.integers(0, n_ids, size=size, dtype=np.int64)
