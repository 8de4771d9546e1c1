"""Independent random streams drawn from one ``--seed``."""
from __future__ import annotations

import numpy as np


def stream(seed: int, tag: int) -> np.random.Generator:
    """The stream ``tag`` of ``seed``: locations, field, start, sample."""
    return np.random.default_rng([seed % 2**63, tag])


def sample(items: list, k: int, seed: int, tag: int) -> list:
    """``k`` of ``items`` (all of them where there are fewer), drawn from
    the seed, in their original order."""
    if len(items) <= k:
        return list(items)
    idx = stream(seed, tag).choice(len(items), size=k, replace=False)
    return [items[i] for i in sorted(idx)]
