"""Seeds for the traffic generators.  A mix is a data file under
``traffic/``; its ``kind`` names the driver, ``drivers/<kind>.py``, whose
generator reads the mix's other keys.  Every generator makes its traffic
from the seed in set-up, before the window opens, and gives every seed the
same set of sizes and arrivals in another order, so the seed changes the
order of the work and not its amount.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per use, from a seed of any size."""
    return np.random.default_rng([int(seed) & (2**63 - 1), int(seed) >> 63,
                                  sum(map(ord, stream))])
