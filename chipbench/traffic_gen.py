"""The one traffic generator: turns a mix's parameters and a seed into
request kinds. The program sees only the requests.

A mix file (``traffic/<mix>.json``) names its ``loop`` (a file under
``loops/``) and the parameters that loop reads. The parameters this file
reads:

* ``judge_share``: the share of requests that are threshold judges; the
  rest are brackets. Every batch holds exactly that share, in an order
  drawn from the seed.
"""
from __future__ import annotations

import numpy as np

from chipbench.common import rng


def judge_mask(mix: dict, count: int, seed: int, stream: int) -> np.ndarray:
    """Bool (count,): True for a judge. Exactly round(share * count)."""
    k = int(round(float(mix["judge_share"]) * count))
    mask = np.zeros(count, bool)
    mask[:k] = True
    return rng(seed, stream).permutation(mask)

