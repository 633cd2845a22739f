"""The one general traffic generator: a mix is a data file of parameters.

Every seed gives the same set of sizes in another order. The set is the
quantiles of the mix's distribution, so two runs differ in which row
meets which, never in how much work the window holds.
"""

from __future__ import annotations

from statistics import NormalDist
from typing import Dict, List

import numpy as np


def quantile_sizes(spec: dict, n: int) -> np.ndarray:
    """The n mid-quantiles of a length distribution, clipped, as ints.

    spec: {"dist": "lognormal", "median", "sigma", "min", "max"}."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    sizes = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(sizes), int(spec["min"]),
                   int(spec["max"])).astype(np.int64)


def balanced_groups(sizes: np.ndarray, group: int) -> np.ndarray:
    """Deal the sizes into groups of ``group`` whose sums are nearly
    equal: largest first, each to the group that holds least so far and
    is not yet full. Returns [groups, group]."""
    if len(sizes) % group:
        raise ValueError(f"{len(sizes)} sizes do not fill groups of {group}")
    n = len(sizes) // group
    members: List[List[int]] = [[] for _ in range(n)]
    for size in np.sort(sizes)[::-1]:
        open_ = [g for g in members if len(g) < group]
        min(open_, key=sum).append(int(size))
    return np.array(members, np.int64)


def train_examples(rows: dict, vocab: int, seq_len: int, group: int,
                   seed: int) -> List[Dict[str, np.ndarray]]:
    """Tokenised examples {input_ids [L+1], loss_weights [L+1]} whose
    row lengths L follow the mix; ids are uniform over 1..vocab-1
    (0 is the pad id) and every token is trained on.

    The lengths are the ``distinct`` mid-quantiles of the mix's
    distribution, dealt into optimizer steps (``group`` rows each) of
    nearly equal token counts. A seed changes the order of the steps
    within each round of ``distinct`` rows, the order of the rows within
    a step and every token id, never the amount of work: whichever steps
    a window holds, it holds the same number of tokens a step."""
    rng = np.random.default_rng(int(seed))
    count = int(rows["count"])
    distinct = int(rows.get("distinct", count))
    steps = balanced_groups(
        np.minimum(quantile_sizes(rows["length"], distinct), seq_len), group)
    sizes = []
    while len(sizes) < count:
        for g in rng.permutation(len(steps)):
            sizes.extend(rng.permutation(steps[g]))
    return [{"input_ids": rng.integers(1, vocab, int(L) + 1,
                                       dtype=np.int32),
             "loss_weights": np.ones(int(L) + 1, np.float32)}
            for L in sizes[:count - count % group]]
