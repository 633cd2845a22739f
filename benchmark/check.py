"""The numbers that ``correct`` compares, from the program's readings and
the reference's. No limits here: those are data (benchmark/limits/).
"""

from __future__ import annotations

from statistics import median
from typing import Dict, Sequence

# a leaf whose gradient, in the reference, stays under this share of the
# median leaf's through every followed step moves under Adam by round-off
# alone; it is left out of the comparison of parameter changes
DEAD_GRADIENT_SHARE = 1e-3


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float],
                   leaves: Sequence[str] = None) -> float:
    """Largest |program norm - reference norm| over the leaves, measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    names = list(reference if leaves is None else leaves)
    if set(names) - set(program):
        raise KeyError(f"program lacks leaves {set(names) - set(program)}")
    floor = median(reference[n] for n in reference)
    return max(abs(program[n] - reference[n]) / max(reference[n], floor)
               for n in names)


def live_leaves(reference_grad_norms: Sequence[Dict[str, float]]):
    """Leaves whose reference gradient is not nought to rounding: the
    largest norm over the followed steps against the median leaf's."""
    top = {n: max(step[n] for step in reference_grad_norms)
           for n in reference_grad_norms[0]}
    floor = DEAD_GRADIENT_SHARE * median(top.values())
    return [n for n, v in top.items() if v >= floor]


def train_readings(program: dict, reference: dict) -> Dict[str, float]:
    """program / reference: {"loss": [l1, l2, ...], "grad_norm": {leaf:
    norm of the first gradient as the optimizer gets it}, "change": {leaf:
    norm of the change after the followed steps}}; the reference also has
    "grad_norms": one dict a step."""
    out = {}
    for i, (a, b) in enumerate(zip(program["loss"], reference["loss"]), 1):
        out[f"loss{i}"] = abs(a - b) / abs(b)
    out["grad_gap"] = worst_leaf_gap(program["grad_norm"],
                                     reference["grad_norm"])
    out["change_gap"] = worst_leaf_gap(
        program["change"], reference["change"],
        live_leaves(reference["grad_norms"]))
    return out
