"""The numbers that decide ``correct``: gaps between what the timed path
produced and what the plain reference computes, each against its limit."""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np


def loss_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    """Worst relative gap of a round's loss."""
    return max(abs(p - r) / abs(r) for p, r in zip(prog, ref))


def leaf_norm_gap(prog: Dict[str, float], ref: Dict[str, float],
                  keep=None) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger.  ``keep`` names the leaves compared."""
    return max(leaf_gaps(prog, ref, keep).values())


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keep=None) -> Dict[str, float]:
    """Each compared leaf's gap, as ``leaf_norm_gap`` takes the worst."""
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in ref]))
    return {n: abs(prog[n] - ref[n]) / max(ref[n], med) for n in names}


def median_rel_err(prog: Dict[str, np.ndarray],
                   ref: Dict[str, np.ndarray]) -> float:
    """Median over leaves of ||prog - ref|| / ||ref|| on the same sampled
    coordinates: the error of the values themselves, which a norm does
    not see when it is spread evenly over a leaf."""
    errs = [float(np.linalg.norm(prog[n] - ref[n])
                  / max(np.linalg.norm(ref[n]), 1e-30)) for n in ref]
    return float(np.median(errs))


def moved_leaves(grad_norms: Dict[str, float], rel: float = 1e-3):
    """Leaves whose reference gradient is not nought to rounding: at least
    ``rel`` times the median leaf's."""
    med = float(np.median(list(grad_norms.values())))
    return {n for n, g in grad_norms.items() if g >= rel * med}


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, [[name, value, limit], ...]).  A number that is missing
    or not finite fails."""
    rows: List[list] = []
    ok = True
    for name, limit in limits.items():
        v = numbers.get(name, float("nan"))
        good = v is not None and math.isfinite(v) and v <= limit
        ok = ok and good
        rows.append([name, v, limit])
    return ok, rows
