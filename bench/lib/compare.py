"""The numbers that decide ``correct``, each against its own limit.

Every number is a gap between what the system produced and what the
plain reference computes from the same inputs; each is compared with an
upper limit kept in ``bench/cells/<cell>.json`` beside the readings it
was set from.  A number that is not finite fails.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

Check = Tuple[str, float, float]          # (name, value, limit)


def checks_from(values: Dict[str, float], limits: Dict[str, dict]
                ) -> List[Check]:
    """Pair every limit of the cell with its number; a number the run
    did not produce reads NaN and fails."""
    return [(name, float(values.get(name, float("nan"))),
             float(spec["limit"]))
            for name, spec in limits.items()]


def passed(checks: Iterable[Check]) -> bool:
    return all(math.isfinite(v) and v <= lim for _, v, lim in checks)


def leaf_norm_gap(prog: Dict[str, float], ref: Dict[str, float],
                  keep: Optional[Iterable[str]] = None) -> float:
    """Worst leaf of |norm(program) - norm(reference)|, against the larger
    of that leaf's reference norm and the median leaf's."""
    names = sorted(ref if keep is None else keep)
    med = float(np.median([ref[n] for n in names]))
    worst = 0.0
    for n in names:
        if n not in prog:
            return float("nan")
        worst = max(worst, abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30))
    return worst


def moved_leaves(ref_grad: Dict[str, float], frac: float = 1e-3):
    """Leaves whose reference gradient is at least ``frac`` of the median
    leaf's: the others are nought to rounding, and Adam moves them by
    round-off alone, so their change is not compared."""
    med = float(np.median(list(ref_grad.values())))
    return [n for n, g in ref_grad.items() if g >= frac * med]


def rms(x) -> float:
    x = np.asarray(x, np.float64)
    return float(np.sqrt(np.mean(x * x))) if x.size else float("nan")


def report(checks: List[Check], stream) -> None:
    """Each number beside its limit, one per line (the last lines of
    standard error)."""
    for name, v, lim in checks:
        ok = math.isfinite(v) and v <= lim
        print(f"check {name} = {v!r} (limit {lim!r}) "
              f"{'ok' if ok else 'FAIL'}", file=stream)
