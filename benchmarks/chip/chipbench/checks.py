"""The numbers that decide ``correct``, and their comparison with limits.

Every number is a gap between what the timed path produced and what the
plain reference computes from the same weights and inputs; a run is correct
when each number is finite and at most its limit.  Limits live in the
configuration's file, each with the readings it was set from (PERF.md).
"""

from __future__ import annotations

import math
import sys
from typing import Dict, Iterable, List, Mapping, Tuple

import numpy as np

# a leaf whose reference gradient is under this share of the median leaf's
# is moved by round-off alone under Adam; its change is not compared
ROUNDOFF_LEAF = 1e-3


def leaves(tree: Mapping) -> Dict[str, np.ndarray]:
    """``{"layer/leaf": float64 array}`` of a two-level params-like dict."""
    return {f"{k}/{n}": np.asarray(v, np.float64)
            for k, entry in sorted(tree.items())
            for n, v in sorted(entry.items())}


def norms(tree: Mapping) -> Dict[str, float]:
    return {k: float(np.linalg.norm(v)) for k, v in leaves(tree).items()}


def rel_gap(a: float, b: float) -> float:
    """``|a - b| / |b|``; infinite when either is not a finite number."""
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / abs(b) if b != 0 else (0.0 if a == b else math.inf)


def worst_norm_gap(prog: Mapping[str, float], ref: Mapping[str, float],
                   keys: Iterable[str] = None) -> float:
    """Worst leaf's ``|norm_prog - norm_ref|``, against the larger of that
    leaf's reference norm and the median leaf's."""
    keys = sorted(ref) if keys is None else sorted(keys)
    if set(prog) != set(ref):
        return math.inf
    floor = float(np.median([ref[k] for k in ref]))
    return worst(abs(prog[k] - ref[k]) / max(ref[k], floor) for k in keys)


def worst_rel_err(prog: Mapping, ref: Mapping) -> float:
    """Worst leaf's ``||a - b||`` against the larger of its reference norm
    and the median leaf's."""
    pa, ra = leaves(prog), leaves(ref)
    if set(pa) != set(ra) or any(pa[k].shape != ra[k].shape for k in ra):
        return math.inf
    rn = {k: float(np.linalg.norm(v)) for k, v in ra.items()}
    floor = float(np.median(list(rn.values())))
    return worst(float(np.linalg.norm(pa[k] - ra[k])) / max(rn[k], floor)
                  for k in ra)


def worst(values: Iterable[float]) -> float:
    """The largest value; infinite when any is not a finite number."""
    out = 0.0
    for v in values:
        if not math.isfinite(v):
            return math.inf
        out = max(out, v)
    return out


def moving_leaves(ref_grad_norms: Mapping[str, float]) -> List[str]:
    floor = ROUNDOFF_LEAF * float(np.median(list(ref_grad_norms.values())))
    return [k for k, v in ref_grad_norms.items() if v >= floor]


def compare(numbers: Mapping[str, float], limits: Mapping[str, float]
            ) -> Tuple[bool, Dict[str, dict]]:
    """``(correct, {name: {"value", "limit"}})``; a number without a limit,
    or a limit without a number, is a failure."""
    out, ok = {}, set(numbers) == set(limits)
    for name in sorted(set(numbers) | set(limits)):
        v = numbers.get(name, math.nan)
        lim = limits.get(name, math.nan)
        out[name] = {"value": v, "limit": lim}
        if not (math.isfinite(v) and v <= lim):
            ok = False
    return ok, out


def print_checks(checks: Mapping[str, dict], stream=sys.stderr) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=stream, flush=True)
