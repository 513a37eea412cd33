"""Which answers the ``correct`` check compares, and how.

After the window has closed, a sample of the systems that the timed calls
simulated, drawn from the seed and spread over the cell's ``stratify``
axes, is run again through :mod:`reference`. For each metric the number
compared is the widest relative gap over the sampled systems and their
nodes, ``|program - reference| / max(|reference|, 1e-6)``. Each number has
its own limit in ``bench/limits/<cell>.json``, set from the readings kept
there beside it; a number that is not finite fails.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

FLOOR = 1e-6


def sample(seed: int, calls: Sequence[Tuple[int, Sequence[dict]]],
           k: int, stratify: Sequence[str]) -> List[Tuple[int, int]]:
    """``k`` (call, system index) pairs drawn from the seed. Pick ``j``
    takes the ``j``-th value (cycling) of a seeded permutation of each axis
    in ``stratify``, so the sample spreads over every one of them."""
    rng = np.random.default_rng([int(seed) % 2**63, 0xC4EC])
    pool = [(call, i, s["coords"]) for call, systems in calls
            for i, s in enumerate(systems)]
    perms = {}
    for axis in stratify:
        values = list(dict.fromkeys(c[axis] for _, _, c in pool))
        perms[axis] = [values[j] for j in rng.permutation(len(values))]
    picked: List[Tuple[int, int]] = []
    for j in range(k):
        want = {a: v[j % len(v)] for a, v in perms.items()}
        left = [(c, i) for c, i, co in pool if (c, i) not in picked]
        match = [(c, i) for c, i, co in pool if (c, i) not in picked
                 and all(co[a] == v for a, v in want.items())]
        cands = match or left
        if cands:
            picked.append(cands[int(rng.integers(len(cands)))])
    return picked


def gaps(program: Sequence[Dict[str, np.ndarray]],
         reference: Sequence[Dict[str, np.ndarray]],
         names: Sequence[str]) -> Dict[str, float]:
    """Widest relative gap per metric over the pairs of results."""
    out = {}
    for name in names:
        worst = 0.0
        for p, r in zip(program, reference):
            a = np.asarray(p[name], np.float64).reshape(-1)
            b = np.asarray(r[name], np.float64).reshape(-1)
            if a.shape != b.shape:
                worst = float("inf")
                break
            g = np.abs(a - b) / np.maximum(np.abs(b), FLOOR)
            g = np.where(np.isfinite(g), g, np.inf)
            worst = max(worst, float(np.max(g)) if g.size else 0.0)
        out[name] = worst
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, dict]
          ) -> Tuple[bool, Dict[str, dict]]:
    """(all within limits, {name: {value, limit}}); NaN never passes."""
    table = {}
    ok = True
    for name, spec in limits.items():
        v = numbers.get(name, float("nan"))
        within = bool(v <= spec["limit"])
        ok = ok and within
        table[name] = {"value": v, "limit": spec["limit"]}
    return ok, table
