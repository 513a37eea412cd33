"""A stand-in reference for the tests: the plain reference's numbers times
``SCALE``, each call recorded in ``CALLS`` as (coords, dtype)."""
import numpy as np

import grid

_REF = grid.bench_module("reference.py")
METRICS = _REF.METRICS
SCALE = 1.0
CALLS = []


def simulate(system, dtype=np.float32):
    CALLS.append((system["coords"], dtype))
    return {k: v * SCALE for k, v in _REF.simulate(system, dtype).items()}
