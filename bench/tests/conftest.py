"""Keep the benchmark's tests on the CPU and let them import ``bench/``."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


def tiny_spec(monkeypatch, cell, T=96, edit=None):
    """The cell's files at a short trace length (``edit(spec)`` may change
    them further), handed to ``run.load_cell``; the program's executable
    caches are fresh, and the persistent compile cache is not touched."""
    import run
    from repro.core import famsim
    from repro.experiments import executor as ex
    spec = run.load_cell(cell)
    spec["traffic"]["T"] = T
    if edit is not None:
        edit(spec)
    monkeypatch.setattr(run, "load_cell", lambda name: spec)
    monkeypatch.setattr(run, "use_compile_cache", lambda: "off")
    monkeypatch.setattr(ex, "_EXEC_CACHE", {})
    monkeypatch.setattr(famsim, "_MASKED_CACHE", {})
    return spec


def tiny_run_of(monkeypatch, cell, T=96, seed=2**31 + 12345, seconds=0.3,
                edit=None):
    """A whole run of a cell on the CPU, skipping only the harness's look
    for a chip."""
    import run
    tiny_spec(monkeypatch, cell, T, edit)
    args = run.parse(["--workload", cell, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", "0"])
    return run.run(args, require_tpu=False)


@pytest.fixture
def tiny_run(monkeypatch):
    """``tiny_run(cell, T=..., seed=..., seconds=..., edit=...)``: see
    :func:`tiny_run_of`."""
    return lambda cell, **kw: tiny_run_of(monkeypatch, cell, **kw)


@pytest.fixture
def plant(monkeypatch):
    """``plant(fault, limits)`` breaks the program's timed path underneath
    a run."""
    return lambda fault, limits: _plant(monkeypatch, fault, limits)


def _plant(monkeypatch, fault, limits):
    import numpy as np

    from repro.core import famsim
    from repro.experiments import executor as ex

    if fault == "state_unchanged":
        orig = famsim._make_step

        def frozen(cfg, num_nodes, policies=None):
            orig(cfg, num_nodes, policies)
            return lambda p, carry, inputs: (carry, None)
        monkeypatch.setattr(famsim, "_make_step", frozen)
        return
    orig_run = ex._run_group

    def broken(data, compiled):
        out = {k: np.array(v) for k, v in orig_run(data, compiled).items()}
        if fault == "half_batch":
            for v in out.values():
                h = v.shape[0] // 2
                v[h:] = v[:h].mean(axis=0)
        elif fault == "answer_altered":
            out["ipc"] = out["ipc"] * (1.0 + 10 * limits["ipc"]["limit"])
        elif fault == "exchange_left_out":
            # every chip's lanes read chip 0's, as if no chip's results
            # but the first were gathered
            import jax
            D = len(jax.devices())
            for v in out.values():
                q = v.shape[0] // D
                for d in range(1, D):
                    v[d * q:(d + 1) * q] = v[:q]
        else:
            raise ValueError(fault)
        return out
    monkeypatch.setattr(ex, "_run_group", broken)
