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


@pytest.fixture
def tiny_run(monkeypatch):
    """Drive a whole run of a cell on the CPU at a short trace length,
    skipping only the harness's look for a chip; the program's executable
    caches are fresh for the test, and the persistent compile cache is not
    touched."""
    def go(cell, T=96, seed=2**31 + 12345, seconds=0.3):
        import run
        from repro.core import famsim
        from repro.experiments import executor as ex
        spec = run.load_cell(cell)
        spec["traffic"]["T"] = T
        monkeypatch.setattr(run, "load_cell", lambda name: spec)
        monkeypatch.setattr(run, "use_compile_cache", lambda: "off")
        monkeypatch.setattr(ex, "_EXEC_CACHE", {})
        monkeypatch.setattr(famsim, "_MASKED_CACHE", {})
        args = run.parse(["--workload", cell, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", "0"])
        return run.run(args, require_tpu=False)
    return go


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
        else:
            raise ValueError(fault)
        return out
    monkeypatch.setattr(ex, "_run_group", broken)
