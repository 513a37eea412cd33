"""The simulator's in-graph phases carry ``jax.named_scope`` names.

The names tag the ops' metadata only: the optimized program, printed the
way XLA compares modules (no metadata, instruction and computation names
canonicalized), is the same as a build without scopes. Instruction names
themselves are not compared: XLA derives them from the ops' locations,
which the scopes change.
"""
import re
from contextlib import nullcontext

import jax
import pytest

SCOPES = ("trace_gen", "phase_a", "cache_lookup", "prefetcher", "sched",
          "phase_c", "cache_fill", "prefetch_queue", "metrics")


def _compile(monkeypatch, telemetry: int):
    """A small masked group with in-graph traces: S 4, N 2, T 64."""
    from repro.configs.base import FamConfig, fam_replace
    from repro.core import famsim
    from repro.experiments import executor as ex
    from repro.policies import DEFAULT_POLICY_SET

    monkeypatch.setattr(famsim, "_MASKED_CACHE", {})
    jax.clear_caches()
    cfg = fam_replace(FamConfig(), telemetry=telemetry)
    fn, shapes = ex.group_program(
        cfg, 4, 2, 64, pad_sets=cfg.num_sets, pad_ways=cfg.cache_ways,
        trace_backend="device", policies=DEFAULT_POLICY_SET)
    return jax.jit(fn).lower(*shapes).compile()


def _canonical(compiled) -> str:
    from jax._src.lib import xla_client as xc
    (module,) = compiled.runtime_executable().hlo_modules()
    return module.to_string(xc._xla.HloPrintOptions.canonical())


def _scopes_in(text: str):
    found = set()
    for name in re.findall(r'op_name="([^"]*)"', text):
        for part in name.split("/"):
            while part.startswith("vmap(") and part.endswith(")"):
                part = part[5:-1]
            found.add(part)
    return found


@pytest.mark.parametrize("telemetry", [0, 4])
def test_group_program_is_scoped_and_unchanged_by_scopes(monkeypatch,
                                                         telemetry):
    scoped = _compile(monkeypatch, telemetry)
    found = _scopes_in(scoped.as_text())
    expect = set(SCOPES) | ({"telemetry"} if telemetry else set())
    assert expect <= found, sorted(expect - found)
    if not telemetry:
        assert "telemetry" not in found
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: nullcontext())
        bare = _compile(monkeypatch, telemetry)
    assert not set(SCOPES) & _scopes_in(bare.as_text())
    assert _canonical(scoped) == _canonical(bare)


def test_prefetch_queue_scope_nests_in_its_callers(monkeypatch):
    """The queue's slot work carries ``prefetch_queue`` inside the scope
    of its caller: the drain, the demand probe and the space gate under
    ``phase_a``, the candidate probes under ``phase_a/prefetcher``, the
    inserts under ``phase_c/cache_fill``."""
    text = _compile(monkeypatch, 0).as_text()
    callers = set()
    for name in re.findall(r'op_name="([^"]*)"', text):
        parts = []
        for part in name.split("/"):
            while part.startswith("vmap(") and part.endswith(")"):
                part = part[5:-1]
            parts.append(part)
        if "prefetch_queue" in parts:
            outer = parts[:parts.index("prefetch_queue")]
            callers.add(tuple(p for p in outer if p in SCOPES))
    assert {("phase_a",), ("phase_a", "prefetcher"),
            ("phase_c", "cache_fill")} <= callers, sorted(callers)
