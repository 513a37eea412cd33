"""Backend dispatch for the fused cache step.

``cache_step`` is what famsim calls once per node per event. The
``backend`` tag is STATIC (it rides on ``FamConfig.kernel_backend`` and
therefore on every compile key): ``"xla"`` runs the dram_cache reference
sequence, ``"pallas"`` the fused kernel — compiled when the program is
lowered for TPU, interpreted (and still jit-compatible) when it is
lowered for any other platform, bit-identical either way.

The fused kernel bakes the replacement policy in as a static mode, so
only policies that declare ``fused_mode`` ("lru", "srrip") can ride it;
``random`` needs threefry inside the update and stays XLA-only.
"""
from __future__ import annotations

import functools

import jax

from repro.core import dram_cache as dc
from repro.kernels.famsim_step.kernel import fused_cache_step
from repro.kernels.famsim_step.ref import cache_step_ref

KERNEL_BACKENDS = ("xla", "pallas")
FUSED_REPLACEMENT_MODES = ("lru", "srrip")


def fused_replacement_mode(policy):
    """The kernel's static ``(mode, max_rrpv)`` for a *bound* policy (or
    the policy class itself — both carry ``fused_mode``). Raises for
    policies the fused kernel cannot express. Host-side: runs on the
    policy OBJECT at build/dispatch time, never on traced values (scoped
    out of the jit checks in ``repro.analysis.scopes``)."""
    mode = "lru" if policy is None else getattr(policy, "fused_mode", None)
    if mode not in FUSED_REPLACEMENT_MODES:
        raise ValueError(
            f"kernel_backend='pallas' supports replacement policies "
            f"{FUSED_REPLACEMENT_MODES} only, got "
            f"{getattr(policy, 'name', type(policy).__name__)!r}; use "
            "kernel_backend='xla' for this policy")
    return mode, int(getattr(policy, "max_rrpv", 0))


def cache_step(cache: dc.CacheState, fill_blocks, fill_enable,
               demand_block, demand_enable, probe_blocks,
               num_sets, ways, policy=None, backend: str = "xla"):
    """One event's fused cache work; see :func:`ref.cache_step_ref`."""
    if backend == "xla":
        return cache_step_ref(cache, fill_blocks, fill_enable,
                              demand_block, demand_enable, probe_blocks,
                              num_sets, ways, policy=policy)
    if backend != "pallas":
        raise ValueError(f"unknown kernel backend {backend!r}; expected "
                         f"one of {KERNEL_BACKENDS}")
    mode, max_rrpv = fused_replacement_mode(policy)

    def kernel(interpret):
        return functools.partial(fused_cache_step, mode=mode,
                                 max_rrpv=max_rrpv, interpret=interpret)

    # chosen by the platform the program is lowered for: compiled on TPU,
    # interpreted anywhere else
    tags, lru, stamp, hit, probe_hits = jax.lax.platform_dependent(
        cache.tags, cache.lru, cache.stamp, fill_blocks, fill_enable,
        demand_block, demand_enable, probe_blocks, num_sets, ways,
        tpu=kernel(False), default=kernel(True))
    return dc.CacheState(tags, lru, stamp), hit, probe_hits
