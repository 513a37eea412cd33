"""Dynamic simulator parameters — the traced half of :class:`FamConfig`.

The simulator's configuration splits into two kinds of parameter:

* **static shape parameters** (stay on ``FamConfig``): the *padded* cache
  geometry, table entries, queue sizes, prefetch degrees — anything that
  decides an array allocation. Changing one forces a recompile.
* **dynamic parameters** (:class:`FamParams`): latencies, bandwidths,
  the allocation ratio, the feature flags — and, since the
  dynamic-geometry refactor, the *effective* cache geometry
  (``num_sets``, ``cache_ways``, ``block_bits``/``block_bytes``). These
  are plain scalars threaded through the simulator as traced values, so a
  whole sweep over them (plus its baseline!) runs under ONE jit compile,
  and ``jax.vmap`` batches independent simulated systems. The cache state
  is allocated at the maximum swept ``(num_sets, ways)`` and every cache
  operation masks down to the effective geometry (see
  ``repro.core.dram_cache``) — bit-exactly equivalent to the unpadded run.

Since the policy-layer redesign there is a third axis: **policy choice vs
policy parameters** (see :mod:`repro.policies`). Which prefetcher /
scheduler / replacement / adaptation policy runs is *static* — the
:class:`~repro.policies.PolicySet`'s compile tags join the planner's
compile key — while each policy's numeric knobs (WFQ weight, SPP
confidence threshold, adaptation rates, ...) ride here on
:attr:`FamParams.policy` as a ``{kind: {param: scalar}}`` pytree of traced
values, sweepable under one compile like any other dynamic parameter.

``FamParams`` deliberately mirrors the ``FamConfig`` attribute names it
replaces (``fam_mem_latency``, ``cxl_min_latency_cycles``,
``fam_service_cycles(nbytes)``, ...) so downstream modules (throttle,
fam_controller) accept either object unchanged.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FamConfig
from repro.core.addresses import block_bits
from repro.policies import PolicySet, SimFlags


class FamParams(NamedTuple):
    """Per-system dynamic scalars. :meth:`of` builds the leaves as host
    numpy scalars; :func:`stack_params` stacks them into host arrays with
    a leading sweep axis for ``vmap``, which the executor sends to the
    device in one transfer. Under ``jit`` a numpy leaf and a device
    scalar of the same dtype are the same traced argument."""

    # core / memory timing
    base_ipc: jax.Array
    mlp: jax.Array
    cores_per_node: jax.Array
    llc_latency: jax.Array
    local_mem_latency: jax.Array
    fam_mem_latency: jax.Array
    cxl_min_latency_cycles: jax.Array
    fam_cycles_per_byte: jax.Array     # DDR occupancy per byte moved
    demand_bytes: jax.Array
    block_bytes: jax.Array             # service size (bytes moved per fill)
    # effective cache geometry (the CacheState is allocated at the padded
    # maximum; these traced scalars mask it down — see repro.core.dram_cache)
    num_sets: jax.Array                # i32 effective set count
    cache_ways: jax.Array              # i32 effective associativity
    block_bits: jax.Array              # i32 log2(block_bytes): traced shift
    # placement
    allocation_ratio: jax.Array
    # feature flags (dynamic: baseline + variants share one compile)
    core_prefetch: jax.Array
    dram_prefetch: jax.Array
    bw_adapt: jax.Array
    all_local: jax.Array
    #: per-policy numeric params: {kind: {param: scalar}} —
    #: schema from the PolicySet (see repro.policies), values traced. The
    #: SPP confidence threshold, WFQ weight/backlog cap, and the
    #: adaptation tuning knobs live here now, not as loose fields.
    policy: Dict[str, Dict[str, jax.Array]]

    @classmethod
    def of(cls, cfg: FamConfig, flags: Optional[SimFlags] = None,
           policies: Optional[PolicySet] = None) -> "FamParams":
        """Build concrete params from a config (+ optional SimFlags and
        :class:`~repro.policies.PolicySet`).

        ``policies=None`` derives the set from the flags
        (:meth:`PolicySet.from_flags`: ``wfq=True`` selects the ``wfq``
        scheduler with the flag weight). An *explicit* ``policies`` is
        authoritative for policy choice and numeric params — the legacy
        ``flags.wfq``/``flags.wfq_weight`` are ignored then — while the
        remaining flag booleans always populate the dynamic feature gates.
        """
        f32, i32, b = np.float32, np.int32, np.bool_
        if flags is None:
            flags = SimFlags()
        if policies is None:
            policies = PolicySet.from_flags(flags)
        return cls(
            base_ipc=f32(cfg.base_ipc), mlp=f32(cfg.mlp),
            cores_per_node=f32(cfg.cores_per_node),
            llc_latency=f32(cfg.llc_latency),
            local_mem_latency=f32(cfg.local_mem_latency),
            fam_mem_latency=f32(cfg.fam_mem_latency),
            cxl_min_latency_cycles=f32(cfg.cxl_min_latency_cycles),
            fam_cycles_per_byte=f32(cfg.fam_service_cycles(1)),
            demand_bytes=f32(cfg.demand_bytes),
            block_bytes=f32(cfg.block_bytes),
            num_sets=i32(cfg.num_sets),
            cache_ways=i32(cfg.cache_ways),
            block_bits=i32(block_bits(cfg.block_bytes)),
            allocation_ratio=i32(cfg.allocation_ratio),
            core_prefetch=b(flags.core_prefetch),
            dram_prefetch=b(flags.dram_prefetch),
            bw_adapt=b(flags.bw_adapt),
            all_local=b(flags.all_local),
            policy=policies.numeric_params(cfg))

    # -- FamConfig-compatible helpers (duck-typed by throttle/controller) --
    def fam_service_cycles(self, nbytes) -> jax.Array:
        return self.fam_cycles_per_byte * nbytes

    def with_flags(self, flags: SimFlags) -> "FamParams":
        """Replace the flag fields (broadcast over any sweep axis).

        The legacy ``wfq``/``wfq_weight`` flags map onto the scheduler
        policy's numeric params when its schema carries them (the fused
        ``fifo``/``wfq`` chain policies do); under a scheduler without
        those params (e.g. ``strict``) they are ignored.
        """
        shape = jnp.shape(self.base_ipc)
        full = lambda v, dt: jnp.full(shape, v, dt)
        pol: Dict[str, Dict[str, Any]] = \
            {k: dict(v) for k, v in self.policy.items()}
        sched = pol.get("scheduler", {})
        if "use_wfq" in sched:
            sched["use_wfq"] = full(flags.wfq, jnp.bool_)
        if "weight" in sched:
            sched["weight"] = full(flags.wfq_weight, jnp.float32)
        return self._replace(
            core_prefetch=full(flags.core_prefetch, jnp.bool_),
            dram_prefetch=full(flags.dram_prefetch, jnp.bool_),
            bw_adapt=full(flags.bw_adapt, jnp.bool_),
            all_local=full(flags.all_local, jnp.bool_),
            policy=pol)


def stack_params(params: Sequence[FamParams]) -> FamParams:
    """Stack S per-system FamParams into one host batch with leading axis
    S (numpy arrays, dtypes kept).

    Every member must share the policy-param schema — i.e. come from
    PolicySets with equal compile tags (the planner's group invariant).
    """
    return jax.tree.map(lambda *xs: np.stack(xs), *params)
