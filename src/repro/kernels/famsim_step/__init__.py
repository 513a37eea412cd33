"""Fused DRAM-cache step engine (the famsim hot path, docs/performance.md).

One simulator event's worth of per-node cache work — retire up to
``completions_per_step`` prefetch fills, probe + LRU/SRRIP-touch the
demand block, then probe the prefetch-candidate and core-prefetch blocks
for redundancy — as ONE kernel over the padded ``(sets, ways)`` metadata
arrays, instead of the ~15 separate gather/scatter ops the pure-XLA path
emits per event.

``ops.cache_step`` is the entry point famsim calls; ``backend="xla"``
(the default) runs the pure-XLA reference in :mod:`ref` — the exact
``repro.core.dram_cache`` op sequence the classic simulator used —
while ``backend="pallas"`` runs the fused kernel in :mod:`kernel`
(interpreted when the program is lowered for a platform other than
TPU), bit-identical by property test (``tests/test_famsim_step.py``).
"""
from repro.kernels.famsim_step.kernel import fused_cache_step
from repro.kernels.famsim_step.ops import (FUSED_REPLACEMENT_MODES,
                                           KERNEL_BACKENDS, cache_step,
                                           fused_replacement_mode)
from repro.kernels.famsim_step.ref import cache_step_ref

__all__ = ["KERNEL_BACKENDS", "FUSED_REPLACEMENT_MODES", "cache_step",
           "cache_step_ref", "fused_cache_step", "fused_replacement_mode"]
