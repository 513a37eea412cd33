"""FAM-controller scheduling policies (paper §IV-A + QoS variants).

* ``fifo`` / ``wfq`` — both ride the FUSED service-chain kernel
  (``repro.core.fam_controller.arbitrate``): the kernel evaluates the
  single-queue FIFO order and the fluid two-class DWRR and selects per
  element on the traced ``use_wfq`` param, so a FIFO baseline and every
  WFQ weight share ONE compiled simulator (compile tag
  ``scheduler:chain`` for both; the weight and the CXL backlog cap are
  numeric params — sweepable without recompiling).
* ``strict`` — strict demand-over-prefetch priority (its own compile
  tag): an idealized preemptive-priority fluid model where demands never
  see prefetch occupancy and prefetch service begins only once the
  demand chain drains. The Pond-style per-tenant QoS limit case: maximum
  demand protection, maximum prefetch starvation.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.fam_controller import FamTimings, arbitrate, service_chain
from repro.policies.base import register


class ChainScheduler:
    """FIFO / WFQ over the fused service-chain kernel.

    Two registry names, one traced program: ``params_of`` differs only in
    the ``use_wfq`` selector, so either policy (or a mix across sweep
    points) executes the same executable — this is what keeps
    fig12/fig16's FIFO-vs-WFQ grids at one compile group per node count.
    """

    kind = "scheduler"
    compile_tag = "scheduler:chain"

    def __init__(self, name: str, use_wfq: bool):
        self.name = name
        self._use_wfq = use_wfq

    def params_of(self, cfg):
        return {"use_wfq": np.bool_(self._use_wfq),
                "weight": np.float32(cfg.wfq_weight),
                "backlog_cap": np.float32(cfg.wfq_backlog_cap)}

    def backlog_ok(self, p, pol, fam_busy, clock):
        # finite prefetch input queue at the controller: CXL backpressure
        # stops prefetch issue at the nodes. FIFO mode: no gate (the single
        # queue has no per-class backlog), exactly the legacy behaviour.
        return ((fam_busy[1] - clock) < pol["backlog_cap"]) | ~pol["use_wfq"]

    def arbitrate(self, p, pol, busy0, d_arr, d_valid, d_bytes,
                  p_arr, p_valid, p_bytes):
        return arbitrate(p, busy0, d_arr, d_valid, d_bytes,
                         p_arr, p_valid, p_bytes,
                         use_wfq=pol["use_wfq"], weight=pol["weight"])


class StrictScheduler:
    """Strict demand priority (idealized preemptive fluid model).

    Demands are timed through their own chain at full pooled-DDR
    bandwidth, blind to prefetch occupancy; prefetch arrivals are
    deferred to the demand chain's drain point and then served in order
    at full bandwidth. Demand latency is the best any discipline can do;
    prefetch latency is unboundedly worse under demand load, so the
    CXL backlog gate applies unconditionally (without it the deferred
    prefetch chain would grow without limit).
    """

    kind = "scheduler"
    name = "strict"
    compile_tag = "scheduler:strict"

    def params_of(self, cfg):
        return {"backlog_cap": np.float32(cfg.wfq_backlog_cap)}

    def backlog_ok(self, p, pol, fam_busy, clock):
        return (fam_busy[1] - clock) < pol["backlog_cap"]

    def arbitrate(self, p, pol, busy0, d_arr, d_valid, d_bytes,
                  p_arr, p_valid, p_bytes):
        d_service = p.fam_service_cycles(1) * d_bytes
        p_service = p.fam_service_cycles(1) * p_bytes
        d_fin, d_busy = service_chain(d_arr, d_service, d_valid, busy0[0])
        # prefetches wait out the (post-step) demand backlog, then queue
        # among themselves
        p_fin, p_busy = service_chain(jnp.maximum(p_arr, d_busy), p_service,
                                      p_valid, busy0[1])
        lat_fixed = p.fam_mem_latency + p.cxl_min_latency_cycles
        return FamTimings(
            demand_finish=jnp.where(d_valid, d_fin + lat_fixed, 0.0),
            prefetch_finish=jnp.where(p_valid, p_fin + lat_fixed, 0.0),
            new_busy=jnp.stack([d_busy, jnp.maximum(p_busy, d_busy)]))


FIFO = register(ChainScheduler("fifo", use_wfq=False))
WFQ = register(ChainScheduler("wfq", use_wfq=True))
STRICT = register(StrictScheduler())
