"""Multi-node FAM memory-system simulator (paper §V methodology, in JAX).

Vectorized discrete-event model: one LLC-miss event per node per scan step.
Each step:
  A. (per node, vmapped) advance clock, retire completed prefetches into the
     DRAM cache, probe cache/prefetch-queue for the demand, train the
     DRAM-cache prefetch policy and generate prefetch candidates, run the
     core (stride) prefetcher, apply the adaptation policy's issue tokens;
  B. (global) the scheduler policy orders the step's demand+prefetch
     arrivals at the FAM controller and times them through the DDR service
     chain;
  C. (per node) demand stall accounting (IPC model), prefetch-queue fills,
     adaptation-policy observation, metric accumulation.

Figures of merit follow the paper's §V-A definitions: IPC gain, relative
FAM latency, relative DRAM prefetches issued, demand / core-prefetch hit
fractions. The core model is analytic: cycles = sum(gap) + sum(stall/MLP).

Configuration splits THREE ways (see ``repro.core.fam_params`` and
``repro.policies``):

* ``FamConfig`` supplies the **static shape parameters** (the *padded*
  cache allocation, table sizes, degrees) that are baked into the
  compiled program;
* a ``PolicySet`` names the **policy implementations** — prefetcher,
  scheduler, replacement, adaptation — whose compile tags are static too
  (a different traced program per tag), while each policy's numeric
  params ride on ``FamParams.policy`` as traced scalars;
* ``FamParams`` carries every remaining **dynamic scalar** (latencies,
  bandwidths, the allocation ratio, the feature flags — and the
  *effective* cache geometry ``num_sets``/``cache_ways``/``block_bits``)
  as traced values.

The cache state may be allocated at a maximum swept ``(num_sets, ways)``
(``pad_sets``/``pad_ways`` on the builders) while each system's effective
geometry masks it down per operation (``repro.core.dram_cache``) — block
size included, via the traced ``block_bits`` address split — bit-exactly
equivalent to the unpadded run.

``build_sim`` keeps the classic one-system API (params become XLA
constants).  ``sweep``/``build_sweep`` vmap the same step function over a
batch of independent simulated systems — sweep points x workloads — so a
whole paper figure costs ONE jit compile, geometry sweeps included.
Every builder takes an optional ``policies: PolicySet``; the default set
(spp + fifo/wfq chain + lru + token_bucket) executes the same traced
program the pre-policy simulator did.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FamConfig
from repro.core import dram_cache as dc
from repro.core import prefetch_queue as pq
from repro.core.addresses import (PAGE_BITS, dyn_block_addr,
                                  dyn_blocks_per_page, dyn_split)
from repro.core.fam_params import FamParams, stack_params
from repro.core.throttle import ThrottleState  # noqa: F401 (compat)
from repro.kernels.famsim_step import (KERNEL_BACKENDS, cache_step,
                                       fused_replacement_mode)
from repro.obs import telemetry as obs_telemetry
from repro.policies import DEFAULT_POLICY_SET, PolicySet, SimFlags

__all__ = ["SimFlags", "PolicySet", "NodeState", "build_sim", "build_sweep",
           "build_masked_vmap", "sweep", "simulate"]

# Legacy aliases of the now-config-carried core-prefetch shape parameters
# (``FamConfig.core_pf_degree`` / ``completions_per_step`` /
# ``core_fill_entries``); kept only for external references — the
# simulator reads the config fields.
CORE_PF_DEGREE = 2
COMPLETIONS_PER_STEP = 8
CORE_FILL_ENTRIES = 64


def _resolve(policies: Optional[PolicySet]) -> PolicySet:
    return DEFAULT_POLICY_SET if policies is None else policies


class NodeState(NamedTuple):
    clock: jax.Array
    pf: jax.Array              # prefetch-policy state pytree (SPP: SppState)
    cache: dc.CacheState
    queue: pq.PrefetchQueue
    throttle: jax.Array        # adaptation-policy state (ThrottleState)
    core_last: jax.Array       # last demand line addr (for stride detect)
    core_stride: jax.Array
    core_buf_line: jax.Array   # (core_fill_entries,) line addr +1; 0 empty
    core_buf_fin: jax.Array    # fill completion times
    core_buf_ptr: jax.Array
    # accumulators
    instr: jax.Array
    cycles: jax.Array
    fam_lat_sum: jax.Array
    fam_cnt: jax.Array
    demand_fam: jax.Array      # demands to FAM-resident data
    demand_hit: jax.Array      # ... that hit the DRAM cache
    corepf_fam: jax.Array
    corepf_hit: jax.Array
    pf_issued: jax.Array       # DRAM-cache prefetches issued to FAM


def _init_node(cfg: FamConfig, p: FamParams,
               pad_sets: Optional[int] = None,
               pad_ways: Optional[int] = None,
               policies: Optional[PolicySet] = None) -> NodeState:
    """``pad_sets``/``pad_ways`` size the cache *allocation* (>= every
    effective geometry in the batch); default: ``cfg``'s own geometry."""
    impls = _resolve(policies).impls()
    f0 = jnp.float32(0.0)
    return NodeState(
        clock=f0, pf=impls.prefetch.init(cfg),
        cache=dc.init_cache(pad_sets or cfg.num_sets,
                            pad_ways or cfg.cache_ways),
        queue=pq.init_queue(cfg.prefetch_queue),
        throttle=impls.adaptation.init(p, p.policy["adaptation"]),
        core_last=jnp.int32(-1), core_stride=jnp.int32(0),
        core_buf_line=jnp.zeros((cfg.core_fill_entries,), jnp.int32),
        core_buf_fin=jnp.zeros((cfg.core_fill_entries,), jnp.float32),
        core_buf_ptr=jnp.int32(0),
        instr=f0, cycles=f0, fam_lat_sum=f0, fam_cnt=f0,
        demand_fam=f0, demand_hit=f0, corepf_fam=f0, corepf_hit=f0,
        pf_issued=f0)


def _is_fam_page(allocation_ratio, page):
    """allocation ratio X => X/(X+1) of pages live in FAM (paper §V-A.4)."""
    h = (page.astype(jnp.uint32) * jnp.uint32(0x61C88647)) >> 16
    mod = jnp.asarray(allocation_ratio + 1, jnp.uint32)
    return (h % mod) != 0


def _phase_a(cfg: FamConfig, p: FamParams, ns: NodeState, addr, gap, warm,
             live=True, policies: Optional[PolicySet] = None):
    """Per-node pre-arbitration work. Returns (ns, req) where req carries
    this node's demand + prefetch candidates.

    ``live`` (a traced bool in the dynamic-T masked runner) gates every
    state write through the per-op ``enable`` masks that already exist:
    a non-live step is an exact no-op — bit-identical carry out — without
    the whole-state carry-select (and its full-array copies) the masked
    runner used to pay per step. ``live=True`` folds to the classic step.
    """
    impls = _resolve(policies).impls()
    pf_pol = p.policy["prefetch"]
    ad_pol = p.policy["adaptation"]
    repl = impls.replacement.bind(p.policy["replacement"])
    # effective geometry: traced scalars masking the padded cache state
    bb = jnp.asarray(p.block_bits, jnp.int32)
    eff_sets, eff_ways = p.num_sets, p.cache_ways
    live = jnp.asarray(live)
    clock = ns.clock + jnp.where(live, gap, 0.0)

    # retire completed prefetches into the cache (bounded per step).
    # top_k indices are DISTINCT, so the per-slot fill blocks/enables can
    # be gathered up front (value-identical to reading them inside the
    # fill loop) and the queue drained with one scatter — the sequential
    # part (same-set fills interact) lives in the cache engine.
    with jax.named_scope("prefetch_queue"):
        done = (ns.queue.block > 0) & (ns.queue.finish <= clock) & live
        score = jnp.where(done, -ns.queue.finish, -jnp.inf)
        _, idxs = jax.lax.top_k(score, cfg.completions_per_step)
        fill_blocks = ns.queue.block[idxs] - 1
        fill_ok = done[idxs] & (ns.queue.block[idxs] > 0)
        queue = ns.queue._replace(block=ns.queue.block.at[idxs].set(
            jnp.where(fill_ok, 0, ns.queue.block[idxs])))

    page, block_in_page = dyn_split(addr, bb)
    page = page.astype(jnp.int32)
    block_in_page = block_in_page.astype(jnp.int32)
    gblock = dyn_block_addr(addr, bb).astype(jnp.int32)
    is_fam = _is_fam_page(p.allocation_ratio, page) & ~p.all_local & live

    # core-prefetch fill buffer (LLC side): a demand whose line was core-
    # prefetched is served on-chip once the fill lands
    line0 = (addr >> 6).astype(jnp.int32)
    cb_match = ns.core_buf_line == (line0 + 1)
    cpb_hit = jnp.any(cb_match) & p.core_prefetch
    cpb_fin = jnp.max(jnp.where(cb_match, ns.core_buf_fin, 0.0))

    # prefetch-policy train + predict (FAM-bound LLC misses only, incl.
    # core prefetch misses per paper §III; here the demand stream trains).
    # Cache-independent, so it hoists above the cache ops value-identically
    # — which lets ALL of this event's cache work go to the engine at once.
    with jax.named_scope("prefetcher"):
        pf_state, ctx = impls.prefetch.train(
            cfg, pf_pol, ns.pf, page, block_in_page,
            enable=is_fam & p.dram_prefetch)
        bpp = dyn_blocks_per_page(bb)
        cand_gblock, cand_valid = impls.prefetch.predict(
            cfg, pf_pol, pf_state, page, block_in_page, ctx,
            cfg.prefetch_degree, bpp)

    # core (stride) prefetcher target addresses (cache-independent too)
    line = (addr >> 6).astype(jnp.int32)
    stride = line - ns.core_last
    stride_ok = (stride == ns.core_stride) & (stride != 0) & \
        (jnp.abs(stride) < 32)
    cpf_lines = line + stride * (1 + jnp.arange(cfg.core_pf_degree,
                                                dtype=jnp.int32))
    cpf_pages = (cpf_lines >> (PAGE_BITS - 6)).astype(jnp.int32)
    cpf_fam = jax.vmap(lambda pg: _is_fam_page(p.allocation_ratio, pg))(
        cpf_pages) & ~p.all_local
    cpf_valid = stride_ok & cpf_fam & p.core_prefetch & live
    cpf_gblock = (cpf_lines >> (bb - 6)).astype(jnp.int32)

    # the event's ENTIRE cache interaction, fused (docs/performance.md):
    # C fill inserts -> demand probe + touch -> D+CPF pure probes. The
    # demand probe is masked out entirely when DRAM-cache prefetch is off.
    with jax.named_scope("cache_lookup"):
        cache, hit, probe_hits = cache_step(
            ns.cache, fill_blocks, fill_ok, gblock,
            is_fam & p.dram_prefetch, jnp.concatenate([cand_gblock,
                                                       cpf_gblock]),
            eff_sets, eff_ways, policy=repl, backend=cfg.kernel_backend)
    cand_hit = probe_hits[:cfg.prefetch_degree]
    cpf_raw_hits = probe_hits[cfg.prefetch_degree:]

    inflight, inflight_fin = pq.contains(queue, gblock)
    inflight = inflight & is_fam & ~hit & p.dram_prefetch
    hit = hit & ~cpb_hit
    inflight = inflight & ~cpb_hit
    demand_to_fam = is_fam & ~hit & ~inflight & ~cpb_hit

    with jax.named_scope("prefetcher"):
        # in-flight dedupe of the candidates
        cand_inflight = jax.vmap(lambda b: pq.contains(queue, b)[0])(
            cand_gblock)
        fresh = ~cand_hit & ~cand_inflight
    pf_valid = cand_valid & fresh & is_fam & p.dram_prefetch
    pf_blocks = cand_gblock
    # adaptation: grant tokens for the surviving candidates (the rate
    # controller must not drift on non-live steps). The policy owns its
    # activation gate: token_bucket keeps the legacy bw_adapt flag,
    # static is active whenever chosen.
    want = jnp.sum(pf_valid.astype(jnp.int32))
    thr, grant = impls.adaptation.take(p, ad_pol, ns.throttle, want,
                                       impls.adaptation.gate(p) & live)
    rank = jnp.cumsum(pf_valid.astype(jnp.int32))
    pf_valid = pf_valid & (rank <= grant)
    # queue-space gate (§III-A2: drop when the queue is full/threshold)
    with jax.named_scope("prefetch_queue"):
        free = jnp.sum((queue.block == 0).astype(jnp.int32))
    pf_valid = pf_valid & (jnp.cumsum(pf_valid.astype(jnp.int32)) <= free)

    # core prefetches may hit the DRAM cache (probed by the engine above)
    cpf_hits = cpf_raw_hits & p.dram_prefetch
    cpf_to_fam = cpf_valid & ~cpf_hits

    ns = ns._replace(clock=clock, pf=pf_state, cache=cache, queue=queue,
                     throttle=thr,
                     core_last=jnp.where(live, line, ns.core_last),
                     core_stride=jnp.where(live & (stride != 0), stride,
                                           ns.core_stride))
    if cfg.telemetry:
        # telemetry-only signal (repro.obs): prefetch candidates dropped
        # because the block was already cached or in flight. Added ONLY
        # under the static telemetry tag so the default path's traced
        # program stays byte-identical.
        pf_redundant = jnp.sum((cand_valid & ~fresh & is_fam &
                                p.dram_prefetch).astype(jnp.float32))
    # NOTE: cpf_lines rides along in req so phase C fills the buffer with
    # exactly the lines validated here — recomputing them after the
    # core_last/core_stride update is what phase C must NOT do.
    req = dict(gblock=gblock, is_fam=is_fam, hit=hit, inflight=inflight,
               inflight_fin=inflight_fin, demand_to_fam=demand_to_fam,
               cpb_hit=cpb_hit, cpb_fin=cpb_fin,
               pf_blocks=pf_blocks, pf_valid=pf_valid,
               cpf_lines=cpf_lines,
               cpf_valid=cpf_valid, cpf_hits=cpf_hits & cpf_valid,
               cpf_to_fam=cpf_to_fam, gap=gap, warm=warm, live=live)
    if cfg.telemetry:
        req["pf_redundant"] = pf_redundant
    return ns, req


def _phase_c(cfg: FamConfig, p: FamParams, ns: NodeState, req,
             d_fin, pf_fin, cpf_fin, policies: Optional[PolicySet] = None):
    """Per-node post-arbitration accounting + queue fills.

    Returns ``(ns, lat)`` — the per-event demand latency rides out for
    the telemetry accumulator (``repro.obs``); with telemetry off it is
    unused and DCE'd, so the compiled program is unchanged."""
    impls = _resolve(policies).impls()
    ad_pol = p.policy["adaptation"]
    clock = ns.clock
    warm = req["warm"]
    local_lat = jnp.asarray(p.local_mem_latency, jnp.float32)

    fam_demand_lat = jnp.maximum(d_fin - clock, 1.0)
    llc_lat = jnp.asarray(p.llc_latency, jnp.float32)
    lat = jnp.where(req["cpb_hit"],
                    jnp.maximum(req["cpb_fin"] - clock, llc_lat),
                    jnp.where(~req["is_fam"], local_lat,
                              jnp.where(req["hit"], local_lat,
                                        jnp.where(req["inflight"],
                                                  jnp.maximum(req["inflight_fin"] - clock,
                                                              local_lat),
                                                  fam_demand_lat))))

    # fill the prefetch queue with issued prefetches
    queue = ns.queue

    def ins(i, q):
        q2, _ = pq.try_insert(q, req["pf_blocks"][i], pf_fin[i], 0.95,
                              enable=req["pf_valid"][i])
        return q2

    with jax.named_scope("cache_fill"):
        queue = jax.lax.fori_loop(0, cfg.prefetch_degree, ins, queue)

    fam_miss = req["is_fam"] & ~req["hit"] & ~req["inflight"]
    # record core-prefetch fills (round-robin fill buffer) for the lines
    # phase A actually validated (carried in req — see _phase_a)
    cpf_lines = req["cpf_lines"]
    cpf_cached_fin = clock + local_lat
    fin = jnp.where(req["cpf_hits"], cpf_cached_fin, cpf_fin)
    buf_line, buf_fin, ptr = ns.core_buf_line, ns.core_buf_fin, ns.core_buf_ptr

    def put(i, carry):
        bl, bf, ptr_ = carry
        ok = req["cpf_valid"][i]
        bl = bl.at[ptr_].set(jnp.where(ok, cpf_lines[i] + 1, bl[ptr_]))
        bf = bf.at[ptr_].set(jnp.where(ok, fin[i], bf[ptr_]))
        return bl, bf, (ptr_ + ok.astype(jnp.int32)) % cfg.core_fill_entries

    with jax.named_scope("cache_fill"):
        buf_line, buf_fin, ptr = jax.lax.fori_loop(
            0, cfg.core_pf_degree, put, (buf_line, buf_fin, ptr))

    live = req["live"]
    thr = impls.adaptation.observe(
        p, ad_pol, ns.throttle, lat, fam_miss, req["hit"],
        jnp.sum(req["pf_valid"].astype(jnp.int32)), enable=live)
    thr = impls.adaptation.adapt(p, ad_pol, thr,
                                 enable=impls.adaptation.gate(p) & live)

    # node-level accounting: the trace event stream aggregates the node's
    # cores, so per-event compute gaps shrink by 1/cores (higher FAM arrival
    # rate — the paper's congestion regime) while one event's stall only
    # blocks one core: stall_node = lat / (mlp * cores).
    stall = jnp.where(live, lat / (p.mlp * p.cores_per_node), 0.0)
    w = warm.astype(jnp.float32)
    npf = jnp.sum(req["pf_valid"].astype(jnp.int32)).astype(jnp.float32)
    ns = ns._replace(
        clock=clock + stall, queue=queue, throttle=thr,
        core_buf_line=buf_line, core_buf_fin=buf_fin, core_buf_ptr=ptr,
        instr=ns.instr + w * req["gap"] * p.base_ipc,
        cycles=ns.cycles + w * (req["gap"] + stall),
        fam_lat_sum=ns.fam_lat_sum + w * jnp.where(req["is_fam"], lat, 0.0),
        fam_cnt=ns.fam_cnt + w * req["is_fam"].astype(jnp.float32),
        demand_fam=ns.demand_fam + w * req["is_fam"].astype(jnp.float32),
        demand_hit=ns.demand_hit + w * (req["hit"]).astype(jnp.float32),
        corepf_fam=ns.corepf_fam + w * jnp.sum(
            req["cpf_valid"].astype(jnp.float32)),
        corepf_hit=ns.corepf_hit + w * jnp.sum(
            req["cpf_hits"].astype(jnp.float32)),
        pf_issued=ns.pf_issued + w * npf)
    return ns, lat


def _make_step(cfg: FamConfig, num_nodes: int,
               policies: Optional[PolicySet] = None):
    """The shared per-event step: step(p, carry, (addr, gap, warm, live)).

    Both the classic fixed-T runner (``_make_run``, live always True) and
    the dynamic-T masked runner (``_make_run_masked``) scan this exact
    function, so the two paths execute identical floating-point programs
    on live steps — and a non-live step is an exact no-op on the carry
    (every state write is gated through the per-op enable masks; the FAM
    busy chains are preserved because no request is valid), which is what
    lets the masked runner skip the whole-state carry-select it used to
    pay per step.

    ``policies`` selects the policy implementations statically (one traced
    program per compile-tag combination); their numeric params arrive
    traced on ``p.policy``.

    ``cfg.telemetry`` (a static compile tag, see ``repro.obs``) extends
    the carry with a windowed-counter accumulator and the inputs with a
    per-step window index: step(p, (nodes, fam_busy, tele),
    (addr, gap, warm, live, win)). With the default 0 the step is built
    exactly as before — same signature, same traced program.
    """
    policies = _resolve(policies)
    impls = policies.impls()
    n_win = cfg.telemetry
    if cfg.kernel_backend not in KERNEL_BACKENDS:
        raise ValueError(
            f"FamConfig.kernel_backend={cfg.kernel_backend!r}; expected "
            f"one of {KERNEL_BACKENDS}")
    if cfg.kernel_backend == "pallas":
        # fail at build time (not mid-trace) for policies the fused
        # kernel cannot express (random needs threefry in the update)
        fused_replacement_mode(impls.replacement)
    D = cfg.prefetch_degree
    CPF = cfg.core_pf_degree

    def step(p, carry, inputs):
        sp = p.policy["scheduler"]
        if n_win:
            nodes, fam_busy, tele = carry
            addr, gap, warm, live, win = inputs    # addr/gap: (N,)
        else:
            nodes, fam_busy = carry
            addr, gap, warm, live = inputs     # addr/gap: (N,)
        # the named scopes (docs/observability.md) only tag the ops'
        # metadata, so a device trace reads by phase; the optimized
        # program is the same without them
        with jax.named_scope("phase_a"):
            nodes, req = jax.vmap(
                lambda ns, a, g: _phase_a(cfg, p, ns, a, g, warm, live,
                                          policies))(
                    nodes, addr, gap)

        with jax.named_scope("sched"):
            # finite prefetch input queue at the FAM controller: when the
            # prefetch-class backlog exceeds the cap, CXL backpressure
            # stops prefetch issue at the nodes (this is what makes WFQ
            # reduce prefetches-issued in the paper's Fig. 12C). The
            # scheduler policy owns the gate (FIFO mode: none).
            backlog_ok = impls.scheduler.backlog_ok(p, sp, fam_busy,
                                                    nodes.clock)
            req["pf_valid"] = req["pf_valid"] & backlog_ok[:, None]
            req["cpf_to_fam"] = req["cpf_to_fam"] & backlog_ok[:, None]

            d_arr = nodes.clock
            d_valid = req["demand_to_fam"]
            d_bytes = jnp.full((num_nodes,), p.demand_bytes, jnp.float32)
            p_arr = jnp.concatenate([
                jnp.repeat(nodes.clock, D), jnp.repeat(nodes.clock, CPF)])
            p_valid = jnp.concatenate([req["pf_valid"].reshape(-1),
                                       req["cpf_to_fam"].reshape(-1)])
            p_bytes = jnp.concatenate([
                jnp.full((num_nodes * D,), p.block_bytes, jnp.float32),
                jnp.full((num_nodes * CPF,), p.demand_bytes,
                         jnp.float32)])
            t = impls.scheduler.arbitrate(p, sp, fam_busy, d_arr, d_valid,
                                          d_bytes, p_arr, p_valid, p_bytes)
            pf_fin = t.prefetch_finish[: num_nodes * D].reshape(
                num_nodes, D)
            cpf_fin = t.prefetch_finish[num_nodes * D:].reshape(
                num_nodes, CPF)

        with jax.named_scope("phase_c"):
            nodes, lat = jax.vmap(
                lambda ns, r, df, pf, cf: _phase_c(cfg, p, ns, r, df, pf,
                                                   cf, policies)
            )(nodes, req, t.demand_finish, pf_fin, cpf_fin)
        if n_win:
            with jax.named_scope("telemetry"):
                tele = obs_telemetry.accumulate(
                    tele, win, num_nodes=num_nodes, live=live, req=req,
                    lat=lat, nodes=nodes, new_busy=t.new_busy)
            return (nodes, t.new_busy, tele), None
        return (nodes, t.new_busy), None

    return step


def _init_carry(cfg: FamConfig, p: FamParams, num_nodes: int,
                pad_sets: Optional[int] = None,
                pad_ways: Optional[int] = None,
                policies: Optional[PolicySet] = None):
    one = _init_node(cfg, p, pad_sets, pad_ways, policies)
    nodes = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (num_nodes,) + x.shape).copy(), one)
    return nodes, jnp.zeros((2,), jnp.float32)


def _metrics(nodes: NodeState, p: FamParams,
             telemetry: Optional[jax.Array] = None
             ) -> Dict[str, jax.Array]:
    with jax.named_scope("metrics"):
        ipc = nodes.instr / jnp.maximum(nodes.cycles, 1.0)
        out = {
            "ipc": ipc,
            "fam_latency": nodes.fam_lat_sum /
                jnp.maximum(nodes.fam_cnt, 1.0),
            "demand_hit_fraction": nodes.demand_hit /
                jnp.maximum(nodes.demand_fam, 1.0),
            "corepf_hit_fraction": nodes.corepf_hit /
                jnp.maximum(nodes.corepf_fam, 1.0),
            "prefetches_issued": nodes.pf_issued,
            "issue_rate": nodes.throttle.issue_rate,
            # occupancy over the EFFECTIVE geometry (padded region stays
            # empty)
            "cache_occupancy": jax.vmap(
                lambda c: dc.occupancy(c, p.num_sets, p.cache_ways))(
                    nodes.cache),
        }
        if telemetry is not None:
            # windowed observability streams (repro.obs.telemetry): one
            # per-system (node-summed) ``(n_windows, N_COUNTERS)`` matrix
            out["telemetry"] = telemetry
        return out


def _make_run(cfg: FamConfig, num_nodes: int, warmup_frac: float = 0.2,
              pad_sets: Optional[int] = None,
              pad_ways: Optional[int] = None,
              policies: Optional[PolicySet] = None):
    """One-system step loop: run(params, addrs (N,T), gaps (N,T)) -> metrics.

    Only the static shape parameters of ``cfg`` (plus the optional padded
    cache allocation and the policy choice) are read here; every dynamic
    value — the effective cache geometry and the policy numeric params
    included — comes from the traced ``FamParams``.
    """
    step = _make_step(cfg, num_nodes, policies)
    n_win = cfg.telemetry

    def run(p: FamParams, addrs, gaps):
        N, T = addrs.shape
        assert N == num_nodes
        gaps = gaps.astype(jnp.float32) / p.cores_per_node  # aggregate stream
        warm = jnp.arange(T) >= int(T * warmup_frac)
        live = jnp.ones((T,), jnp.bool_)
        carry0 = _init_carry(cfg, p, N, pad_sets, pad_ways, policies)
        xs = (addrs.T.astype(jnp.int32), gaps.T, warm, live)
        if n_win:
            win = obs_telemetry.window_index(jnp.arange(T), jnp.int32(T),
                                             n_win)
            carry, _ = jax.lax.scan(lambda c, i: step(p, c, i),
                                    carry0 + (obs_telemetry.init_windows(
                                        n_win),),
                                    xs + (win,))
            nodes, _, tele = carry
            return _metrics(nodes, p, tele)
        (nodes, _), _ = jax.lax.scan(
            lambda c, i: step(p, c, i), carry0, xs)
        return _metrics(nodes, p)

    return run


def _make_run_masked(cfg: FamConfig, num_nodes: int,
                     pad_sets: Optional[int] = None,
                     pad_ways: Optional[int] = None,
                     trace_gen=None,
                     policies: Optional[PolicySet] = None):
    """Dynamic-T runner for bucketed (padded) traces.

    run(params, addrs (N, T_pad), gaps (N, T_pad), t_true, warm_start)
    simulates only the first ``t_true`` events: padded tail steps run the
    step with ``live=False``, which makes them exact no-ops on the carry
    (every write gated through the per-op enable masks — no whole-state
    carry-select, no full-array copies), so every piece of state —
    including the final-state metrics (``issue_rate``, ``cache_occupancy``)
    — is bit-identical to an unpadded run of length ``t_true``.

    ``warm_start`` is the first accumulated event index, computed on the
    host as ``int(t_true * warmup_frac)`` so it matches ``_make_run``'s
    static arithmetic exactly. Both scalars are traced: one executable
    serves every true length that pads to the same bucket.

    ``trace_gen`` (a per-node :func:`repro.traces.device.node_generator`)
    switches the signature to run(params, trace_params, t_true,
    warm_start): the node traces are generated IN GRAPH — vmapped over
    the node axis right here — instead of being staged from the host.
    The generated arrays feed the exact same simulation body, so in-graph
    generation is bit-identical to pre-staging
    ``repro.traces.device.system_traces`` arrays at the same T_pad.
    """
    step = _make_step(cfg, num_nodes, policies)
    n_win = cfg.telemetry

    def _sim(p: FamParams, addrs, gaps, t_true, warm_start):
        N, T_pad = addrs.shape
        assert N == num_nodes
        gaps = gaps.astype(jnp.float32) / p.cores_per_node
        i = jnp.arange(T_pad)
        valid = i < t_true
        warm = (i >= warm_start) & valid
        carry0 = _init_carry(cfg, p, N, pad_sets, pad_ways, policies)
        xs = (addrs.T.astype(jnp.int32), gaps.T, warm, valid)
        if n_win:
            # windows partition the TRUE length (traced): padded tail
            # steps all map to the last window and contribute zero
            win = obs_telemetry.window_index(i, t_true, n_win)
            carry, _ = jax.lax.scan(lambda c, inp: step(p, c, inp),
                                    carry0 + (obs_telemetry.init_windows(
                                        n_win),),
                                    xs + (win,))
            nodes, _, tele = carry
            return _metrics(nodes, p, tele)
        (nodes, _), _ = jax.lax.scan(
            lambda c, inp: step(p, c, inp), carry0, xs)
        return _metrics(nodes, p)

    if trace_gen is None:
        return _sim

    def run_gen(p: FamParams, trace_params, t_true, warm_start):
        with jax.named_scope("trace_gen"):
            addrs, gaps = jax.vmap(trace_gen)(trace_params)   # (N, T_pad)
        return _sim(p, addrs, gaps, t_true, warm_start)

    return run_gen


def build_sim(cfg: FamConfig, flags: SimFlags, num_nodes: int,
              policies: Optional[PolicySet] = None):
    """Returns jitted run(addrs (N,T), gaps (N,T)) -> metrics dict.

    Classic one-system entry point. The dynamic params are passed as traced
    arguments (not closed-over constants) so this path executes the exact
    same floating-point program as the batched ``sweep`` — constant-folding
    a latency into the XLA graph would otherwise make long simulations
    drift measurably from the vmapped run."""
    p = FamParams.of(cfg, flags, policies)
    jitted: Dict = {}

    def run(addrs, gaps, warmup_frac: float = 0.2):
        if warmup_frac not in jitted:
            jitted[warmup_frac] = jax.jit(
                _make_run(cfg, num_nodes, warmup_frac, policies=policies))
        return jitted[warmup_frac](p, addrs, gaps)

    return run


# --------------------------------------------------------------------------
# Batched sweep engine
# --------------------------------------------------------------------------

_SWEEP_CACHE: Dict = {}


def build_sweep(cfg: FamConfig, num_nodes: int, warmup_frac: float = 0.2,
                policies: Optional[PolicySet] = None):
    """Jitted batched runner: fn(params_batch, addrs (S,N,T), gaps (S,N,T))
    -> metrics dict with arrays of shape (S, N).

    One entry per ``(cfg.static_shape(), policy compile tags)`` — every
    sweep point that only varies dynamic parameters (feature flags, block
    size, policy numeric params, and any cache geometry fitting the
    donor's allocation) reuses the same compiled program; jit re-traces
    only when (S, N, T) change shape. Same-tag policies (``fifo``/``wfq``)
    share the entry by construction.
    """
    policies = _resolve(policies)
    key = (cfg.static_shape(), num_nodes, warmup_frac,
           policies.compile_tags())
    if key not in _SWEEP_CACHE:
        run = _make_run(cfg, num_nodes, warmup_frac, policies=policies)
        _SWEEP_CACHE[key] = jax.jit(jax.vmap(run))
    return _SWEEP_CACHE[key]


_MASKED_CACHE: Dict = {}


def build_masked_vmap(cfg: FamConfig, num_nodes: int,
                      pad_sets: Optional[int] = None,
                      pad_ways: Optional[int] = None,
                      trace_gen=None, trace_key=None,
                      policies: Optional[PolicySet] = None):
    """Unjitted vmapped dynamic-T runner:
    fn(params_batch, addrs (S, N, T_pad), gaps, t_true (S,), warm_start (S,))
    -> metrics dict of (S, N) arrays.

    ``pad_sets``/``pad_ways`` size the shared cache allocation (default:
    ``cfg``'s own geometry); each batched system's *effective* geometry is
    its ``FamParams`` scalars and must fit inside the allocation. Left
    unjitted on purpose: the ``repro.experiments`` executor wraps it in
    either a plain ``jax.jit`` (single device) or a ``shard_map`` over the S
    axis (multi-device) and AOT-compiles the result. One entry per
    (geometry-free shape, padded allocation, policy compile tags), like
    :func:`build_sweep`.

    ``trace_gen``/``trace_key``: in-graph trace generation (see
    :func:`_make_run_masked`) — the signature becomes fn(params_batch,
    trace_params (S, N, ...), t_true, warm_start). ``trace_key`` (e.g.
    ``("device", T_pad)``) keys the cache alongside the shapes, since the
    generator bakes in its trace length.
    """
    policies = _resolve(policies)
    key = (cfg.geometry_free_shape(), num_nodes,
           pad_sets or cfg.num_sets, pad_ways or cfg.cache_ways, trace_key,
           policies.compile_tags())
    if key not in _MASKED_CACHE:
        _MASKED_CACHE[key] = jax.vmap(
            _make_run_masked(cfg, num_nodes, pad_sets, pad_ways,
                             trace_gen=trace_gen, policies=policies))
    return _MASKED_CACHE[key]


def sweep(cfg: FamConfig, params_batch: FamParams, flags: Optional[SimFlags],
          addrs, gaps, warmup_frac: float = 0.2,
          policies: Optional[PolicySet] = None) -> Dict[str, jax.Array]:
    """Run S independent simulated systems in one (cached) compile.

    cfg: static shape donor — every system must share
        ``cfg.geometry_free_shape()`` and its effective cache geometry
        must fit inside the donor's allocation (``num_sets``,
        ``cache_ways``). Block size is fully dynamic (traced
        ``block_bits`` address split).
    params_batch: ``FamParams`` with leading axis S (see ``stack_params``);
        every member must share ``policies``' param schema (equal compile
        tags).
    flags: optional ``SimFlags`` applied uniformly to all S systems;
        ``None`` keeps the flags already embedded in ``params_batch``.
    addrs/gaps: (S, N, T) per-system node traces.

    Returns the ``build_sim`` metrics dict with a leading sweep axis (S, N).
    """
    if flags is not None:
        params_batch = params_batch.with_flags(flags)
    for field, cap in (("num_sets", cfg.num_sets),
                       ("cache_ways", cfg.cache_ways)):
        eff = getattr(params_batch, field)
        if not isinstance(eff, jax.core.Tracer) and \
                bool(jnp.any(eff > cap)):
            raise ValueError(
                f"params_batch effective {field} (max "
                f"{int(jnp.max(eff))}) exceeds the static donor's padded "
                f"allocation ({cap}); build the donor from the max swept "
                "geometry (the repro.experiments planner does this "
                "automatically)")
    S, N, T = addrs.shape
    fn = build_sweep(cfg, N, warmup_frac, policies=policies)
    return fn(params_batch, jnp.asarray(addrs), jnp.asarray(gaps))


def simulate(cfg: FamConfig, flags: SimFlags, workload_names, T: int = 60_000,
             seed: int = 0, trace_backend: str = "numpy",
             policies: Optional[PolicySet] = None) -> Dict[str, np.ndarray]:
    """Convenience wrapper: generate traces for the node list and run.

    NOTE the default backend here is ``"numpy"`` — the classic reference
    path — while ``repro.experiments.Experiment`` defaults to
    ``"device"``: comparing this wrapper against an executor run for the
    same point mixes backends (statistically, not bit-, equivalent)
    unless you pass ``trace_backend="device"``, which pre-stages the
    device-generated traces (:mod:`repro.traces.device`) through the same
    classic simulation path — bit-identical to the executor's in-graph
    generation at the same T."""
    from repro.traces import system_traces
    N = len(workload_names)
    addrs, gaps = system_traces(workload_names, T, seed,
                                backend=trace_backend)
    run = build_sim(cfg, flags, N, policies=policies)
    out = run(jnp.asarray(addrs), jnp.asarray(gaps))
    return {k: np.asarray(v) for k, v in out.items()}
