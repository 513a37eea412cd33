"""TieredBlockPool — the paper's DRAM-cache/prefetch mechanism as a
first-class runtime feature (DESIGN.md §2c).

Two storage regions hold fixed-size blocks (KV pages, expert slabs,
optimizer slabs):

* fast region — HBM-resident pool of ``fast_blocks`` slots (the "DRAM
  cache"; slot == cache data location, managed by
  ``repro.core.dram_cache`` set-associative metadata);
* slow region — the pooled/"FAM" tier holding every block (source of
  truth; host memory on a real TPU deployment).

``access(ids)`` is fully traced: demand misses are copied slow->fast
(eviction via set-LRU), the SPP engine trains on the block-id stream and
prefetches predicted blocks through a bounded in-flight window, and a DWRR
schedule arbitrates demand vs prefetch copy issue per step (the paper's
WFQ-at-the-memory-node, applied at the copy-engine issue point). Reads then
gather from the fast region — the Pallas ``block_gather``/
``paged_attention`` kernels consume exactly this layout.

Correctness property (tested): reads through the tier == direct reads of
the slow region, for any access stream.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import FamConfig
from repro.core import dram_cache as dc
from repro.core import spp as spp_lib
from repro.core.wfq import DEMAND, PREFETCH, WfqState, init_wfq, schedule_batch
from repro.kernels.block_gather.kernel import block_gather
from repro.kernels.cache_lookup.kernel import cache_lookup
from repro.kernels.cache_lookup.ref import cache_lookup_ref


class TierState(NamedTuple):
    fast: jax.Array            # (fast_blocks, block_elems) fast-tier storage
    slot_of_block: jax.Array   # (num_blocks,) int32 fast slot or -1
    block_of_slot: jax.Array   # (fast_blocks,) int32 resident block or -1
    cache: dc.CacheState       # set-assoc metadata over block ids
    spp: spp_lib.SppState
    wfq: WfqState
    # telemetry
    demand_misses: jax.Array
    hits: jax.Array
    prefetches: jax.Array
    prefetch_hits: jax.Array


class TieredBlockPool:
    """Functional manager; all methods return (new_state, ...)."""

    def __init__(self, cfg: FamConfig, num_blocks: int, fast_blocks: int,
                 block_elems: int, *, page_span: int = 16,
                 prefetch_degree: Optional[int] = None,
                 wfq_weight: Optional[int] = None, dtype=jnp.bfloat16):
        assert fast_blocks % cfg.cache_ways == 0
        self.cfg = cfg
        self.num_blocks = num_blocks
        self.fast_blocks = fast_blocks
        self.block_elems = block_elems
        self.page_span = page_span          # blocks per "page" for SPP
        self.degree = prefetch_degree or cfg.prefetch_degree
        self.weight = cfg.wfq_weight if wfq_weight is None else wfq_weight
        self.dtype = dtype
        self.num_sets = fast_blocks // cfg.cache_ways

    # -- construction -------------------------------------------------------
    def init(self, slow: jax.Array) -> TierState:
        assert slow.shape == (self.num_blocks, self.block_elems), slow.shape
        f0 = jnp.zeros((), jnp.float32)
        return TierState(
            fast=jnp.zeros((self.fast_blocks, self.block_elems), self.dtype),
            slot_of_block=jnp.full((self.num_blocks,), -1, jnp.int32),
            block_of_slot=jnp.full((self.fast_blocks,), -1, jnp.int32),
            cache=dc.init_cache(self.num_sets, self.cfg.cache_ways),
            spp=spp_lib.init_spp(self.cfg), wfq=init_wfq(),
            demand_misses=f0, hits=f0, prefetches=f0, prefetch_hits=f0)

    # -- internals -----------------------------------------------------------
    def _fill(self, st: TierState, slow: jax.Array, block_id,
              enable=True) -> TierState:
        """Copy one block slow->fast, evicting the set-LRU victim.

        ``enable`` masks written values (in-place-friendly — no cond, so XLA
        never copies the fast pool or metadata tables). The pool always runs
        its EXACT geometry — the static ``num_sets``/``ways`` passed to the
        cache ops fold the masked-geometry arithmetic away; padding is a
        simulator-planner concern, not a runtime one."""
        en = jnp.asarray(enable)
        cache, evicted, slot = dc.insert(st.cache, block_id, enable=en,
                                         num_sets=self.num_sets,
                                         ways=self.cfg.cache_ways)
        slot_of_block = st.slot_of_block
        ev_idx = jnp.maximum(evicted, 0)
        slot_of_block = slot_of_block.at[ev_idx].set(
            jnp.where(evicted >= 0, -1, slot_of_block[ev_idx]))
        slot_of_block = slot_of_block.at[block_id].set(
            jnp.where(en, slot, slot_of_block[block_id]))
        block_of_slot = st.block_of_slot.at[slot].set(
            jnp.where(en, block_id, st.block_of_slot[slot]))
        data = jnp.where(en, slow[block_id].astype(self.dtype),
                         st.fast[slot])
        fast = jax.lax.dynamic_update_slice(st.fast, data[None], (slot, 0))
        return st._replace(fast=fast, cache=cache,
                           slot_of_block=slot_of_block,
                           block_of_slot=block_of_slot)

    def _maybe_fill(self, st: TierState, slow, block_id, do) -> TierState:
        return self._fill(st, slow, block_id, enable=do)

    # -- the demand/prefetch flow (paper Fig. 7) -----------------------------
    def access(self, st: TierState, slow: jax.Array, ids: jax.Array,
               *, prefetch: bool = True) -> Tuple[TierState, jax.Array]:
        """Ensure residency for ``ids`` (K,) and return their fast slots.

        Demand misses fill immediately (blocking copy — the latency the
        prefetcher exists to hide); then SPP-predicted blocks are prefetched
        subject to DWRR arbitration against the step's demand count.
        """
        K = ids.shape[0]
        cfg = self.cfg

        def demand_one(st, bid):
            hit, si, way = dc.lookup(st.cache, bid, num_sets=self.num_sets,
                                     ways=cfg.cache_ways)
            st = jax.lax.cond(hit, lambda s: s._replace(
                cache=dc.touch(s.cache, si, way)), lambda s: s, st)
            st = self._maybe_fill(st, slow, bid, ~hit)
            st = st._replace(
                hits=st.hits + hit.astype(jnp.float32),
                demand_misses=st.demand_misses + (~hit).astype(jnp.float32),
                prefetch_hits=st.prefetch_hits + hit.astype(jnp.float32))
            return st, ~hit

        def scan_demand(st, bid):
            st, miss = demand_one(st, bid)
            return st, miss

        st, misses = jax.lax.scan(scan_demand, st, ids)

        if prefetch:
            # train SPP on the block stream; "page" = page_span blocks
            def train(st, bid):
                page = bid // self.page_span
                blk = bid % self.page_span
                spp, sig = spp_lib.update(cfg, st.spp, page, blk)
                return st._replace(spp=spp), (page, blk, sig)

            st, (pages, blks, sigs) = jax.lax.scan(train, st, ids)

            cand, valid = spp_lib.predict(
                cfg, st.spp, pages[-1], blks[-1], sigs[-1], self.degree,
                bpp=self.page_span)
            cand = jnp.clip(cand, 0, self.num_blocks - 1)

            # DWRR arbitration: this step's demand copies vs prefetch copies
            n_demand = jnp.sum(misses.astype(jnp.int32))
            n_pf = jnp.sum(valid.astype(jnp.int32))
            wfq, order = schedule_batch(
                st.wfq, n_demand, n_pf, weight=self.weight,
                quantum=cfg.wfq_quantum, max_deficit=cfg.wfq_max_deficit,
                r=1, max_issues=self.degree + K)
            granted = jnp.sum((order == PREFETCH).astype(jnp.int32))
            st = st._replace(wfq=wfq)

            def pf_one(st, xs):
                bid, v, rank = xs
                fresh = ~dc.lookup(st.cache, bid, num_sets=self.num_sets,
                                   ways=cfg.cache_ways)[0]
                do = v & fresh & (rank < granted)
                st = self._maybe_fill(st, slow, bid, do)
                return st._replace(
                    prefetches=st.prefetches + do.astype(jnp.float32)), None

            ranks = jnp.cumsum(valid.astype(jnp.int32)) - 1
            st, _ = jax.lax.scan(pf_one, st, (cand, valid, ranks))

        hit, _, kslot = self.probe(st, ids)
        # every demand id was just filled, so the metadata probe resolves
        # them all; the side table only backs up a (never-taken) miss
        slots = jnp.where(hit, kslot, st.slot_of_block[ids])
        return st, slots

    def probe(self, st: TierState, ids: jax.Array):
        """Batched residency probe over the set-assoc metadata: the
        paper's Fig. 6 retrieval (hash -> tag row -> compare), returning
        (hit, way, slot) per id with slot = set*ways + way = the fast-
        pool data slot. ``cfg.kernel_backend`` routes it through the
        Pallas ``cache_lookup`` kernel (one VMEM-staged tag row per
        probe; interpreted off-TPU) or the pure-XLA reference — bit-
        identical either way (tests/test_kernels.py)."""
        ids = ids.astype(jnp.int32)
        tags, _ = dc.set_view(st.cache)
        if self.cfg.kernel_backend == "pallas":
            return cache_lookup(tags, ids,
                                interpret=jax.default_backend() != "tpu")
        return cache_lookup_ref(tags, ids)

    def read(self, st: TierState, slots: jax.Array) -> jax.Array:
        """Gather blocks from the fast region. ``cfg.kernel_backend``
        routes through the Pallas ``block_gather`` kernel (streams one
        pool block per grid cell HBM->VMEM via scalar-prefetched slot
        indices) or a plain XLA gather — bit-identical either way."""
        if self.cfg.kernel_backend == "pallas":
            return block_gather(st.fast, slots.astype(jnp.int32),
                                interpret=jax.default_backend() != "tpu")
        return st.fast[slots]

    def hit_rate(self, st: TierState) -> jax.Array:
        total = st.hits + st.demand_misses
        return st.hits / jnp.maximum(total, 1.0)
