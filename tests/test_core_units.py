"""Unit + property tests for the paper's core mechanisms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import FamConfig, fam_replace
from repro.core import dram_cache as dc
from repro.core import prefetch_queue as pq
from repro.core import spp as spp_lib
from repro.core import wfq
from repro.core.throttle import init_throttle, maybe_adapt, observe

CFG = FamConfig()


# ---------------------------------------------------------------------------
# SPP
# ---------------------------------------------------------------------------

def _train_seq(cfg, s, page, blocks):
    sig = jnp.int32(0)
    for b in blocks:
        s, sig = spp_lib.update(cfg, s, jnp.int32(page), jnp.int32(b))
    return s, sig


def test_spp_learns_stride():
    cfg = CFG
    s = spp_lib.init_spp(cfg)
    s, sig = _train_seq(cfg, s, 7, [0, 2, 4, 6, 8, 10])
    blocks, valid = spp_lib.predict(cfg, s, jnp.int32(7), jnp.int32(10), sig,
                                    4, bpp=64)
    got = np.asarray(blocks)[np.asarray(valid)]
    assert len(got) >= 2
    np.testing.assert_array_equal(got[:2] % 64, [12, 14])


def test_spp_signature_formula():
    """signature = (sig << 4) ^ delta, masked — matches the paper's example
    structure (delta updates compound)."""
    cfg = CFG
    s = spp_lib.init_spp(cfg)
    s, sig1 = _train_seq(cfg, s, 3, [1])
    s, sig2 = _train_seq(cfg, s, 3, [3])      # delta 2
    mask = (1 << cfg.spp_signature_bits) - 1
    assert int(sig2) == ((int(sig1) << 4) ^ 2) & mask


def test_spp_prediction_stays_in_page():
    cfg = CFG
    s = spp_lib.init_spp(cfg)
    s, sig = _train_seq(cfg, s, 1, [56, 58, 60, 62])
    blocks, valid = spp_lib.predict(cfg, s, jnp.int32(1), jnp.int32(62), sig,
                                    4, bpp=64)
    got = np.asarray(blocks)[np.asarray(valid)]
    assert all(0 <= b % 64 < 64 for b in got)
    assert all(b // 64 == 1 for b in got)


# ---------------------------------------------------------------------------
# address decomposition (static vs traced-geometry forms)
# ---------------------------------------------------------------------------

def test_dyn_address_decomposition_matches_static():
    """The dyn_* helpers (traced block_bits) must compute the exact same
    integers as the classic static-int decomposition, for every swept
    block size — the foundation of the dynamic-geometry compile sharing."""
    from repro.core import addresses as ad
    addr = jnp.arange(0, 1 << 20, 4097, dtype=jnp.int32)
    for bb_bytes in (64, 128, 256, 512, 1024, 4096):
        bits = ad.block_bits(bb_bytes)
        dyn_bits = ad.dyn_block_bits(jnp.int32(bb_bytes))
        assert int(dyn_bits) == bits
        assert int(ad.dyn_blocks_per_page(dyn_bits)) == \
            ad.blocks_per_page(bb_bytes)
        page_s, blk_s = ad.split(addr, bb_bytes)
        page_d, blk_d = ad.dyn_split(addr, dyn_bits)
        np.testing.assert_array_equal(np.asarray(page_s), np.asarray(page_d))
        np.testing.assert_array_equal(np.asarray(blk_s), np.asarray(blk_d))
        np.testing.assert_array_equal(
            np.asarray(ad.block_addr(addr, bb_bytes)),
            np.asarray(ad.dyn_block_addr(addr, dyn_bits)))


# ---------------------------------------------------------------------------
# DRAM cache
# ---------------------------------------------------------------------------

#: per storage, an allocation init_cache stores that way
CACHE_GEOM = {"sets_ways": (4, 2), "lane_dense": (64, 16)}


@pytest.mark.parametrize("storage", sorted(CACHE_GEOM))
def test_cache_insert_lookup_lru(storage):
    st = dc.init_cache(*CACHE_GEOM[storage])
    assert dc.storage(*CACHE_GEOM[storage]) == storage
    st, ev, slot = dc.insert(st, jnp.int32(10))
    assert int(ev) == -1
    hit, si, way = dc.lookup(st, jnp.int32(10))
    assert bool(hit)
    assert int(slot) == int(si) * st.w_pad + int(way)
    assert int(dc.set_view(st)[0][si, way]) == 11
    hit2, _, _ = dc.lookup(st, jnp.int32(11))
    assert not bool(hit2)


@pytest.mark.parametrize("storage", ["sets_ways", "lane_dense"])
def test_cache_lru_eviction_order(storage):
    # one set, two ways: the whole allocation, or the one effective set
    # of a lane-dense allocation of 64 two-way sets
    if storage == "sets_ways":
        st, kw = dc.init_cache(1, 2), {}
    else:
        st, kw = dc.init_cache(64, 2), dict(num_sets=1)
    assert dc.storage(*dc.set_view(st)[0].shape) == storage
    st, _, _ = dc.insert(st, jnp.int32(1), **kw)
    st, _, _ = dc.insert(st, jnp.int32(2), **kw)
    # touch 1 so 2 becomes LRU
    hit, si, way = dc.lookup(st, jnp.int32(1), **kw)
    st = dc.touch(st, si, way)
    st, evicted, _ = dc.insert(st, jnp.int32(3), **kw)
    assert int(evicted) == 2


@pytest.mark.parametrize("storage,geom", [("sets_ways", (8, 4)),
                                          ("lane_dense", (32, 4))])
@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000))
def test_cache_never_duplicates(storage, geom, seed):
    assert dc.storage(*geom) == storage
    rng = np.random.default_rng(seed)
    st_ = dc.init_cache(*geom)
    for a in rng.integers(0, 100, 60):
        st_, _, _ = dc.insert(st_, jnp.int32(int(a)))
        tags = np.asarray(dc.set_view(st_)[0]).ravel()
        tags = tags[tags > 0]
        assert len(set(tags.tolist())) == len(tags)


# ---------------------------------------------------------------------------
# prefetch queue
# ---------------------------------------------------------------------------

def test_prefetch_queue_roundtrip():
    q = pq.init_queue(4)
    q, ok = pq.try_insert(q, jnp.int32(5), jnp.float32(100.0))
    assert bool(ok)
    inflight, fin = pq.contains(q, jnp.int32(5))
    assert bool(inflight) and float(fin) == 100.0
    q, blocks, done = pq.complete_until(q, jnp.float32(150.0))
    assert bool(done.any()) and int(pq.occupancy(q)) == 0


def test_prefetch_queue_full_rejects():
    q = pq.init_queue(2)
    q, ok1 = pq.try_insert(q, jnp.int32(1), jnp.float32(10.0))
    q, ok2 = pq.try_insert(q, jnp.int32(2), jnp.float32(10.0))
    q, ok3 = pq.try_insert(q, jnp.int32(3), jnp.float32(10.0))
    assert bool(ok1) and bool(ok2) and not bool(ok3)


# ---------------------------------------------------------------------------
# WFQ / DWRR (Algorithm 1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("W", [1, 2, 3])
def test_wfq_ratio_under_saturation(W):
    """Both queues saturated -> demands:prefetches ~ W:1 in byte-cost terms
    (prefetch deficit is charged r per issue)."""
    st_ = wfq.init_wfq()
    r = 4
    counts = {wfq.DEMAND: 0, wfq.PREFETCH: 0}
    for _ in range(3000):
        st_, c = wfq.issue(st_, jnp.bool_(True), jnp.bool_(True),
                           weight=W, quantum=1, max_deficit=8, r=r)
        counts[int(c)] = counts.get(int(c), 0) + 1
    # cost-weighted service ratio: demands get ~W/(W+1) of issue slots;
    # prefetches are further limited by the r-deficit charge
    ratio = counts[wfq.DEMAND] / max(counts[wfq.PREFETCH], 1)
    assert ratio > W, (W, counts)


def test_wfq_work_conserving():
    """Never IDLE when any queue is non-empty."""
    st_ = wfq.init_wfq()
    for i in range(50):
        st_, c = wfq.issue(st_, jnp.bool_(i % 2 == 0), jnp.bool_(i % 2 == 1),
                           weight=2, r=4)
        assert int(c) != wfq.IDLE


def test_wfq_prefetch_only_progress():
    st_ = wfq.init_wfq()
    served = 0
    for _ in range(100):
        st_, c = wfq.issue(st_, jnp.bool_(False), jnp.bool_(True),
                           weight=3, r=4)
        served += int(c) == wfq.PREFETCH
    assert served == 100   # work conservation: all slots serve prefetch


def test_schedule_batch_empty_demand_queue():
    """Prefetch-only backlog: work conservation serves every prefetch,
    never emits a DEMAND, and goes IDLE once the backlog drains."""
    st_, order = wfq.schedule_batch(wfq.init_wfq(), jnp.int32(0),
                                    jnp.int32(5), weight=2, max_issues=16)
    order = np.asarray(order)
    assert (order != wfq.DEMAND).all()
    assert (order == wfq.PREFETCH).sum() == 5
    # backlog exhausted -> IDLE for the rest of the batch
    last_pf = np.max(np.nonzero(order == wfq.PREFETCH)[0])
    assert (order[last_pf + 1:] == wfq.IDLE).all()


def test_schedule_batch_empty_prefetch_queue():
    st_, order = wfq.schedule_batch(wfq.init_wfq(), jnp.int32(7),
                                    jnp.int32(0), weight=3, max_issues=16)
    order = np.asarray(order)
    assert (order != wfq.PREFETCH).all()
    assert (order == wfq.DEMAND).sum() == 7
    assert (order[7:] == wfq.IDLE).all()


def test_schedule_batch_weight1_serves_both_classes():
    """weight=1: half the rounds prefer prefetches — the drained order
    must interleave the classes (no starvation window beyond the W+1
    round cycle x the r-deficit replenish period)."""
    st_, order = wfq.schedule_batch(wfq.init_wfq(), jnp.int32(64),
                                    jnp.int32(64), weight=1, max_issues=64)
    order = np.asarray(order)
    assert (order != wfq.IDLE).all()             # both backlogged: no idle
    d = (order == wfq.DEMAND).sum()
    p = (order == wfq.PREFETCH).sum()
    assert d + p == 64 and d >= p > 0
    # demands dominate by at most the byte-cost ratio r under weight=1
    assert d / p <= 4 + 1


def test_schedule_batch_deficit_round_robin_fairness():
    """Long saturated batch, weight=2: the prefetch deficit replenishes
    every (W+1)-round window, so the gap between consecutive PREFETCH
    issues is bounded by 2*(W+1) — deficit exhaustion round-robins, it
    never starves the prefetch class."""
    W = 2
    st_, order = wfq.schedule_batch(wfq.init_wfq(), jnp.int32(64),
                                    jnp.int32(64), weight=W, max_issues=64)
    order = np.asarray(order)
    assert (order != wfq.IDLE).all()
    pf_slots = np.nonzero(order == wfq.PREFETCH)[0]
    assert len(pf_slots) >= 64 // (2 * (W + 1)) - 1
    gaps = np.diff(pf_slots)
    assert gaps.max() <= 2 * (W + 1), (pf_slots, order.tolist())
    # consumed counts match the order's accounting
    assert (order == wfq.DEMAND).sum() + len(pf_slots) == 64


# ---------------------------------------------------------------------------
# throttle (MIMD/RED)
# ---------------------------------------------------------------------------

def test_throttle_decreases_under_congestion_increases_when_clear():
    cfg = fam_replace(CFG, sample_interval=4)
    s = init_throttle(cfg)
    base = float(s.min_latency)
    # congested: latency 2x the floor
    for _ in range(8):
        s = observe(s, jnp.float32(2.0 * base), jnp.bool_(True),
                    jnp.bool_(False), jnp.int32(1))
        s = maybe_adapt(cfg, s)
    assert float(s.issue_rate) < 1.0
    low = float(s.issue_rate)
    # clear: latency at the floor
    for _ in range(40):
        s = observe(s, jnp.float32(base), jnp.bool_(True), jnp.bool_(False),
                    jnp.int32(1))
        s = maybe_adapt(cfg, s)
    assert float(s.issue_rate) > low


def test_throttle_rate_bounds():
    cfg = fam_replace(CFG, sample_interval=2)
    s = init_throttle(cfg)
    for _ in range(100):
        s = observe(s, jnp.float32(1e6), jnp.bool_(True), jnp.bool_(False),
                    jnp.int32(1))
        s = maybe_adapt(cfg, s)
    assert cfg.min_issue_rate <= float(s.issue_rate) <= 1.0


# ---------------------------------------------------------------------------
# System-level invariants (hypothesis over simulator configs)
# ---------------------------------------------------------------------------

def test_all_local_beats_fam_configs():
    """Invariant: the all-local configuration upper-bounds every FAM config
    (local DRAM is strictly faster than the pooled tier)."""
    from repro.core.famsim import SimFlags, simulate
    cfg = CFG
    wl = ["LU", "canneal"]
    local = simulate(cfg, SimFlags(all_local=True), wl, T=3000)
    for fl in (SimFlags(), SimFlags(core_prefetch=False, dram_prefetch=False),
               SimFlags(wfq=True)):
        out = simulate(cfg, fl, wl, T=3000)
        assert (out["ipc"] <= local["ipc"] + 1e-3).all(), fl


def test_prefetching_never_breaks_correctness_counters():
    """Counters stay consistent: hits <= FAM demands, prefetch issue counts
    are non-negative, hit fractions in [0, 1]."""
    from repro.core.famsim import SimFlags, simulate
    out = simulate(CFG, SimFlags(bw_adapt=True), ["bfs", "mg"], T=4000)
    assert (out["demand_hit_fraction"] >= 0).all()
    assert (out["demand_hit_fraction"] <= 1).all()
    assert (out["corepf_hit_fraction"] <= 1).all()
    assert (out["prefetches_issued"] >= 0).all()
    assert (out["issue_rate"] >= CFG.min_issue_rate - 1e-6).all()


def test_single_node_prefetch_gain_positive_on_streams():
    """On a streaming workload with no contention, DRAM-cache prefetching
    must help (the paper's 1-node result)."""
    from repro.core.famsim import SimFlags, simulate
    base = simulate(CFG, SimFlags(core_prefetch=False, dram_prefetch=False),
                    ["603.bwaves_s"], T=6000)
    pf = simulate(CFG, SimFlags(), ["603.bwaves_s"], T=6000)
    assert pf["ipc"][0] > base["ipc"][0] * 1.1
    assert pf["fam_latency"][0] < base["fam_latency"][0]
