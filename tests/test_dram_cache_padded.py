"""Masked-geometry dram_cache: every operation on a state padded to a
larger ``(num_sets, ways)`` allocation — with the effective geometry passed
as scalars — must be bit-identical to the same operation on an exactly
sized state, across randomized insert/touch/invalidate/occupancy sequences
(the foundation of the planner's one-group-per-figure guarantee), and the
padded region must stay invalid forever. The padded state runs on either
storage of the tables (lane-dense or ``(sets, ways)``), compared through
``dc.set_view``."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dram_cache as dc

# (sets, ways, pad_sets, pad_ways)
TINY = [
    (4, 2, 4, 2),          # no padding: kwargs must be pure identity
    (4, 2, 16, 2),         # padded sets only
    (8, 4, 8, 8),          # padded ways only
    (1, 1, 8, 4),          # degenerate direct-mapped single set
]
# allocations whose sets tile 128-lane rows: init_cache stores them
# lane-dense, and the same geometry can be held as (sets, ways) too
ELIGIBLE = [
    (40, 12, 64, 16),      # both padded, as the figure grids pad
    (2, 2, 64, 16),        # both, heavily
    (64, 16, 64, 16),      # no padding
    (5, 3, 32, 4),         # 32 sets to a row
]
GEOMETRIES = {"sets_ways": TINY + ELIGIBLE, "lane_dense": ELIGIBLE}


def sets_ways_state(num_sets, ways):
    """An empty cache held as ``(sets, ways)`` tables whatever its shape
    (init_cache would store an eligible allocation lane-dense)."""
    z = jnp.zeros((num_sets, ways), jnp.int32)
    return dc.CacheState(tags=z, lru=z, stamp=jnp.zeros((), jnp.int32),
                         w_pad=ways)


def _padded_state(storage, pad_sets, pad_ways):
    if storage == "lane_dense":
        st_ = dc.init_cache(pad_sets, pad_ways)
        assert st_.tags.shape == (pad_sets * pad_ways // 128, 128)
        return st_
    return sets_ways_state(pad_sets, pad_ways)


def _run_sequence(sets, ways, pad_sets, pad_ways, ops, storage="sets_ways"):
    """Drive exact and padded states through one op sequence, asserting
    every returned value and the effective state region match bit-for-bit
    after each op."""
    exact = sets_ways_state(sets, ways)
    padded = _padded_state(storage, pad_sets, pad_ways)
    kw = dict(num_sets=sets, ways=ways)

    def check(tag):
        assert padded.tags.shape == padded.lru.shape
        e_tags, e_lru = map(np.asarray, dc.set_view(exact))
        p_tags, p_lru = map(np.asarray, dc.set_view(padded))
        np.testing.assert_array_equal(e_tags, p_tags[:sets, :ways], tag)
        np.testing.assert_array_equal(e_lru, p_lru[:sets, :ways], tag)
        assert int(exact.stamp) == int(padded.stamp), tag
        # the padded region must never acquire a tag
        mask = np.ones_like(p_tags, bool)
        mask[:sets, :ways] = False
        assert (p_tags[mask] == 0).all(), tag

    for op, addr in ops:
        a = jnp.int32(addr)
        if op == "insert":
            exact, ev_e, _ = dc.insert(exact, a)
            padded, ev_p, _ = dc.insert(padded, a, **kw)
            assert int(ev_e) == int(ev_p), (op, addr)
        elif op == "probe":           # lookup + LRU touch on hit
            hit_e, si_e, way_e = dc.lookup(exact, a)
            hit_p, si_p, way_p = dc.lookup(padded, a, **kw)
            assert (bool(hit_e), int(si_e)) == (bool(hit_p), int(si_p))
            if bool(hit_e):
                assert int(way_e) == int(way_p), (op, addr)
            exact = dc.touch(exact, si_e, way_e, enable=hit_e)
            padded = dc.touch(padded, si_p, way_p, enable=hit_p)
        elif op == "invalidate":
            exact = dc.invalidate(exact, a)
            padded = dc.invalidate(padded, a, **kw)
        occ_e = dc.occupancy(exact)
        occ_p = dc.occupancy(padded, **kw)
        # bitwise-equal floats: same sum, same effective-entry divisor
        assert np.float32(occ_e) == np.float32(occ_p), (op, addr)
        check((op, addr))


@pytest.mark.parametrize("storage", ["sets_ways", "lane_dense"])
@settings(max_examples=10, deadline=None)
@given(data=st.data())
def test_padded_equals_exact_random_sequences(storage, data):
    seed = data.draw(st.integers(0, 10_000))
    sets, ways, pad_sets, pad_ways = data.draw(
        st.sampled_from(GEOMETRIES[storage]))
    rng = np.random.default_rng(seed)
    # enough distinct addresses to force evictions in every set
    n_addr = 4 * sets * ways + 8
    ops = []
    for _ in range(40):
        kind = ["insert", "insert", "probe", "invalidate"][rng.integers(4)]
        ops.append((kind, int(rng.integers(0, n_addr))))
    _run_sequence(sets, ways, pad_sets, pad_ways, ops, storage)


@pytest.mark.parametrize("pad_sets", [1, 16])
def test_eviction_ignores_padded_ways(pad_sets):
    """A full effective set must evict its LRU member even when padded
    ways sit empty next to it (vacancy must not leak into the padding) —
    on a one-set allocation and on a lane-dense one of 16 sets."""
    st_ = dc.init_cache(pad_sets, 8)  # padded to 8 ways
    assert dc.storage(pad_sets, 8) == ("lane_dense" if pad_sets == 16
                                       else "sets_ways")
    kw = dict(num_sets=1, ways=2)    # effective: 1 set, 2 ways
    st_, _, _ = dc.insert(st_, jnp.int32(1), **kw)
    st_, _, _ = dc.insert(st_, jnp.int32(2), **kw)
    hit, si, way = dc.lookup(st_, jnp.int32(1), **kw)
    st_ = dc.touch(st_, si, way, enable=hit)      # 2 becomes LRU
    st_, evicted, _ = dc.insert(st_, jnp.int32(3), **kw)
    assert int(evicted) == 2
    assert (np.asarray(dc.set_view(st_)[0])[:, 2:] == 0).all()


@pytest.mark.parametrize("num_sets,ways,storage,shape", [
    (16384, 16, "lane_dense", (2048, 128)),     # fig08's allocation
    (4096, 16, "lane_dense", (512, 128)),
    (64, 2, "lane_dense", (1, 128)),
    (1, 128, "lane_dense", (1, 128)),
    (4, 2, "sets_ways", (4, 2)),                # under one row
    (40, 12, "sets_ways", (40, 12)),            # 12 does not divide 128
    (8, 256, "sets_ways", (8, 256)),            # wider than a row
])
def test_init_cache_picks_storage_by_shape(num_sets, ways, storage, shape):
    """The allocation's (sets, ways) alone decides the storage; the
    logical view is (sets, ways) either way."""
    st_ = dc.init_cache(num_sets, ways)
    assert dc.storage(num_sets, ways) == storage
    assert st_.tags.shape == st_.lru.shape == shape
    assert st_.w_pad == ways
    tags, lru = dc.set_view(st_)
    assert tags.shape == lru.shape == (num_sets, ways)


def test_set_hash_modulo_effective_sets():
    """Addresses must map to the same set whether the modulus comes from
    the array shape (exact) or a traced-style scalar (padded)."""
    for n in (1, 2, 5, 64, 4096):
        a = jnp.arange(0, 10_000, 37, dtype=jnp.int32)
        exact = dc._set_index(a, n)
        dyn = dc._set_index(a, jnp.int32(n))
        np.testing.assert_array_equal(np.asarray(exact), np.asarray(dyn))
        assert int(jnp.max(dyn)) < n
