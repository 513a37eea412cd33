"""DRAM cache metadata — set-associative, LRU, sub-page blocks (paper §III-B).

The cache itself is a region of local DRAM; this module manages the
*metadata* (tags + LRU state), exactly like the paper: FAM block addresses
hash into sets, tag compare guards collisions, LRU within the set picks the
victim. ~7 B/block metadata => <5% of cache capacity (paper's 16 MB example).

Functional jnp state -> jit/vmap/scan-safe; the same structure backs both
the simulator and the production ``TieredBlockPool`` (where the "data" lives
in an HBM block pool and slot index = HBM pool slot).

**Storage.** The tag and recency tables are stored *lane-dense* —
``(sets*ways/128, 128)``, each set's ways a contiguous segment of one
128-lane row — whenever a set's ways tile the row evenly
(``128 % ways == 0`` and ``sets*ways % 128 == 0``); otherwise they keep
the ``(sets, ways)`` shape. The allocation's shape alone decides
(:func:`storage`). Lane-dense tables keep one lanes-minor TPU layout
through the simulator's loop, so a set is read as one 128-lane row
gather and written back into that same layout (the whole row on a fill,
one element on a touch); on the ``(sets, ways)`` shape a row gather next to element scatters makes the
compiler relayout the whole table, so sets are read there one element per
way (:func:`gather_row`). Both storages hold the same elements in the same
(row-major) order: :func:`set_view` is a reshape to ``(sets, ways)``, and
every operation returns the same values on either.

**Padded geometry.** State arrays may be allocated at a *maximum* swept
``(num_sets, ways)`` while the effective geometry rides along as (possibly
traced) ``num_sets``/``ways`` scalars on every operation: the set hash is
taken modulo the effective set count, and lookup/insert/LRU restrict tag
matches, vacancy, and victim selection to the first ``ways`` ways. Because
set indices never reach a padded row and way masks keep writes inside the
effective ways, the padded region stays all-invalid forever and every
operation is **bit-identical** to the same operation on an exactly-sized
state (property-tested in ``tests/test_dram_cache_padded.py``). Passing
``num_sets=None``/``ways=None`` (the default) uses the full allocation —
the classic exact-geometry behaviour.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

#: lanes of one TPU vector register row: a lane-dense table's row width
LANES = 128


@dataclasses.dataclass(frozen=True)
class CacheState:
    tags: jax.Array     # int32 block_addr + 1; 0 = invalid  (see storage)
    lru: jax.Array      # int32 last-touch stamp, same shape as tags
    stamp: jax.Array    # () int32 monotonic counter
    w_pad: int          # allocated ways per set (static)


jax.tree_util.register_dataclass(CacheState,
                                 data_fields=["tags", "lru", "stamp"],
                                 meta_fields=["w_pad"])


def storage(num_sets: int, ways: int) -> str:
    """The tables' storage for an allocation of ``num_sets x ways``:
    ``"lane_dense"`` (``(sets*ways/128, 128)``) when a set's ways tile a
    128-lane row evenly, else ``"sets_ways"`` (``(sets, ways)``)."""
    if LANES % ways == 0 and (num_sets * ways) % LANES == 0:
        return "lane_dense"
    return "sets_ways"


def init_cache(num_sets: int, ways: int) -> CacheState:
    shape = ((num_sets * ways // LANES, LANES)
             if storage(num_sets, ways) == "lane_dense"
             else (num_sets, ways))
    return CacheState(tags=jnp.zeros(shape, jnp.int32),
                      lru=jnp.zeros(shape, jnp.int32),
                      stamp=jnp.zeros((), jnp.int32), w_pad=ways)


def set_view(state: CacheState) -> Tuple[jax.Array, jax.Array]:
    """(tags, lru) as ``(..., sets, ways)`` tables, whatever the storage
    (a reshape: both storages hold the elements in the same order)."""
    shape = state.tags.shape[:-2] + (-1, state.w_pad)
    return state.tags.reshape(shape), state.lru.reshape(shape)


def _num_sets(state: CacheState) -> int:
    return state.tags.shape[-2] * state.tags.shape[-1] // state.w_pad


def _locate(state: CacheState, si):
    """(row, lane offset) of set ``si``'s ways in the stored tables."""
    k = state.tags.shape[-1] // state.w_pad       # sets per stored row
    if k == 1:
        return si, 0
    return si // k, (si % k) * state.w_pad


def _set_index(block_addr, num_sets):
    """Set hash modulo the (possibly traced) effective set count."""
    h = (block_addr.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)) >> 7
    mod = jnp.asarray(num_sets).astype(jnp.uint32)
    return (h % mod).astype(jnp.int32)


def _way_mask(state: CacheState, ways):
    """(W_pad,) bool: True for the effective ways (``ways`` may be traced)."""
    return jnp.arange(state.w_pad) < jnp.asarray(ways)


def gather_row(a, i):
    """Row ``i`` of a 2-D table, read as one element gather per column.

    A plain row gather (``a[i]``) and the element scatters that update
    these tables ask the TPU compiler for different layouts of the array,
    so inside a loop it relayouts the whole array on every read (89 % of
    fig08's compiled step). Read this way, reads and writes share one
    layout; the values are the same."""
    return a[i, jnp.arange(a.shape[1])]


def _read_row(table, row):
    """Stored row ``row`` of ``table`` (tags or lru): one 128-lane row
    gather when lane-dense, one element gather per way on the
    ``(sets, ways)`` shape."""
    if table.shape[-1] != LANES:
        return gather_row(table, row)
    return table[row]


def _own_lanes(state: CacheState, si, ways):
    """Which lanes of set ``si``'s stored row are its effective ways:
    its own ``W_pad`` segment (lane-dense), cut to the first ``ways``;
    ``None`` when that is every lane."""
    lane = jnp.arange(state.tags.shape[-1])
    k = state.tags.shape[-1] // state.w_pad
    own = None if k == 1 else lane // state.w_pad == si % k
    if ways is not None:
        in_ways = lane % state.w_pad < jnp.asarray(ways)
        own = in_ways if own is None else own & in_ways
    return own


def _segment(state: CacheState, row, si):
    """Set ``si``'s ``W_pad`` ways out of its stored ``row``, by a masked
    sum over the row's sets (a lane select, never a gather)."""
    k = state.tags.shape[-1] // state.w_pad
    if k == 1:
        return row
    segs = row.reshape(k, state.w_pad)
    return jnp.sum(jnp.where((jnp.arange(k) == si % k)[:, None], segs, 0),
                   axis=0)


def _pick(row, i):
    """``row[i]`` as a masked sum: a lane select, never a gather."""
    return jnp.sum(jnp.where(jnp.arange(row.shape[-1]) == i, row, 0))


def lookup(state: CacheState, block_addr, num_sets=None, ways=None
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> (hit, set_idx, way). Pure query; no state change.

    ``num_sets``/``ways`` give the effective geometry of a padded state
    (both may be traced scalars); ``None`` uses the full allocation.
    """
    si = _set_index(block_addr,
                    _num_sets(state) if num_sets is None else num_sets)
    row = _read_row(state.tags, _locate(state, si)[0])
    match = row == (block_addr.astype(jnp.int32) + 1)
    own = _own_lanes(state, si, ways)
    if own is not None:
        match = match & own
    hit = jnp.any(match)
    way = (jnp.argmax(match) % state.w_pad).astype(jnp.int32)
    return hit, si, way


def touch(state: CacheState, set_idx, way, enable=True,
          policy=None) -> CacheState:
    """LRU update on a hit (paper: 'the corresponding LRU field is updated').

    ``enable`` masks the write *value* (not the op) so XLA keeps the update
    in place inside loops — no whole-table copies. ``policy`` is a *bound*
    replacement policy (see ``repro.policies.replacement``) supplying the
    hit-time recency value; ``None`` is the classic LRU stamp."""
    en = jnp.asarray(enable)
    stamp = state.stamp + en.astype(jnp.int32)
    r, off = _locate(state, set_idx)
    old = state.lru[r, off + way]
    hit_val = stamp if policy is None else policy.on_hit(old, stamp)
    new_lru = jnp.where(en, hit_val, old)
    return dataclasses.replace(
        state, lru=state.lru.at[r, off + way].set(new_lru), stamp=stamp)


def insert(state: CacheState, block_addr, enable=True,
           num_sets=None, ways=None, policy=None
           ) -> Tuple[CacheState, jax.Array, jax.Array]:
    """Fill one block: evict the replacement policy's victim if no vacancy.

    Returns (state, evicted_tag-1 or -1, slot) where slot = set*W_pad + way
    identifies the cache data location (used as HBM pool slot in tiering).
    ``enable`` masks the written values (in-place-friendly, see touch).
    ``num_sets``/``ways`` give the effective geometry of a padded state:
    vacancy and victim selection never consider a padded way.

    The set's tags and recency are read once each, as the stored rows
    that hold the set; hit, vacancy and the LRU victim are found on the
    set's own lanes of those rows. ``policy=None`` keeps the classic
    in-place set-LRU path (the pre-policy results, bit for bit), writing
    the rows back whole when lane-dense and one element per table on the
    ``(sets, ways)`` shape. A bound replacement policy
    (``repro.policies.replacement``) switches to the generalized path:
    the policy may age the set's whole recency segment on eviction
    (SRRIP) and chooses the victim way; hit/vacancy handling is shared.
    """
    en = jnp.asarray(enable)
    si = _set_index(block_addr,
                    _num_sets(state) if num_sets is None else num_sets)
    r, off = _locate(state, si)
    row_tags = _read_row(state.tags, r)
    row_lru = _read_row(state.lru, r)
    tag = block_addr.astype(jnp.int32) + 1
    own = _own_lanes(state, si, ways)
    already = row_tags == tag
    vacant = row_tags == 0
    if own is not None:
        already = already & own
        vacant = vacant & own
    has = jnp.any(already)
    has_vacant = jnp.any(vacant)
    stamp = state.stamp + en.astype(jnp.int32)
    w_pad = state.w_pad
    if policy is None:
        victim_lru = row_lru if own is None else \
            jnp.where(own, row_lru, jnp.iinfo(jnp.int32).max)
        way = (jnp.where(has, jnp.argmax(already),
                         jnp.where(has_vacant, jnp.argmax(vacant),
                                   jnp.argmin(victim_lru))) % w_pad
               ).astype(jnp.int32)
        old_tag = _pick(row_tags, off + way)
        evicted = jnp.where(en & ~(has | has_vacant), old_tag - 1, -1)
        if state.tags.shape[-1] == LANES:
            # the rows go back whole, the one way replaced: on a v5e that
            # ran fig08's grid 12 % faster than two element scatters
            lane = en & (jnp.arange(LANES) == off + way)
            tags = state.tags.at[r].set(jnp.where(lane, tag, row_tags))
            lru = state.lru.at[r].set(jnp.where(lane, stamp, row_lru))
        else:
            tags = state.tags.at[r, off + way].set(
                jnp.where(en, tag, old_tag))
            lru = state.lru.at[r, off + way].set(
                jnp.where(en, stamp, _pick(row_lru, off + way)))
        new = dataclasses.replace(state, tags=tags, lru=lru, stamp=stamp)
        return new, evicted, si * w_pad + way

    seg_lru = _segment(state, row_lru, si)
    wmask = (jnp.ones((w_pad,), jnp.bool_) if ways is None
             else _way_mask(state, ways))
    eff_ways = jnp.asarray(w_pad if ways is None else ways, jnp.int32)
    aged_row, evict_way = policy.evict(seg_lru, wmask, stamp, si, eff_ways)
    way = jnp.where(has, jnp.argmax(already) % w_pad,
                    jnp.where(has_vacant, jnp.argmax(vacant) % w_pad,
                              evict_way)).astype(jnp.int32)
    old_tag = _pick(row_tags, off + way)
    evicted = jnp.where(en & ~(has | has_vacant), old_tag - 1, -1)
    # aging applies only on the eviction path; hit/vacancy keep the row.
    # A redundant fill of an already-present block is a re-reference —
    # the policy's hit update (promote), never a fresh-insert value
    # (which would DEMOTE a hot line under SRRIP).
    base_row = jnp.where(has | has_vacant, seg_lru, aged_row)
    fill_val = jnp.where(has, policy.on_hit(_pick(seg_lru, way), stamp),
                         policy.insert_value(stamp))
    new_row = base_row.at[way].set(fill_val)
    new = dataclasses.replace(
        state,
        tags=state.tags.at[r, off + way].set(jnp.where(en, tag, old_tag)),
        lru=state.lru.at[r, off + jnp.arange(w_pad)].set(
            jnp.where(en, new_row, seg_lru)),
        stamp=stamp)
    return new, evicted, si * w_pad + way


def invalidate(state: CacheState, block_addr, num_sets=None, ways=None
               ) -> CacheState:
    hit, si, way = lookup(state, block_addr, num_sets=num_sets, ways=ways)
    r, off = _locate(state, si)
    tags = jnp.where(hit, state.tags.at[r, off + way].set(0), state.tags)
    return dataclasses.replace(state, tags=tags)


def occupancy(state: CacheState, num_sets=None, ways=None) -> jax.Array:
    """Fraction of the EFFECTIVE cache entries holding a valid tag.

    The padded region never holds tags (see module docstring), so the sum
    over the full array equals the sum over the effective region, and the
    divisor is the effective entry count — one division of the same two
    numbers whatever the allocation, so the quotient is bit-identical to
    the one of an exactly-sized state (0/1 partial sums are integers,
    exact in f32 below 2**24 entries). ``jnp.mean`` is not used: it may
    multiply by the reciprocal instead, which rounds differently.
    """
    tags, _ = set_view(state)
    num_sets = tags.shape[0] if num_sets is None else num_sets
    ways = tags.shape[1] if ways is None else ways
    total = (jnp.asarray(num_sets, jnp.int32) *
             jnp.asarray(ways, jnp.int32)).astype(jnp.float32)
    return jnp.sum((tags > 0).astype(jnp.float32)) / total
