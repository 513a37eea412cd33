"""DRAM cache metadata — set-associative, LRU, sub-page blocks (paper §III-B).

The cache itself is a region of local DRAM; this module manages the
*metadata* (tags + LRU state), exactly like the paper: FAM block addresses
hash into sets, tag compare guards collisions, LRU within the set picks the
victim. ~7 B/block metadata => <5% of cache capacity (paper's 16 MB example).

Functional jnp state -> jit/vmap/scan-safe; the same structure backs both
the simulator and the production ``TieredBlockPool`` (where the "data" lives
in an HBM block pool and slot index = HBM pool slot).

**Padded geometry.** State arrays may be allocated at a *maximum* swept
``(num_sets, ways)`` while the effective geometry rides along as (possibly
traced) ``num_sets``/``ways`` scalars on every operation: the set hash is
taken modulo the effective set count, and lookup/insert/LRU restrict tag
matches, vacancy, and victim selection to the first ``ways`` ways. Because
set indices never reach a padded row and way masks keep writes inside the
effective ways, the padded region stays all-invalid forever and every
operation is **bit-identical** to the same operation on an exactly-sized
state (property-tested in ``tests/test_dram_cache_padded.py``). Passing
``num_sets=None``/``ways=None`` (the default) uses the full array shape —
the classic exact-geometry behaviour.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class CacheState(NamedTuple):
    tags: jax.Array     # (sets, ways) int32: block_addr + 1; 0 = invalid
    lru: jax.Array      # (sets, ways) int32: last-touch stamp
    stamp: jax.Array    # () int32 monotonic counter


def init_cache(num_sets: int, ways: int) -> CacheState:
    return CacheState(tags=jnp.zeros((num_sets, ways), jnp.int32),
                      lru=jnp.zeros((num_sets, ways), jnp.int32),
                      stamp=jnp.zeros((), jnp.int32))


def _set_index(block_addr, num_sets):
    """Set hash modulo the (possibly traced) effective set count."""
    h = (block_addr.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)) >> 7
    mod = jnp.asarray(num_sets).astype(jnp.uint32)
    return (h % mod).astype(jnp.int32)


def _way_mask(state: CacheState, ways):
    """(W_pad,) bool: True for the effective ways (``ways`` may be traced)."""
    return jnp.arange(state.tags.shape[1]) < jnp.asarray(ways)


def gather_row(a, i):
    """Row ``i`` of a 2-D table, read as one element gather per column.

    A plain row gather (``a[i]``) and the element scatters that update
    these tables ask the TPU compiler for different layouts of the array,
    so inside a loop it relayouts the whole array on every read (89 % of
    fig08's compiled step). Read this way, reads and writes share one
    layout; the values are the same."""
    return a[i, jnp.arange(a.shape[1])]


def lookup(state: CacheState, block_addr, num_sets=None, ways=None
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> (hit, set_idx, way). Pure query; no state change.

    ``num_sets``/``ways`` give the effective geometry of a padded state
    (both may be traced scalars); ``None`` uses the full array shape.
    """
    si = _set_index(block_addr,
                    state.tags.shape[0] if num_sets is None else num_sets)
    row = gather_row(state.tags, si)
    match = row == (block_addr.astype(jnp.int32) + 1)
    if ways is not None:
        match = match & _way_mask(state, ways)
    hit = jnp.any(match)
    way = jnp.argmax(match).astype(jnp.int32)
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
    old = state.lru[set_idx, way]
    hit_val = stamp if policy is None else policy.on_hit(old, stamp)
    new_lru = jnp.where(en, hit_val, old)
    return state._replace(lru=state.lru.at[set_idx, way].set(new_lru),
                          stamp=stamp)


def insert(state: CacheState, block_addr, enable=True,
           num_sets=None, ways=None, policy=None
           ) -> Tuple[CacheState, jax.Array, jax.Array]:
    """Fill one block: evict the replacement policy's victim if no vacancy.

    Returns (state, evicted_tag-1 or -1, slot) where slot = set*W_pad + way
    identifies the cache data location (used as HBM pool slot in tiering).
    ``enable`` masks the written values (in-place-friendly, see touch).
    ``num_sets``/``ways`` give the effective geometry of a padded state:
    vacancy and victim selection never consider a padded way.

    ``policy=None`` keeps the classic single-element in-place set-LRU path
    (the pre-policy program, bit for bit). A bound replacement policy
    (``repro.policies.replacement``) switches to the generalized path:
    the policy may age the whole recency row on eviction (SRRIP) and
    chooses the victim way; hit/vacancy handling is shared.
    """
    en = jnp.asarray(enable)
    si = _set_index(block_addr,
                    state.tags.shape[0] if num_sets is None else num_sets)
    row_tags = gather_row(state.tags, si)
    row_lru = gather_row(state.lru, si)
    tag = block_addr.astype(jnp.int32) + 1
    already = row_tags == tag
    vacant = row_tags == 0
    victim_lru = row_lru
    wmask = None
    if ways is not None:
        wmask = _way_mask(state, ways)
        already = already & wmask
        vacant = vacant & wmask
        victim_lru = jnp.where(wmask, row_lru, jnp.iinfo(jnp.int32).max)
    has = jnp.any(already)
    has_vacant = jnp.any(vacant)
    stamp = state.stamp + en.astype(jnp.int32)
    w_pad = state.tags.shape[1]
    if policy is None:
        way = jnp.where(has, jnp.argmax(already),
                        jnp.where(has_vacant, jnp.argmax(vacant),
                                  jnp.argmin(victim_lru))).astype(jnp.int32)
        evicted = jnp.where(en & ~(has | has_vacant), row_tags[way] - 1, -1)
        new = CacheState(
            tags=state.tags.at[si, way].set(jnp.where(en, tag,
                                                      row_tags[way])),
            lru=state.lru.at[si, way].set(jnp.where(en, stamp,
                                                    row_lru[way])),
            stamp=stamp)
        return new, evicted, si * w_pad + way

    if wmask is None:
        wmask = jnp.ones((w_pad,), jnp.bool_)
    eff_ways = jnp.asarray(w_pad if ways is None else ways, jnp.int32)
    aged_row, evict_way = policy.evict(row_lru, wmask, stamp, si, eff_ways)
    way = jnp.where(has, jnp.argmax(already),
                    jnp.where(has_vacant, jnp.argmax(vacant),
                              evict_way)).astype(jnp.int32)
    evicted = jnp.where(en & ~(has | has_vacant), row_tags[way] - 1, -1)
    # aging applies only on the eviction path; hit/vacancy keep the row.
    # A redundant fill of an already-present block is a re-reference —
    # the policy's hit update (promote), never a fresh-insert value
    # (which would DEMOTE a hot line under SRRIP).
    base_row = jnp.where(has | has_vacant, row_lru, aged_row)
    fill_val = jnp.where(has, policy.on_hit(row_lru[way], stamp),
                         policy.insert_value(stamp))
    new_row = base_row.at[way].set(fill_val)
    new = CacheState(
        tags=state.tags.at[si, way].set(jnp.where(en, tag, row_tags[way])),
        lru=state.lru.at[si, jnp.arange(w_pad)].set(
            jnp.where(en, new_row, row_lru)),
        stamp=stamp)
    return new, evicted, si * w_pad + way


def invalidate(state: CacheState, block_addr, num_sets=None, ways=None
               ) -> CacheState:
    hit, si, way = lookup(state, block_addr, num_sets=num_sets, ways=ways)
    tags = jnp.where(hit, state.tags.at[si, way].set(0), state.tags)
    return state._replace(tags=tags)


def occupancy(state: CacheState, num_sets=None, ways=None) -> jax.Array:
    """Fraction of the EFFECTIVE cache entries holding a valid tag.

    The padded region never holds tags (see module docstring), so the sum
    over the full array equals the sum over the effective region, and the
    divisor uses the effective entry count — the quotient is bit-identical
    to ``jnp.mean`` over an exactly-sized state (0/1 partial sums are
    integers, exact in f32 below 2**24 entries).
    """
    filled = (state.tags > 0).astype(jnp.float32)
    if num_sets is None and ways is None:
        return jnp.mean(filled)
    num_sets = state.tags.shape[0] if num_sets is None else num_sets
    ways = state.tags.shape[1] if ways is None else ways
    total = (jnp.asarray(num_sets, jnp.int32) *
             jnp.asarray(ways, jnp.int32)).astype(jnp.float32)
    return jnp.sum(filled) / total
