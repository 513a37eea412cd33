# analysis-scope: jit
"""Pallas kernel: the fused per-event DRAM-cache step (metadata path).

One ``pallas_call`` per event does, for every node of every vmapped
system, everything the pure-XLA path spreads over ~15 gather/scatter
ops: C sequential prefetch-fill inserts (vacancy scan + LRU/SRRIP victim
selection + row update), the demand probe with its recency touch, and P
pure redundancy probes — all against the padded ``(sets, ways)`` int32
tag/recency arrays, with the *effective* geometry arriving as traced
scalars (set hash modulo ``num_sets``, way ops masked to the first
``ways`` lanes — the padded region is never read as valid and never
written, exactly like ``repro.core.dram_cache``).

The replacement policy is a STATIC compile tag: ``mode="lru"`` is the
classic stamp-LRU, ``mode="srrip"`` the 2-bit-RRPV path (hit -> 0,
insert at ``max_rrpv - 1``, victim = aged max-RRPV way). ``random``
replacement needs threefry and stays XLA-only (``ops.cache_step``
raises). Booleans cross the kernel boundary as int32.

Layout. The kernel runs over a grid of B caches (B = every vmapped
node x system). Each grid step holds one cache's tag and recency arrays
in VMEM; the scalars — stamp, fills and their enables, demand and its
enable, probes, the effective geometry, and the three small outputs —
live in SMEM as flat whole arrays, indexed by the grid step. The TPU
lowering keeps an SMEM operand only whole, so ``vmap`` cannot batch the
call on its own: a ``custom_vmap`` rule folds each vmapped axis into B.

Off-TPU the kernel runs with ``interpret=True``; it is bit-identical to
:func:`ref.cache_step_ref` by property test. It composes with ``vmap``
over nodes and systems and with ``lax.scan`` over events — famsim
invokes it per node inside its vmapped phase-A.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cache_lookup.ref import HASH_MULT

_I32_MAX = jnp.iinfo(jnp.int32).max


def _si_of(blk, num_sets_u32):
    """Set hash modulo the effective set count (dram_cache._set_index)."""
    h = (blk.astype(jnp.uint32) * jnp.uint32(HASH_MULT)) >> 7
    return (h % num_sets_u32).astype(jnp.int32)


def _first_lane(mask, col, ways_pad):
    """Index of the first True lane of a ``(1, ways_pad)`` row, or
    ``ways_pad`` when none is set — an iota + min reduction, because the
    TPU lowering has no integer/bool argmax."""
    return jnp.min(jnp.where(mask, col, ways_pad))


def _kernel(tags_ref, lru_ref, stamp_ref, fills_ref, fen_ref, q_ref,
            qen_ref, probes_ref, eff_ref,
            otags_ref, olru_ref, ostamp_ref, ohit_ref, ophits_ref,
            *, mode: str, max_rrpv: int, ways_pad: int, n_fills: int,
            n_probes: int):
    b = pl.program_id(0)
    otags_ref[...] = tags_ref[...]
    olru_ref[...] = lru_ref[...]
    ns_u = eff_ref[2 * b].astype(jnp.uint32)
    eff_ways = eff_ref[2 * b + 1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, ways_pad), 1)
    wmask = col < eff_ways
    first = functools.partial(_first_lane, col=col, ways_pad=ways_pad)

    def insert_one(blk, en, stamp):
        si = _si_of(blk, ns_u)
        row_t = otags_ref[pl.ds(si, 1), :]
        row_l = olru_ref[pl.ds(si, 1), :]
        tag = blk + 1
        am_already = first((row_t == tag) & wmask)
        am_vacant = first((row_t == 0) & wmask)
        has = am_already < ways_pad
        has_vacant = am_vacant < ways_pad
        stamp = stamp + en
        en_b = en > 0
        if mode == "lru":
            victim = jnp.where(wmask, row_l, _I32_MAX)
            way = jnp.where(has, am_already,
                            jnp.where(has_vacant, am_vacant,
                                      first(victim == jnp.min(victim))))
            onehot = col == way
            sel = en_b & onehot
            new_t = jnp.where(sel, tag, row_t)
            new_l = jnp.where(sel, stamp, row_l)
        else:            # srrip: recency field holds the 2-bit RRPV
            m = jnp.int32(max_rrpv)
            eff_l = jnp.where(wmask, row_l, 0)
            bump = jnp.maximum(m - jnp.max(eff_l), 0)
            aged = jnp.where(wmask, row_l + bump, row_l)
            cand = jnp.where(wmask, aged, -1)
            evict_way = first(cand == jnp.max(cand))
            way = jnp.where(has, am_already,
                            jnp.where(has_vacant, am_vacant, evict_way))
            onehot = col == way
            # aging applies only on the eviction path; a redundant fill
            # of a present block re-references (promotes) it — exactly
            # dram_cache.insert's generalized-policy path
            base = jnp.where(has | has_vacant, row_l, aged)
            fill_val = jnp.where(has, jnp.int32(0), m - 1)
            new_row = jnp.where(onehot, fill_val, base)
            new_t = jnp.where(en_b & onehot, tag, row_t)
            new_l = jnp.where(en_b, new_row, row_l)
        otags_ref[pl.ds(si, 1), :] = new_t
        olru_ref[pl.ds(si, 1), :] = new_l
        return stamp

    # 1) retire prefetch fills (sequential: same-set fills interact)
    def fill_body(i, stamp):
        k = b * n_fills + i
        return insert_one(fills_ref[k], fen_ref[k], stamp)

    stamp = jax.lax.fori_loop(0, n_fills, fill_body, stamp_ref[b])

    # 2) demand probe + recency touch on the post-fill state
    q = q_ref[b]
    si = _si_of(q, ns_u)
    row_t = otags_ref[pl.ds(si, 1), :]
    way = first((row_t == q + 1) & wmask)
    hit = (way < ways_pad) & (qen_ref[b] > 0)
    hit_i = hit.astype(jnp.int32)
    stamp = stamp + hit_i
    hit_val = stamp if mode == "lru" else jnp.int32(0)
    row_l = olru_ref[pl.ds(si, 1), :]
    olru_ref[pl.ds(si, 1), :] = jnp.where(hit & (col == way), hit_val,
                                          row_l)
    ohit_ref[b] = hit_i
    ostamp_ref[b] = stamp

    # 3) pure probes (touch never writes tags, so these are order-free)
    def probe_body(j, carry):
        k = b * n_probes + j
        blk = probes_ref[k]
        row = otags_ref[pl.ds(_si_of(blk, ns_u), 1), :]
        ophits_ref[k] = (first((row == blk + 1) & wmask)
                         < ways_pad).astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, n_probes, probe_body, 0)


def _pallas_step(tags, lru, stamp, fills, fen, q, qen, probes, eff, *,
                 mode: str, max_rrpv: int, interpret: bool):
    """The kernel over B caches. tags/lru: (B, S_pad, W_pad); stamp, q,
    qen: (B,); fills/fen: (B, C); probes: (B, P); eff: (B, 2)."""
    B, s_pad, w_pad = tags.shape
    C, P = fills.shape[1], probes.shape[1]
    kern = functools.partial(_kernel, mode=mode, max_rrpv=max_rrpv,
                             ways_pad=w_pad, n_fills=C, n_probes=P)
    state = pl.BlockSpec((None, s_pad, w_pad), lambda b: (b, 0, 0),
                         memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    # VMEM holds tags and recency, in and out, double-buffered: eight
    # blocks, each row padded to the 128-lane tile
    block_bytes = s_pad * -(-w_pad // 128) * 128 * 4
    i32 = jnp.int32
    tags2, lru2, stamp2, hit, phits = pl.pallas_call(
        kern,
        grid=(B,),
        out_shape=[jax.ShapeDtypeStruct((B, s_pad, w_pad), i32),
                   jax.ShapeDtypeStruct((B, s_pad, w_pad), i32),
                   jax.ShapeDtypeStruct((B,), i32),
                   jax.ShapeDtypeStruct((B,), i32),
                   jax.ShapeDtypeStruct((B * P,), i32)],
        in_specs=[state, state] + [smem] * 7,
        out_specs=[state, state, smem, smem, smem],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=8 * block_bytes + (16 << 20)),
        interpret=interpret,
    )(tags, lru, stamp, fills.reshape(-1), fen.reshape(-1), q, qen,
      probes.reshape(-1), eff.reshape(-1))
    return tags2, lru2, stamp2, hit, phits.reshape(B, P)


@functools.cache
def _batched_step(mode: str, max_rrpv: int, interpret: bool):
    """:func:`_pallas_step` (every operand carries a leading batch axis)
    with a vmap rule that folds the vmapped axis into that batch axis, so
    any nesting of ``vmap`` stays one ``pallas_call``."""
    step = jax.custom_batching.custom_vmap(functools.partial(
        _pallas_step, mode=mode, max_rrpv=max_rrpv, interpret=interpret))

    @step.def_vmap
    def _fold(axis_size, in_batched, *args):
        args = [a if bat else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, bat in zip(args, in_batched)]
        B = args[0].shape[1]
        outs = step(*(a.reshape((axis_size * B,) + a.shape[2:])
                      for a in args))
        return (tuple(o.reshape((axis_size, B) + o.shape[1:])
                      for o in outs), (True,) * len(outs))

    return step


@functools.partial(jax.jit,
                   static_argnames=("mode", "max_rrpv", "interpret"))
def fused_cache_step(tags, lru, stamp, fill_blocks, fill_enable,
                     demand_block, demand_enable, probe_blocks,
                     num_sets, ways, *, mode: str = "lru",
                     max_rrpv: int = 0, interpret: bool = False):
    """tags/lru: (S_pad, W_pad) int32; stamp: () int32; fills: (C,);
    demand: scalars; probe_blocks: (P,); num_sets/ways: effective
    geometry (traced ok). Returns (tags, lru, stamp, hit, probe_hits)
    with the same semantics as :func:`ref.cache_step_ref`."""
    i32 = jnp.int32
    args = (tags, lru, jnp.asarray(stamp, i32),
            jnp.asarray(fill_blocks, i32),
            jnp.asarray(fill_enable).astype(i32),
            jnp.asarray(demand_block, i32),
            jnp.asarray(demand_enable).astype(i32),
            jnp.asarray(probe_blocks, i32),
            jnp.stack([jnp.asarray(num_sets).astype(i32),
                       jnp.asarray(ways).astype(i32)]))
    tags2, lru2, stamp2, hit, phits = _batched_step(
        mode, max_rrpv, interpret)(*(a[None] for a in args))
    return tags2[0], lru2[0], stamp2[0], hit[0] > 0, phits[0] > 0
