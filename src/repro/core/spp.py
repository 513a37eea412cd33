"""Signature Path Prefetcher (SPP) — paper §II-B, adapted to sub-page blocks.

Faithful structure (Kim et al., MICRO'16, as summarized by the paper):

* Signature table: page-indexed; holds (page tag, last accessed block,
  signature). The signature compresses the page's recent delta history:
      delta     = block_now - block_prev
      signature = ((signature << 4) ^ delta) & SIG_MASK
* Pattern table: signature-indexed; 4 (delta, weight) slots plus a
  signature weight counter. Lookahead walks the pattern table recursively,
  multiplying per-step path confidence = w_delta / w_sig and stopping below
  ``confidence_threshold`` (path-confidence lookahead).

All state is jnp arrays (functional updates) so the whole prefetcher jits,
vmaps over nodes, and runs inside ``lax.scan`` in the simulator; the same
module drives the production tiering engine (block ids instead of physical
block addresses).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import FamConfig
from repro.core.dram_cache import gather_row

SIG_SHIFT = 4
PT_WAYS = 4
MAX_WEIGHT = 15          # 4-bit saturating counters, as in SPP


class SppState(NamedTuple):
    st_tag: jax.Array        # (ST,) int32 page tag (+1; 0 = invalid)
    st_last: jax.Array       # (ST,) int32 last block within page
    st_sig: jax.Array        # (ST,) int32 current signature
    pt_delta: jax.Array      # (PT, 4) int32 delta (signed)
    pt_weight: jax.Array     # (PT, 4) int32 saturating weights
    pt_sigw: jax.Array       # (PT,) int32 signature weight


def init_spp(cfg: FamConfig) -> SppState:
    ST, PT = cfg.spp_signature_entries, cfg.spp_pattern_entries
    z = jnp.zeros
    return SppState(
        st_tag=z((ST,), jnp.int32), st_last=z((ST,), jnp.int32),
        st_sig=z((ST,), jnp.int32),
        pt_delta=z((PT, PT_WAYS), jnp.int32),
        pt_weight=z((PT, PT_WAYS), jnp.int32),
        pt_sigw=z((PT,), jnp.int32))


def _sig_mask(cfg: FamConfig) -> int:
    return (1 << cfg.spp_signature_bits) - 1


def _st_index(cfg: FamConfig, page):
    h = (page.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)) >> jnp.uint32(8)
    return h % jnp.uint32(cfg.spp_signature_entries)


def _pt_index(cfg: FamConfig, sig):
    return sig % cfg.spp_pattern_entries


def update(cfg: FamConfig, s: SppState, page, block, enable=True
           ) -> Tuple[SppState, jax.Array]:
    """Train on one access (page, block). Returns (state, current signature).

    ``enable`` masks all written values (keeps updates in place in loops)."""
    en = jnp.asarray(enable)
    page = page.astype(jnp.int32)
    block = block.astype(jnp.int32)
    idx = _st_index(cfg, page).astype(jnp.int32)
    tag = page + 1
    hit = s.st_tag[idx] == tag

    delta = block - s.st_last[idx]
    old_sig = s.st_sig[idx]
    train = hit & (delta != 0) & en

    # --- pattern table update (only on ST hit with nonzero delta)
    pt_i = _pt_index(cfg, old_sig)
    row_d = gather_row(s.pt_delta, pt_i)
    row_w = gather_row(s.pt_weight, pt_i)
    match = row_d == delta
    has_match = jnp.any(match & (row_w > 0))
    way = jnp.where(has_match,
                    jnp.argmax(match & (row_w > 0)),
                    jnp.argmin(row_w))
    new_w = jnp.where(has_match, jnp.minimum(row_w[way] + 1, MAX_WEIGHT), 1)
    row_d = row_d.at[way].set(jnp.where(train, delta, row_d[way]))
    row_w = row_w.at[way].set(jnp.where(train, new_w, row_w[way]))
    ways = jnp.arange(PT_WAYS)
    pt_delta = s.pt_delta.at[pt_i, ways].set(row_d)
    pt_weight = s.pt_weight.at[pt_i, ways].set(row_w)
    pt_sigw = s.pt_sigw.at[pt_i].add(
        jnp.where(train, jnp.where(s.pt_sigw[pt_i] < 4 * MAX_WEIGHT, 1, 0), 0))

    # --- signature table update (allocate on miss)
    mask = _sig_mask(cfg)
    new_sig = jnp.where(hit, ((old_sig << SIG_SHIFT) ^ (delta & mask)) & mask,
                        block & mask)   # bootstrap signature on allocation
    st_tag = s.st_tag.at[idx].set(jnp.where(en, tag, s.st_tag[idx]))
    st_last = s.st_last.at[idx].set(jnp.where(en, block, s.st_last[idx]))
    st_sig = s.st_sig.at[idx].set(jnp.where(en, new_sig, s.st_sig[idx]))

    return SppState(st_tag, st_last, st_sig, pt_delta, pt_weight, pt_sigw), \
        new_sig


def predict(cfg: FamConfig, s: SppState, page, block, sig, degree: int,
            bpp: int = 64, threshold=None) -> Tuple[jax.Array, jax.Array]:
    """Recursive path-confidence lookahead from (page, block, sig).

    Returns (block_addrs (degree,), valid (degree,)) — global block addrs;
    predictions stay within the page (``bpp`` blocks per page), as SPP
    prefetches within the spatial region. ``threshold`` may be a traced
    scalar (sweepable); defaults to ``cfg.spp_confidence_threshold``.
    """
    mask = _sig_mask(cfg)
    if threshold is None:
        threshold = cfg.spp_confidence_threshold

    def body(carry, _):
        cur_sig, cur_block, conf, alive = carry
        pt_i = _pt_index(cfg, cur_sig)
        row_w = gather_row(s.pt_weight, pt_i)
        row_d = gather_row(s.pt_delta, pt_i)
        way = jnp.argmax(row_w)
        w = row_w[way]
        sigw = jnp.maximum(s.pt_sigw[pt_i], 1)
        step_conf = w.astype(jnp.float32) / sigw.astype(jnp.float32)
        new_conf = conf * jnp.minimum(step_conf * 4.0, 1.0)
        delta = row_d[way]
        nb = cur_block + delta
        ok = alive & (w > 0) & (new_conf >= threshold) & \
            (nb >= 0) & (nb < bpp) & (delta != 0)
        nsig = ((cur_sig << SIG_SHIFT) ^ (delta & mask)) & mask
        out_block = jnp.where(ok, nb, -1)
        return (jnp.where(ok, nsig, cur_sig),
                jnp.where(ok, nb, cur_block),
                jnp.where(ok, new_conf, conf),
                ok), out_block

    init = (sig.astype(jnp.int32), block.astype(jnp.int32),
            jnp.float32(1.0), jnp.bool_(True))
    _, blocks = jax.lax.scan(body, init, None, length=degree)
    valid = blocks >= 0
    return page.astype(jnp.int32) * bpp + jnp.maximum(blocks, 0), valid


def storage_bits(cfg: FamConfig) -> int:
    """Rough metadata budget (paper: ~11 kB, 2x SPP)."""
    st = cfg.spp_signature_entries * (16 + 6 + cfg.spp_signature_bits)
    pt = cfg.spp_pattern_entries * (PT_WAYS * (7 + 4) + 8)
    return st + pt
