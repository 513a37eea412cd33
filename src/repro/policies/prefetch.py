"""DRAM-cache prefetch policies (paper §III-A and related-work families).

* ``spp`` — the paper's Signature Path Prefetcher, delegating to
  ``repro.core.spp`` (the default; byte-identical to the pre-policy
  simulator).
* ``nextline`` — stateless next-N-blocks prefetcher with a sweepable
  ``distance`` numeric param (the classic sequential baseline the
  *Prefetcher-based DRAM Architecture* line of work compares against).
* ``bestoffset`` — a Best-Offset-style offset prefetcher (Michaud,
  HPCA'16, miniaturized): a recent-access ring scores a fixed candidate
  offset list per training round; the winning offset drives degree-deep
  in-page prefetches once its score clears a threshold.

All state is fixed-shape jnp (vmap/scan-safe); every write is masked by
``enable`` so non-live steps stay exact no-ops.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spp as spp_lib
from repro.policies.base import register


class SppPrefetch:
    """The paper's SPP, as a policy: state/train/predict delegate to
    ``repro.core.spp``; the confidence threshold is the numeric param."""

    kind = "prefetch"
    name = "spp"
    compile_tag = "prefetch:spp"

    def params_of(self, cfg):
        return {"confidence_threshold":
                np.float32(cfg.spp_confidence_threshold)}

    def init(self, cfg):
        return spp_lib.init_spp(cfg)

    def train(self, cfg, pol, state, page, block, enable):
        return spp_lib.update(cfg, state, page, block, enable=enable)

    def predict(self, cfg, pol, state, page, block, ctx, degree, bpp):
        return spp_lib.predict(cfg, state, page, block, ctx, degree,
                               bpp=bpp, threshold=pol["confidence_threshold"])


class NextLinePrefetch:
    """Stateless sequential prefetcher: blocks ``+d, +2d, ... +degree*d``
    within the page (``distance`` d is a traced numeric param, so a
    distance sweep shares one compile)."""

    kind = "prefetch"
    name = "nextline"
    compile_tag = "prefetch:nextline"

    def params_of(self, cfg):
        return {"distance": np.float32(1.0)}

    def init(self, cfg):
        return jnp.int32(0)          # stateless (scan-carry placeholder)

    def train(self, cfg, pol, state, page, block, enable):
        return state, jnp.int32(0)

    def predict(self, cfg, pol, state, page, block, ctx, degree, bpp):
        step = pol["distance"].astype(jnp.int32)
        nb = block.astype(jnp.int32) + \
            step * (1 + jnp.arange(degree, dtype=jnp.int32))
        valid = (nb >= 0) & (nb < bpp) & (step != 0)
        return page.astype(jnp.int32) * bpp + jnp.where(valid, nb, 0), valid


RECENT_ENTRIES = 16
#: candidate offsets scored each round (static — the list size is a shape)
BO_OFFSETS = (1, 2, 3, 4, 6, 8, -1, -2)


class BoState(NamedTuple):
    r_page: jax.Array    # (RECENT_ENTRIES,) recent access pages (+1; 0 empty)
    r_block: jax.Array   # (RECENT_ENTRIES,) recent in-page blocks
    ptr: jax.Array       # () ring pointer
    scores: jax.Array    # (len(BO_OFFSETS),) current-round scores
    best: jax.Array      # () winning offset (0 = untrained/disabled)
    round: jax.Array     # () accesses into the current round


class BestOffsetPrefetch:
    """Best-Offset-style scoring: each trained access tests every candidate
    offset ``o`` against the recent-access ring (did ``block - o`` on the
    same page happen recently?); after ``round_len`` accesses the
    best-scoring offset wins if it clears ``score_threshold``, else the
    prefetcher disables itself until a later round (BO's "no prefetch
    beats bad prefetch" rule)."""

    kind = "prefetch"
    name = "bestoffset"
    compile_tag = "prefetch:bestoffset"

    def params_of(self, cfg):
        return {"round_len": np.float32(64.0),
                "score_threshold": np.float32(8.0)}

    def init(self, cfg):
        K = len(BO_OFFSETS)
        return BoState(
            r_page=jnp.zeros((RECENT_ENTRIES,), jnp.int32),
            r_block=jnp.zeros((RECENT_ENTRIES,), jnp.int32),
            ptr=jnp.int32(0),
            scores=jnp.zeros((K,), jnp.int32),
            best=jnp.int32(0), round=jnp.int32(0))

    def train(self, cfg, pol, state, page, block, enable):
        en = jnp.asarray(enable)
        eni = en.astype(jnp.int32)
        page = page.astype(jnp.int32)
        block = block.astype(jnp.int32)
        offs = jnp.asarray(BO_OFFSETS, jnp.int32)             # (K,)
        src = block - offs                                    # (K,)
        seen = (state.r_page[None, :] == page + 1) & \
            (state.r_block[None, :] == src[:, None])          # (K, R)
        scores = state.scores + jnp.any(seen, axis=1).astype(jnp.int32) * eni
        rnd = state.round + eni
        done = rnd >= pol["round_len"].astype(jnp.int32)
        best_i = jnp.argmax(scores)
        winner = jnp.where(
            scores[best_i] >= pol["score_threshold"].astype(jnp.int32),
            offs[best_i], 0)
        best = jnp.where(done, winner, state.best)
        scores = jnp.where(done, 0, scores)
        rnd = jnp.where(done, 0, rnd)
        ptr = state.ptr
        r_page = state.r_page.at[ptr].set(
            jnp.where(en, page + 1, state.r_page[ptr]))
        r_block = state.r_block.at[ptr].set(
            jnp.where(en, block, state.r_block[ptr]))
        ptr = (ptr + eni) % RECENT_ENTRIES
        return BoState(r_page, r_block, ptr, scores, best, rnd), jnp.int32(0)

    def predict(self, cfg, pol, state, page, block, ctx, degree, bpp):
        nb = block.astype(jnp.int32) + \
            state.best * (1 + jnp.arange(degree, dtype=jnp.int32))
        valid = (state.best != 0) & (nb >= 0) & (nb < bpp)
        return page.astype(jnp.int32) * bpp + jnp.where(valid, nb, 0), valid


SPP = register(SppPrefetch())
NEXTLINE = register(NextLinePrefetch())
BESTOFFSET = register(BestOffsetPrefetch())
