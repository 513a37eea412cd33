"""Prefetch queue — fixed-length in-flight window at the root complex
(paper §III-A2). MSHR-analogue: holds issued prefetches until their response
returns; demand requests probe it to detect in-flight prefetches; when full,
no further prefetches issue (static rate limiting — the BW-adaptive throttle
composes on top, §IV-B).

The probe and the insert run under the in-graph scope ``prefetch_queue``
(docs/observability.md), nested in the scope of their caller, as does the
simulator's drain (``famsim._phase_a``); the scope tags the ops' metadata
only.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class PrefetchQueue(NamedTuple):
    block: jax.Array    # (Q,) int32 block addr (+1; 0 = empty)
    finish: jax.Array   # (Q,) float32 completion time (cycles)


def init_queue(size: int) -> PrefetchQueue:
    return PrefetchQueue(block=jnp.zeros((size,), jnp.int32),
                         finish=jnp.zeros((size,), jnp.float32))


def occupancy(q: PrefetchQueue) -> jax.Array:
    return jnp.sum((q.block > 0).astype(jnp.int32))


def contains(q: PrefetchQueue, block_addr) -> Tuple[jax.Array, jax.Array]:
    """-> (in_flight, finish_time). Demand probe (MSHR-style hit)."""
    with jax.named_scope("prefetch_queue"):
        match = q.block == (block_addr.astype(jnp.int32) + 1)
        inflight = jnp.any(match)
        finish = jnp.max(jnp.where(match, q.finish, 0.0))
    return inflight, finish


def try_insert(q: PrefetchQueue, block_addr, finish_time,
               threshold: float = 1.0, enable=True
               ) -> Tuple[PrefetchQueue, jax.Array]:
    """Insert if a slot is free and occupancy < threshold * capacity.

    (The paper drops prefetches when the queue is at a predefined threshold,
    e.g. 95%.) Returns (queue, inserted?). ``enable`` masks the write.
    """
    size = q.block.shape[0]
    with jax.named_scope("prefetch_queue"):
        free = q.block == 0
        ok = (jnp.any(free) & (occupancy(q) < jnp.int32(threshold * size))
              & jnp.asarray(enable))
        slot = jnp.argmax(free)
        blk = block_addr.astype(jnp.int32) + 1
        q2 = PrefetchQueue(
            block=q.block.at[slot].set(jnp.where(ok, blk, q.block[slot])),
            finish=q.finish.at[slot].set(
                jnp.where(ok, finish_time, q.finish[slot])))
    return q2, ok


def complete_until(q: PrefetchQueue, now) -> Tuple[PrefetchQueue, jax.Array, jax.Array]:
    """Retire all entries with finish <= now.

    Returns (queue, completed_blocks (Q,), completed_mask (Q,)) so the
    caller can fill the DRAM cache for each completed prefetch.
    """
    done = (q.block > 0) & (q.finish <= now)
    blocks = jnp.where(done, q.block - 1, -1)
    q2 = PrefetchQueue(block=jnp.where(done, 0, q.block), finish=q.finish)
    return q2, blocks, done
