"""Mixture-of-Experts with expert parallelism.

Two numerically-equivalent implementations:

* ``moe_dense`` — reference: computes every expert for every token and
  combines with routing weights (O(E) compute; used for tests/smoke).
* ``moe_sharded`` — production EP: experts sharded over the ``model`` mesh
  axis, sort-based capacity dispatch, explicit ``all_to_all`` inside
  ``shard_map`` (tokens travel to their experts and back), token-chunked to
  bound the dispatch-buffer footprint.

Routing (top-k over softmax probs, renormalized) and the load-balance aux
loss are computed *outside* ``shard_map`` so SPMD handles them and the aux
scalar needs no manual psum.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import Params, dense_init


def init_moe(key, cfg: ModelConfig) -> Params:
    m = cfg.moe
    pdt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    d, f, E = cfg.d_model, m.d_ff, m.num_experts
    p = {
        "router": dense_init(ks[0], d, E, pdt),
        "w_gate": (jax.random.normal(ks[1], (E, d, f)) / np.sqrt(d)).astype(pdt),
        "w_up": (jax.random.normal(ks[2], (E, d, f)) / np.sqrt(d)).astype(pdt),
        "w_down": (jax.random.normal(ks[3], (E, f, d)) / np.sqrt(f)).astype(pdt),
    }
    return p


def route(cfg: ModelConfig, p: Params, x: jax.Array):
    """Returns (top_w (B,S,k), top_i (B,S,k), aux_loss scalar)."""
    m = cfg.moe
    logits = (x @ p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, m.top_k)
    top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-9)
    # Switch-style load-balance loss
    E = m.num_experts
    density = jnp.mean(jax.nn.one_hot(top_i, E, dtype=jnp.float32), axis=(0, 1, 2))
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(density * mean_prob) * m.load_balance_coef
    return top_w, top_i, aux


def _expert_ffn(cfg: ModelConfig, w_gate, w_up, w_down, xs: jax.Array) -> jax.Array:
    """xs: (E, C, d) tokens grouped per (local) expert."""
    act = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
    g = jnp.einsum("ecd,edf->ecf", xs, w_gate)
    u = jnp.einsum("ecd,edf->ecf", xs, w_up)
    return jnp.einsum("ecf,efd->ecd", act(g) * u, w_down)


# ---------------------------------------------------------------------------
# Dense reference
# ---------------------------------------------------------------------------

def moe_dense(cfg: ModelConfig, p: Params, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    m = cfg.moe
    dt = x.dtype
    top_w, top_i, aux = route(cfg, p, x)
    act = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
    g = jnp.einsum("bsd,edf->bsef", x, p["w_gate"].astype(dt))
    u = jnp.einsum("bsd,edf->bsef", x, p["w_up"].astype(dt))
    y_all = jnp.einsum("bsef,efd->bsed", act(g) * u, p["w_down"].astype(dt))
    one_hot = jax.nn.one_hot(top_i, m.num_experts, dtype=dt)      # (B,S,k,E)
    w = jnp.einsum("bske,bsk->bse", one_hot, top_w.astype(dt))    # (B,S,E)
    y = jnp.einsum("bsed,bse->bsd", y_all, w)
    return y, aux


# ---------------------------------------------------------------------------
# Sharded EP implementation
# ---------------------------------------------------------------------------

def _rank_within_expert(ids: jax.Array, num_experts: int) -> jax.Array:
    """ids: (T,) expert id per token-slot -> rank of each slot within its
    expert's arrival order (stable). O(T log T), no segment ops."""
    T = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    sorted_ids = ids[order]
    first_occ = jnp.searchsorted(sorted_ids, sorted_ids, side="left")
    rank_sorted = jnp.arange(T) - first_occ
    rank = jnp.zeros((T,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    return rank


def _dispatch_compute_local(cfg: ModelConfig, ep_axis: str, capacity: int,
                            x_flat, top_w, top_i, w_gate, w_up, w_down):
    """Runs per-device inside shard_map. x_flat: (T,d). top_*: (T,k).
    w_*: local expert shards (E_loc, d, f)/(E_loc, f, d)."""
    m = cfg.moe
    T, d = x_flat.shape
    k = m.top_k
    E = m.num_experts
    M = jax.lax.axis_size(ep_axis)
    E_loc = E // M
    C = capacity

    ids = top_i.reshape(T * k).astype(jnp.int32)
    rank = _rank_within_expert(ids, E)
    keep = rank < C
    rank_c = jnp.minimum(rank, C - 1)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)

    # scatter tokens into per-(dest device, local expert, slot) send buffer
    dest = ids // E_loc
    le = ids % E_loc
    vals = x_flat[tok] * keep[:, None].astype(x_flat.dtype)
    send = jnp.zeros((M, E_loc, C, d), x_flat.dtype)
    send = send.at[dest, le, rank_c].add(vals, mode="drop")

    # tokens travel to their expert's device
    recv = jax.lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=0,
                              tiled=False)                 # (M_src, E_loc, C, d)
    recv = jnp.moveaxis(recv, 1, 0).reshape(E_loc, M * C, d)

    out = _expert_ffn(cfg, w_gate, w_up, w_down, recv)     # (E_loc, M*C, d)

    # send results home
    back = jnp.moveaxis(out.reshape(E_loc, M, C, d), 1, 0)  # (M_src, E_loc, C, d)
    got = jax.lax.all_to_all(back, ep_axis, split_axis=0, concat_axis=0,
                             tiled=False)                  # (M_dest, E_loc, C, d)

    # combine: gather each slot's result, weight, sum over k
    slot_out = got[dest, le, rank_c]                       # (T*k, d)
    w = (top_w.reshape(T * k).astype(x_flat.dtype) * keep.astype(x_flat.dtype))
    y = jnp.sum((slot_out * w[:, None]).reshape(T, k, d), axis=1)
    return y


def moe_sharded(cfg: ModelConfig, p: Params, x: jax.Array, *, mesh,
                dp_axes: Tuple[str, ...], ep_axis: str,
                capacity_factor: float = 1.25,
                token_chunk: int = 8192) -> Tuple[jax.Array, jax.Array]:
    """EP MoE. x: (B,S,d) sharded batch->dp_axes. Experts sharded over
    ep_axis. Falls back to dense when experts don't divide the axis."""
    m = cfg.moe
    M = 1
    for ax, sz in zip(mesh.axis_names, mesh.devices.shape):
        if ax == ep_axis:
            M = sz
    if m.num_experts % max(M, 1) != 0:
        return moe_dense(cfg, p, x)

    top_w, top_i, aux = route(cfg, p, x)
    B, S, d = x.shape
    dt = x.dtype

    dp_size = 1
    for ax, sz in zip(mesh.axis_names, mesh.devices.shape):
        if ax in dp_axes:
            dp_size *= sz
    if B % max(dp_size, 1) != 0:   # e.g. batch=1 long-context: replicate batch
        dp_axes = ()
        dp_size = 1
    batch_entry = (dp_axes if len(dp_axes) > 1 else dp_axes[0]) if dp_axes else None
    spec_x = P(batch_entry, None, None)
    T_loc = max((B + dp_size - 1) // dp_size * S, 1)
    chunk = min(token_chunk, T_loc)
    n_chunks = max(T_loc // chunk, 1)
    chunk = T_loc // n_chunks
    capacity = int(max(8, np.ceil(chunk * m.top_k * capacity_factor / m.num_experts)))

    def local_fn(x_l, tw_l, ti_l, wg, wu, wd):
        Bl, Sl = x_l.shape[:2]
        xf = x_l.reshape(Bl * Sl, d)
        twf = tw_l.reshape(Bl * Sl, m.top_k)
        tif = ti_l.reshape(Bl * Sl, m.top_k)

        def one_chunk(i):
            sl = lambda t: jax.lax.dynamic_slice_in_dim(t, i * chunk, chunk, 0)
            return _dispatch_compute_local(cfg, ep_axis, capacity,
                                           sl(xf), sl(twf), sl(tif), wg, wu, wd)

        if n_chunks == 1:
            yf = one_chunk(0)
        else:
            ys = jax.lax.map(one_chunk, jnp.arange(n_chunks))
            yf = ys.reshape(Bl * Sl, d)
        return yf.reshape(Bl, Sl, d)

    from repro.parallel.compat import shard_map
    y = shard_map(
        local_fn, mesh=mesh,
        in_specs=(spec_x, spec_x, spec_x,
                  P(ep_axis, None, None), P(ep_axis, None, None),
                  P(ep_axis, None, None)),
        out_specs=spec_x,
    )(x, top_w.astype(dt), top_i, p["w_gate"].astype(dt),
      p["w_up"].astype(dt), p["w_down"].astype(dt))
    return y, aux


def moe_apply(cfg: ModelConfig, p: Params, x: jax.Array, *, parallel=None
              ) -> Tuple[jax.Array, jax.Array]:
    """Entry point: picks the sharded path when a parallel context is given."""
    if parallel is not None and parallel.use_ep:
        return moe_sharded(cfg, p, x, mesh=parallel.mesh,
                           dp_axes=parallel.dp_axes, ep_axis=parallel.ep_axis,
                           capacity_factor=parallel.capacity_factor,
                           token_chunk=parallel.moe_token_chunk)
    return moe_dense(cfg, p, x)
