"""The fused famsim cache-step kernel (repro.kernels.famsim_step).

Three contracts, all bit-exact:

* the fused Pallas kernel (interpret mode off-TPU) matches the pure-XLA
  reference op sequence on arbitrary driven op streams — random padded
  geometries, effective (num_sets, ways) below the padding, classic LRU
  and SRRIP replacement (hypothesis property test);
* an end-to-end simulation under ``kernel_backend="pallas"`` reproduces
  the default ``"xla"`` backend metric-for-metric;
* the backend is a STATIC compile tag: it splits planner compile groups,
  and unsupported policy/backend combinations fail loudly at build time.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import FamConfig, fam_replace
from repro.core import dram_cache as dc
from repro.core.famsim import SimFlags, _make_step, build_sim
from repro.core.traces import generate, node_seed
from repro.experiments import Experiment, config_axis, plan_points, \
    workload_axis
from repro.kernels.famsim_step import (FUSED_REPLACEMENT_MODES,
                                       KERNEL_BACKENDS, cache_step,
                                       cache_step_ref, fused_cache_step)
from repro.policies import PolicySet
from repro.policies.replacement import SRRIP

N, T = 2, 400
WL = ["LU", "bfs"]


def _node_traces(T=T):
    tr = [generate(w, T, node_seed(0, i)) for i, w in enumerate(WL)]
    return (jnp.asarray(np.stack([a for a, _ in tr])),
            jnp.asarray(np.stack([g for _, g in tr])))


# ---------------------------------------------------------------------------
# kernel vs reference: driven op streams over random padded geometries
# ---------------------------------------------------------------------------

#: per storage of the tables, padded allocations (sets, ways) init_cache
#: stores that way: lane-dense when the sets tile 128-lane rows
PAD_GEOMS = {
    "sets_ways": [(s, w) for s in (4, 8, 16) for w in (2, 4, 8)
                  if s * w % 128],
    "lane_dense": [(64, 16), (16, 8), (32, 4)],
}


def assert_same_cache(a, b):
    """Same storage, and the same (sets, ways) tables and stamp."""
    assert a.tags.shape == b.tags.shape and a.w_pad == b.w_pad
    for x, y in zip(dc.set_view(a) + (a.stamp,), dc.set_view(b) + (b.stamp,)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("storage", sorted(PAD_GEOMS))
@settings(max_examples=12, deadline=None)
@given(data=st.data(), sets_frac=st.floats(0.25, 1.0),
       ways_frac=st.floats(0.25, 1.0), srrip=st.booleans(),
       c=st.integers(1, 4), p=st.integers(1, 6), seed=st.integers(0, 2 ** 16))
def test_fused_cache_step_property(storage, data, sets_frac, ways_frac,
                                   srrip, c, p, seed):
    """Fused kernel == reference, state and outputs, on every step of a
    random op stream — effective geometry strictly below the padding
    exercises the dynamic-ways mask and the modulo set hash; the kernel
    sees the reference's tables through ``dc.set_view``."""
    pad_sets, pad_ways = data.draw(st.sampled_from(PAD_GEOMS[storage]))
    assert dc.storage(pad_sets, pad_ways) == storage
    eff_sets = max(1, int(pad_sets * sets_frac))
    eff_ways = max(1, int(pad_ways * ways_frac))
    policy = SRRIP.bind(None) if srrip else None
    rng = np.random.default_rng(seed)
    ref = fused = dc.init_cache(pad_sets, pad_ways)
    for _ in range(3):
        fills = jnp.asarray(rng.integers(0, 120, c), jnp.int32)
        fen = jnp.asarray(rng.random(c) < 0.7)
        demand = jnp.asarray(rng.integers(0, 120), jnp.int32)
        den = jnp.asarray(rng.random() < 0.8)
        probes = jnp.asarray(rng.integers(0, 120, p), jnp.int32)
        args = (fills, fen, demand, den, probes, eff_sets, eff_ways)
        ref, rhit, rprobes = cache_step_ref(ref, *args, policy=policy)
        fused, fhit, fprobes = cache_step(fused, *args, policy=policy,
                                          backend="pallas")
        np.testing.assert_array_equal(np.asarray(rhit), np.asarray(fhit))
        np.testing.assert_array_equal(np.asarray(rprobes),
                                      np.asarray(fprobes))
        assert_same_cache(ref, fused)


def test_fused_cache_step_raw_wrapper_shapes():
    """The raw kernel wrapper's output contract: state arrays keep the
    padded shape, hit is a scalar bool, probe hits are (P,) bool."""
    cache = dc.init_cache(8, 4)
    tags, lru, stamp, hit, phits = fused_cache_step(
        cache.tags, cache.lru, cache.stamp,
        jnp.asarray([3, 9], jnp.int32), jnp.asarray([True, True]),
        jnp.asarray(3, jnp.int32), jnp.asarray(True),
        jnp.asarray([3, 5, 9], jnp.int32), 8, 4,
        mode="lru", max_rrpv=0, interpret=True)
    assert tags.shape == (8, 4) and lru.shape == (8, 4)
    assert stamp.shape == () and hit.shape == ()
    assert phits.shape == (3,) and phits.dtype == jnp.bool_
    assert bool(hit)                      # block 3 was just filled
    np.testing.assert_array_equal(np.asarray(phits), [True, False, True])


def test_fused_cache_step_nested_vmap():
    """vmap over systems of vmap over nodes folds into one batched kernel
    call (custom_vmap); every cache still matches the reference, with the
    effective geometry shared across nodes (unbatched at the inner
    level)."""
    S_, N_, C, P = 2, 3, 3, 4
    rng = np.random.default_rng(7)
    tags = jnp.asarray(rng.integers(0, 40, (S_, N_, 16, 4)), jnp.int32)
    lru = jnp.asarray(rng.integers(0, 9, (S_, N_, 16, 4)), jnp.int32)
    stamp = jnp.full((S_, N_), 9, jnp.int32)
    fills = jnp.asarray(rng.integers(0, 60, (S_, N_, C)), jnp.int32)
    fen = jnp.asarray(rng.random((S_, N_, C)) < 0.7)
    demand = jnp.asarray(rng.integers(0, 60, (S_, N_)), jnp.int32)
    den = jnp.ones((S_, N_), bool)
    probes = jnp.asarray(rng.integers(0, 60, (S_, N_, P)), jnp.int32)
    sets = jnp.asarray([16, 11], jnp.int32)
    ways = jnp.asarray([4, 3], jnp.int32)

    def run(step):
        def node(t, l, st, f, fe, d, de, p, ns, w):
            c, hit, ph = step(dc.CacheState(t, l, st, w_pad=4), f, fe, d,
                              de, p, ns, w)
            return c.tags, c.lru, c.stamp, hit, ph
        per_system = jax.vmap(node, in_axes=(0,) * 8 + (None, None))
        return jax.vmap(per_system)(tags, lru, stamp, fills, fen, demand,
                                    den, probes, sets, ways)

    ref = run(cache_step_ref)
    fused = run(lambda *a: cache_step(*a, backend="pallas"))
    for a, b in zip(ref, fused):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kernel_package_imports_before_core():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    subprocess.run(
        [sys.executable, "-c", "import repro.kernels.famsim_step"],
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=os.path.join(root, "src")), check=True)


# ---------------------------------------------------------------------------
# end-to-end: pallas backend == xla backend, whole-sim bit-identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("replacement", ["lru", "srrip"])
def test_sim_backends_bit_identical(replacement):
    addrs, gaps = _node_traces()
    ps = PolicySet(replacement=replacement)
    out = {}
    for backend in KERNEL_BACKENDS:
        cfg = fam_replace(FamConfig(), kernel_backend=backend)
        run = build_sim(cfg, SimFlags(), N, policies=ps)
        out[backend] = {k: np.asarray(v)
                        for k, v in run(addrs, gaps).items()}
    assert out["xla"].keys() == out["pallas"].keys()
    for k in out["xla"]:
        np.testing.assert_array_equal(out["xla"][k], out["pallas"][k],
                                      err_msg=k)


# ---------------------------------------------------------------------------
# static wiring: compile keys, build-time validation
# ---------------------------------------------------------------------------

def test_backend_is_a_static_compile_tag():
    """kernel_backend rides on geometry_free_shape(): the two backends
    select different traced programs, so the planner MUST split them —
    while same-backend points still fuse into one group."""
    exp = Experiment(
        name="kb", T=900,
        axes=(config_axis("backend", list(KERNEL_BACKENDS),
                          param="kernel_backend"),
              workload_axis(["LU", "bfs"])))
    plan = plan_points(exp.points())
    assert plan.num_groups == 2
    assert [len(g.indices) for g in plan.groups] == [2, 2]
    xla = FamConfig()
    pal = fam_replace(xla, kernel_backend="pallas")
    assert xla.geometry_free_shape() != pal.geometry_free_shape()


def test_unsupported_policy_fails_at_build_time():
    cfg = fam_replace(FamConfig(), kernel_backend="pallas")
    with pytest.raises(ValueError, match="kernel_backend='pallas'"):
        _make_step(cfg, N, policies=PolicySet(replacement="random"))
    # the supported modes are exactly the advertised ones
    assert FUSED_REPLACEMENT_MODES == ("lru", "srrip")
    # and lru/srrip build fine
    for repl in FUSED_REPLACEMENT_MODES:
        _make_step(cfg, N, policies=PolicySet(replacement=repl))


def test_unknown_backend_fails_at_build_time():
    cfg = fam_replace(FamConfig(), kernel_backend="cuda")
    with pytest.raises(ValueError, match="kernel_backend"):
        _make_step(cfg, N)
