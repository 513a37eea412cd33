"""Compile the simulator's chip path for a described TPU v5e, no chip needed.

The TPU compiler is installed with jaxlib and compiles for a topology that
is described, not attached, so layout, memory-space and VMEM errors show
up here instead of on the chip. Nothing runs: these tests say nothing
about results or times.

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library, and
every test worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("replacement", ["lru", "srrip"])
def test_fused_cache_step_compiles_for_v5e(one_chip, no_persistent_cache,
                                           replacement):
    """The kernel at fig08's padded geometry (64 B blocks: 16,384 sets x
    16 ways), with as many fills and probes as famsim hands it."""
    from repro.configs.base import FamConfig, fam_replace
    from repro.core import dram_cache as dc
    from repro.kernels.famsim_step import (fused_cache_step,
                                           fused_replacement_mode)
    from repro.policies import PolicySet

    cfg = fam_replace(FamConfig(), block_bytes=64)
    policy = PolicySet(replacement=replacement).impl("replacement").bind(None)
    mode, max_rrpv = fused_replacement_mode(policy)
    assert (mode, max_rrpv) == (replacement,
                                3 if replacement == "srrip" else 0)
    S, W = cfg.num_sets, cfg.cache_ways
    C = cfg.completions_per_step
    P = cfg.prefetch_degree + cfg.core_pf_degree
    # the kernel takes the (sets, ways) view of the tables
    tables = jax.eval_shape(
        lambda: (lambda c: (*dc.set_view(c), c.stamp))(dc.init_cache(S, W)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    b = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bool_)
    args = _on(one_chip, (*tables, i32(C), b(C), i32(), b(), i32(P), i32(),
                          i32()))
    compiled = fused_cache_step.lower(*args, mode=mode,
                                      max_rrpv=max_rrpv).compile()
    assert (S, W) == (16384, 16)
    assert "tpu_custom_call" in compiled.as_text()


#: temp bytes of the xla group below when its cache tables were stored
#: (sets, ways) and read one element per way
SETS_WAYS_TEMP_BYTES = 144_536_576


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_group_compiles_for_v5e(one_chip, no_persistent_cache, backend):
    """fig08's quick grid as the executor builds it (one vmapped group,
    S = 80, T = 12,000, in-graph traces). On pallas the compiled text
    carries the kernel, not its interpreted form. On xla no instruction
    copies a whole cache array, in either storage: a row gather and an
    element scatter that disagree on the layout make the compiler
    relayout the tags and recency on every fill, which took 99 % of the
    step. The lane-dense tables are read as 128-lane rows, never as
    S x 16 element gathers, and need no more temp memory than the
    (sets, ways) tables did."""
    import repro.core.famsim  # noqa: F401  (repro.core before the kernels)
    from benchmarks import fig08_blocksize
    from repro.experiments import executor as ex

    plan = fig08_blocksize.experiment(quick=True,
                                      kernel_backend=backend).plan()
    (g,) = plan.groups
    rep = plan.points[g.indices[0]]
    S = len(ex._pad_systems(g.indices, g.s_pad, 1))
    assert (S, g.t_pad, g.pad_sets, g.pad_ways) == (80, 12_000, 16384, 16)
    fn, arg_shapes = ex.group_program(
        rep.cfg, S, g.key.num_nodes, g.t_pad, pad_sets=g.pad_sets,
        pad_ways=g.pad_ways, trace_backend=plan.trace_backend,
        policies=rep.policy_set())
    compiled = jax.jit(fn).lower(*_on(one_chip, arg_shapes)).compile()
    text = compiled.as_text()
    if backend == "pallas":
        assert "tpu_custom_call" in text
    else:
        cache = re.compile(r"= s32\[80,1,(16384,16|2048,128)\]\S* copy\(")
        assert not cache.findall(text)
        # S * W_pad table elements gathered one by one, fused or not
        per_way = re.compile(r"= s32\[(1280|80,16)\]\S* (gather|fusion)\("
                             r'.*op_name="[^"]*cache_lookup[^"]*gather"')
        assert not per_way.findall(text)
        assert re.search(r"= s32\[80,128\]\S* gather\(", text)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e9           # one v5e chip's HBM
    if backend == "xla":
        assert mem.temp_size_in_bytes <= SETS_WAYS_TEMP_BYTES
