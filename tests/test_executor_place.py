"""Sharded staging: on more than one device the executor places each
group's inputs on the mesh itself (span ``stage.place``, time in
``RunInfo.place_s``), in the layout the shard_map program takes, so the
call starts with its inputs in place; on one device it stages exactly as
before. The placed call stays bit-equal to the one-device vmap call."""
import json
import os
import subprocess
import sys

from repro.experiments import Experiment, config_axis, execute, workload_axis
from repro.obs.spans import SpanTracer, set_tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _two_group_experiment():
    # the prefetch queue size is a static shape: two groups
    return Experiment(
        name="place", T=300,
        axes=(config_axis("queue", [64, 256], param="prefetch_queue"),
              workload_axis(["LU", "bfs", "mg"])))


def _traced(plan, devices):
    tracer = SpanTracer("test")
    prev = set_tracer(tracer)
    try:
        res = execute(plan, devices=devices)
    finally:
        set_tracer(prev)
    return res, [n for n, _, _ in tracer.spans]


def test_one_device_stages_without_placing():
    plan = _two_group_experiment().plan()
    assert plan.num_groups == 2
    res, names = _traced(plan, 1)
    assert "stage.place" not in names
    assert names.count("stage.stack") == 2
    assert res.info.place_s == 0.0
    assert res.info.as_dict()["place_s"] == 0.0


_FOUR = """
import json, os, sys
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {src!r})
sys.path.insert(0, {tests!r})
import numpy as np, jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.experiments import executor as ex
from test_executor_place import _traced, _two_group_experiment
assert len(jax.devices()) == 4, jax.devices()
plan = _two_group_experiment().plan()
four, names4 = _traced(plan, 4)
one, names1 = _traced(plan, 1)
check = ex.execute(plan, devices=4, cross_check_shard=True).info.shard_check
equal = all(np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
            for a, b in zip(four.metrics, one.metrics) for k in a)
g = plan.groups[0]
idxs = ex._pad_systems(g.indices, g.s_pad, 4)
data = ex._prepare(plan.points, idxs, g.t_pad, 0.2, "device", devices=4)
want = NamedSharding(ex._mesh(4), P("dev"))
leaves = jax.tree.leaves((data.params, data.inputs, data.t_true,
                          data.warm_start))
placed = all(isinstance(x, jax.Array) and x.committed
             and x.sharding.is_equivalent_to(want, x.ndim) for x in leaves)
print(json.dumps({{
    "groups": plan.num_groups,
    "place_spans": [names4.count("stage.place"), names1.count("stage.place")],
    "place_s": [four.info.place_s, one.info.place_s],
    "place_in_dict": four.info.as_dict()["place_s"],
    "trace_gen_s": four.info.trace_gen_s,
    "bit_exact": bool(equal), "placed": bool(placed),
    "cross_check": [check["alt"], check["bit_exact"]],
    "lanes": len(idxs), "data_place_s": data.place_s}}))
"""


def test_four_devices_place_once_per_group_and_match_vmap():
    """On four host CPU devices: one ``stage.place`` per group, a
    positive ``place_s`` inside the staging time, every input leaf
    committed to the mesh's ``P("dev")`` layout, and metrics bit-equal to
    the one-device call, also when the executor's shard cross-check runs
    the placed group again on one device."""
    snippet = _FOUR.format(src=os.path.join(ROOT, "src"),
                           tests=os.path.join(ROOT, "tests"))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run([sys.executable, "-c", snippet], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["groups"] == 2
    assert out["place_spans"] == [2, 0]
    four_s, one_s = out["place_s"]
    assert four_s > 0 and one_s == 0.0
    assert out["place_in_dict"] == round(four_s, 4)
    assert four_s <= out["trace_gen_s"]
    assert out["bit_exact"] and out["placed"]
    assert out["cross_check"] == ["vmap", True]
    assert out["lanes"] % 4 == 0 and out["data_place_s"] > 0

