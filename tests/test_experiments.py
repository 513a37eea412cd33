"""The repro.experiments API: the compile-key planner must be deterministic
and group baseline+variants together — cache geometry (block size, cache
capacity) and the system axis S included, since the dynamic-geometry
refactor dropped both from the compile key (fig08/fig16 = ONE group each);
dynamic-T bucketing and canonical-S padding must pad (never truncate) and
the padded masked runner — padded geometry included — must reproduce the
unpadded per-point simulator bit-exactly; the device-sharded path must
match the single-device vmap path bit-exactly; and Point.seed must thread
through to the node traces."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs.base import FamConfig, fam_replace
from repro.core import dram_cache as dc
from repro.core.famsim import SimFlags, build_sim
from repro.core.traces import generate, node_seed
from repro.experiments import (Axis, AxisValue, Experiment, config_axis,
                               execute, flag_axis, plan_points, seed_axis,
                               t_bucket, trace_arrays, workload_axis)

BASE = SimFlags(core_prefetch=False, dram_prefetch=False)
DRAM = SimFlags()
T = 900          # buckets to 1024; uniform-T, so the group executes at 900


def _small_experiment():
    return Experiment(
        name="small", T=T,
        axes=(workload_axis(["LU", "bfs"]),
              flag_axis("variant", {"base": BASE, "dram": DRAM})))


@pytest.fixture(scope="module")
def small_result():
    return _small_experiment().run(cross_check_shard=True)


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------

def test_baseline_and_variants_share_one_group():
    plan = _small_experiment().plan()
    assert plan.num_groups == 1
    (g,) = plan.groups
    assert g.indices == (0, 1, 2, 3)
    assert g.key.num_nodes == 1 and g.key.t_bucket == 1024
    # uniform-T group at a canonical S: executes at the true T, zero padding
    assert g.t_pad == T and g.s_pad == 4
    assert plan.padded_events() == 0 and plan.padded_systems() == 0


def test_geometry_axes_merge_into_one_padded_group():
    """Since the dynamic-geometry refactor, block size and cache capacity
    are FamParams scalars: a geometry sweep plans into ONE group whose
    allocation pads to the largest swept geometry."""
    exp = Experiment(
        name="merge", T=T,
        axes=(config_axis("block", [128, 256], param="block_bytes"),
              config_axis("ratio", [1, 8], param="allocation_ratio"),
              workload_axis(["LU"])))
    plan = exp.plan()
    assert plan.num_groups == 1
    (g,) = plan.groups
    assert g.size == 4
    # 16 MB cache, 16 ways: 128 B blocks -> 8192 sets (the pad), 256 -> 4096
    assert g.pad_sets == 8192 and g.pad_ways == 16
    assert g.key.static_shape[:2] == (8192, 16)
    # what padding cannot unify still splits: a bigger prefetch queue
    pts = list(plan.points)
    pts += Experiment(name="q", T=T, axes=(
        config_axis("q", [128], param="prefetch_queue"),
        workload_axis(["LU"]))).points()
    assert plan_points(pts).num_groups == 2


def test_trace_backend_on_plan_not_in_compile_key():
    """The trace backend is an execution choice carried on the Plan —
    switching it must not change group keys, membership, order, or
    padding (the planner is backend-blind)."""
    exp_d = _small_experiment()
    exp_n = Experiment(name="small", T=T, trace_backend="numpy",
                       axes=exp_d.axes)
    plan_d, plan_n = exp_d.plan(), exp_n.plan()
    assert plan_d.trace_backend == "device"
    assert plan_n.trace_backend == "numpy"
    assert [g.key for g in plan_d.groups] == [g.key for g in plan_n.groups]
    assert [g.indices for g in plan_d.groups] == \
        [g.indices for g in plan_n.groups]
    with pytest.raises(ValueError, match="trace backend"):
        exp_d.plan(trace_backend="cuda")


def test_t_bucketing_merges_and_never_truncates():
    pts = []
    for T_true in (700, 900, 1100):
        pts += Experiment(name="t", T=T_true,
                          axes=(workload_axis(["LU"]),)).points()
    plan = plan_points(pts)
    for g in plan.groups:
        assert g.key.t_bucket >= g.t_pad
        for i in g.indices:
            assert g.t_pad >= plan.points[i].T      # pads, never truncates
    # 700 and 900 share bucket 1024 and execute at 900; 1100 goes to the
    # 1536 bucket but executes at its own length
    assert [g.key.t_bucket for g in plan.groups] == [1024, 1536]
    assert [g.t_pad for g in plan.groups] == [900, 1100]
    assert plan.groups[0].size == 2
    assert plan.padded_events() == 1 * (900 - 700)
    # bucket=None disables bucketing entirely: one exact-T group each
    assert plan_points(pts, bucket=None).num_groups == 3


def test_workload_sources_override_in_axis_order():
    """Whichever axis sets the workload source LAST wins — a mix axis after
    a workload axis must not be silently discarded (and vice versa)."""
    from repro.experiments import mix_axis
    wl = workload_axis(["LU"])
    mix = mix_axis({"m": ["bfs", "mg"]})
    pts = Experiment(name="o1", T=T, axes=(wl, mix)).points()
    assert all(p.workloads == ("bfs", "mg") for p in pts)
    pts = Experiment(name="o2", T=T, nodes=2, axes=(mix, wl)).points()
    assert all(p.workloads == ("LU", "LU") for p in pts)


def test_t_bucket_properties():
    for T_true in (1, 7, 1024, 1025, 5000, 12_000, 60_000, 250_000):
        b = t_bucket(T_true)
        assert b >= T_true                      # never truncates
        assert t_bucket(b) == b                 # canonical (idempotent)
        assert b < 2 * max(T_true, 1024)        # bounded pad overhead
    with pytest.raises(ValueError):
        t_bucket(0)


def test_s_bucket_properties():
    from repro.experiments import s_bucket
    for S in (1, 2, 3, 4, 5, 7, 8, 9, 24, 72, 100, 228, 1000):
        b = s_bucket(S)
        assert b >= S                           # never shrinks
        assert s_bucket(b) == b                 # canonical (idempotent)
        assert b <= S + max(-(-S // 4), 1)      # <= 25 % pad overhead
    # the figure grids' exact widths (quick): all canonical but fig08's 72
    assert [s_bucket(s) for s in (24, 48, 72, 80)] == [24, 48, 80, 80]
    with pytest.raises(ValueError):
        s_bucket(0)


def test_plan_keys_deterministic_across_processes():
    """The fig08 plan's group keys (and order) must be identical in a fresh
    interpreter — they are the compile cache keys."""
    from benchmarks.fig08_blocksize import experiment
    here = [repr(g.key) for g in experiment(quick=True).plan().groups]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    snippet = (
        "import sys; sys.path[:0] = [{root!r}, {src!r}]\n"
        "from benchmarks.fig08_blocksize import experiment\n"
        "for g in experiment(quick=True).plan().groups: print(repr(g.key))\n"
    ).format(root=root, src=os.path.join(root, "src"))
    out = subprocess.run([sys.executable, "-c", snippet],
                         capture_output=True, text=True, check=True)
    assert out.stdout.splitlines() == here


def test_figure_plans_one_group_per_figure():
    """Dynamic geometry collapses fig08/fig16 to exactly ONE group each
    (the PR-1/PR-2 engines paid one per block/cache size); fig10/fig12
    stay at one group per node count (N cannot be padded away) and
    fig14/fig15 at ONE."""
    from benchmarks import (fig08_blocksize, fig10_bw_adaptation, fig12_wfq,
                            fig14_mixes, fig15_allocation, fig16_cachesize)
    for mod in (fig08_blocksize, fig14_mixes, fig15_allocation,
                fig16_cachesize):
        plan = mod.experiment(quick=True).plan()
        assert plan.num_groups == 1, (mod.__name__, plan.describe())
    assert fig10_bw_adaptation.experiment(True).plan().num_groups == 3
    assert fig12_wfq.experiment(True).plan().num_groups == 2
    # the fig08 group's allocation pads to the smallest block's geometry
    (g,) = fig08_blocksize.experiment(True).plan().groups
    assert (g.pad_sets, g.pad_ways) == ((16 << 20) // 64 // 16, 16)


def test_run_plan_dry_run(capsys):
    """``benchmarks/run.py --plan`` prints every figure's resolved compile
    groups — and the one-group-per-figure ceilings — without executing."""
    from benchmarks.run import main
    main(["--plan"])
    out = capsys.readouterr().out
    for line in ("fig08_blocksize: 1 group(s)", "fig16_cachesize: 1 group(s)",
                 "fig14_mixes: 1 group(s)", "fig15_allocation: 1 group(s)",
                 "fig10_bw_adaptation: 3 group(s)", "fig12_wfq: 2 group(s)"):
        assert line in out, out
    assert "pad_geom=(16384x16)" in out          # fig08's padded allocation
    # quick vs --full share executables: same group keys/S_pad for fig14
    main(["--plan", "fig14"])
    quick = capsys.readouterr().out
    main(["--plan", "--full", "fig14"])
    full = capsys.readouterr().out
    line_q = [ln for ln in quick.splitlines() if "group 0" in ln][0]
    line_f = [ln for ln in full.splitlines() if "group 0" in ln][0]
    assert "S=24 S_pad=24" in line_q and "S=42 S_pad=48" in line_f
    assert line_q.split("key=")[1] == line_f.split("key=")[1]


# ---------------------------------------------------------------------------
# executor
# ---------------------------------------------------------------------------

def _staged_small_group(monkeypatch, backend):
    """Stage the small experiment's one group, recording every
    ``jax.device_put`` made while staging."""
    from repro.experiments import executor as ex
    plan = _small_experiment().plan()
    (g,) = plan.groups
    idxs = ex._pad_systems(g.indices, g.s_pad, 1)
    puts = []
    real_put = jax.device_put

    def counting_put(x, *args, **kw):
        puts.append(x)
        return real_put(x, *args, **kw)

    monkeypatch.setattr(jax, "device_put", counting_put)
    data = ex._prepare(plan.points, idxs, g.t_pad, 0.2, backend)
    return plan, g, idxs, data, puts


@pytest.mark.parametrize("backend", ["device", "numpy"])
def test_prepare_params_match_group_signature(monkeypatch, backend):
    """Staged params arrive as uncommitted device arrays with leading
    axis S and exactly the dtypes the group executable is compiled for."""
    from repro.experiments.executor import group_program
    plan, g, idxs, data, _ = _staged_small_group(monkeypatch, backend)
    rep = plan.points[g.indices[0]]
    _, (want, *_inputs) = group_program(
        rep.cfg, len(idxs), g.key.num_nodes, g.t_pad, pad_sets=g.pad_sets,
        pad_ways=g.pad_ways, trace_backend=backend,
        policies=rep.policy_set())
    assert jax.tree.structure(data.params) == jax.tree.structure(want)
    for leaf, spec in zip(jax.tree.leaves(data.params),
                          jax.tree.leaves(want)):
        assert isinstance(leaf, jax.Array) and not leaf.committed
        assert leaf.shape == spec.shape and leaf.shape[0] == len(idxs)
        assert leaf.dtype == spec.dtype


def test_prepare_sends_params_in_one_transfer(monkeypatch):
    """The group's params are stacked on the host and sent in ONE
    ``jax.device_put`` — not one dispatch per scalar and per leaf."""
    _, _, idxs, data, puts = _staged_small_group(monkeypatch, "device")
    assert len(puts) == 1
    (sent,) = puts
    assert jax.tree.structure(sent) == jax.tree.structure(data.params)
    assert all(isinstance(x, np.ndarray) and x.shape[0] == len(idxs)
               for x in jax.tree.leaves(sent))


def test_padded_executor_matches_unpadded_per_point(small_result):
    """The masked executor must reproduce the classic build_sim run
    bit-exactly — both for a uniform-T group (executed at exact T) and for
    a genuinely padded point in a mixed-T group. Padding may cost compute,
    never metrics. (The fixture runs the default DEVICE trace backend, so
    the reference pre-stages ``repro.traces.device.system_traces`` arrays
    — bit-identical to the in-graph generation at the same T.)"""
    import jax.numpy as jnp

    from repro.traces.device import system_traces as dev_traces

    # uniform-T fixture group (t_pad == T)
    a, g = dev_traces(["LU"], T, 0)
    run = build_sim(FamConfig(), DRAM, 1)
    ref = run(jnp.asarray(a), jnp.asarray(g))
    got = small_result.get(workload="LU", variant="dram")
    for k, v in ref.items():
        np.testing.assert_array_equal(np.asarray(v), got[k], err_msg=k)

    # mixed-T group: T=700 and T=900 share one executable at t_pad=900,
    # so the T=700 point simulates 200 masked tail steps — and the device
    # backend generates at t_pad, so the T=700 reference is the first 700
    # events of the T=900 device trace
    exp = Experiment(name="mixed_t", workloads=("LU",),
                     axes=(Axis("t", (AxisValue("700", T=700),
                                      AxisValue("900", T=900))),))
    plan = exp.plan()
    assert plan.num_groups == 1 and plan.groups[0].t_pad == 900
    res = execute(plan)
    a, g = dev_traces(["LU"], 900, 0)
    for T_true in (700, 900):
        ref = run(jnp.asarray(a[:, :T_true]), jnp.asarray(g[:, :T_true]))
        got = res.get(t=T_true)
        for k, v in ref.items():
            np.testing.assert_array_equal(np.asarray(v), got[k],
                                          err_msg=f"T={T_true} {k}")

    # the NUMPY backend still reproduces the classic numpy-trace run
    # bit-exactly, including the masked 700-event tail
    res_np = execute(plan, trace_backend="numpy")
    assert res_np.info.trace_backend == "numpy"
    for T_true in (700, 900):
        a2, g2 = generate("LU", T_true, node_seed(0, 0))
        ref = run(jnp.asarray(a2[None]), jnp.asarray(g2[None]))
        got = res_np.get(t=T_true)
        for k, v in ref.items():
            np.testing.assert_array_equal(np.asarray(v), got[k],
                                          err_msg=f"numpy T={T_true} {k}")


def test_padded_geometry_executor_matches_exact_reference():
    """The tentpole guarantee: a geometry sweep (block size AND cache
    capacity) executed as ONE padded group must reproduce every point's
    exact-geometry ``build_sim`` reference bit-for-bit — cache occupancy
    (a geometry-normalized metric) included. References pre-stage the
    device backend's traces (the executor generates the same bits in
    graph)."""
    import jax.numpy as jnp

    from repro.traces.device import system_traces as dev_traces

    exp = Experiment(
        name="geom", T=700,
        axes=(Axis("geom", (AxisValue("b64", cfg=(("block_bytes", 64),)),
                            AxisValue("b4096", cfg=(("block_bytes", 4096),)),
                            AxisValue("cache1m", cfg=(
                                ("dram_cache_bytes", 1 << 20),)))),
              workload_axis(["LU", "mg"]),
              flag_axis("variant", {"base": BASE, "dram": DRAM})))
    plan = exp.plan()
    assert plan.num_groups == 1
    assert plan.groups[0].pad_sets == (16 << 20) // 64 // 16
    res = execute(plan)
    assert res.info.host_trace_events == 0
    for pt in res.points:
        a, g = dev_traces([pt.workloads[0]], pt.T, 0)
        ref = build_sim(pt.cfg, pt.flags, 1)(jnp.asarray(a),
                                             jnp.asarray(g))
        got = res.metrics_for(pt)
        for k, v in ref.items():
            np.testing.assert_array_equal(np.asarray(v), got[k],
                                          err_msg=f"{pt.coords} {k}")


def test_padded_system_axis_bit_exact():
    """Padding S to a canonical width (inert repeated lanes) must not
    change any real point's metrics vs an unpadded execution."""
    exp = Experiment(name="spad", T=600,
                     axes=(workload_axis(["LU", "bfs", "mg"]),))
    padded = execute(exp.plan())                 # S=3 (canonical already)
    forced = execute(exp.plan(s_bucket=lambda s: 8))   # 5 inert lanes
    unpadded = execute(exp.plan(s_bucket=None))
    for i in range(3):
        for k, v in unpadded.metrics[i].items():
            np.testing.assert_array_equal(v, padded.metrics[i][k])
            np.testing.assert_array_equal(v, forced.metrics[i][k])
    assert forced.info.padded_systems == 5
    assert forced.info.padded_events == 5 * 600


def test_pad_systems_terminates_for_any_device_count():
    """Device counts outside the canonical-width grid's prime factors
    (9, 11, 13, ...) must fall back to a plain multiple of D instead of
    searching the grid forever."""
    from repro.experiments.executor import _pad_systems
    for D, S in ((9, 5), (11, 24), (13, 3), (2, 3), (4, 6), (1, 72)):
        out = _pad_systems(list(range(S)), S, D)
        assert len(out) % D == 0 and len(out) >= S
        assert out[:S] == list(range(S)) and set(out[S:]) <= {S - 1}


def test_sharded_path_bit_exact(small_result):
    """The shard_map path must be numerically identical to the vmap path
    (recorded by the executor's cross-check)."""
    chk = small_result.info.shard_check
    assert chk is not None and chk["bit_exact"] is True


def test_sharded_two_devices_bit_exact():
    """With 2 (forced host) devices, execute(devices=2) shards an odd S
    over the mesh — padding the system axis — and must match the
    single-device vmap results bit-exactly."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    snippet = """
import os, sys
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {src!r})
import numpy as np, jax
assert len(jax.devices()) == 2, jax.devices()
from repro.experiments import Experiment, execute, workload_axis
exp = Experiment(name="shard2", T=500,
                 axes=(workload_axis(["LU", "bfs", "mg"]),))
plan = exp.plan()
r2 = execute(plan, devices=2)   # S=3 padded to 4 across the mesh
r1 = execute(plan, devices=1)
assert r2.info.devices == 2
ok = all(np.array_equal(r2.metrics[i][k], r1.metrics[i][k])
         for i in range(plan.num_points) for k in r1.metrics[i])
print("BITEXACT", ok)
""".format(src=os.path.join(root, "src"))
    out = subprocess.run([sys.executable, "-c", snippet],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "BITEXACT True" in out.stdout


def test_overlap_matches_serial():
    """Async double-buffered trace prep must not change any metric — on a
    plan with MULTIPLE groups, so the thread-pool path actually runs (a
    1-group plan disables the pool; so does the DEVICE backend, whose
    no-host fast path has nothing to overlap — hence numpy here).
    Geometry no longer splits groups, so split on the prefetch queue size
    (a genuinely un-paddable shape)."""
    exp = Experiment(
        name="overlap", T=600, trace_backend="numpy",
        axes=(config_axis("queue", [64, 128], param="prefetch_queue"),
              workload_axis(["LU", "bfs"])))
    plan = exp.plan()
    assert plan.num_groups == 2 and plan.trace_backend == "numpy"
    from repro.experiments import executor as _ex
    _ex._TRACE_CACHE.clear()   # the counter records GENERATED events
    overlapped = execute(plan, overlap=True)
    assert overlapped.info.host_trace_events > 0
    serial = execute(plan, overlap=False)
    for i in range(plan.num_points):
        for k, v in overlapped.metrics[i].items():
            np.testing.assert_array_equal(v, serial.metrics[i][k])
    # list-typed Experiment.workloads must coerce, not crash hashing
    res = Experiment(name="listwl", T=600, workloads=["LU"],
                     axes=(seed_axis([0]),)).run()
    assert res.get(seed=0)["ipc"].shape == (1,)


def test_info_records_per_group_wallclock(small_result):
    info = small_result.info
    assert info.planned_groups == 1 == len(info.groups)
    g = info.groups[0]
    for field in ("compile_s", "run_s", "S", "N", "T_pad", "static_shape"):
        assert field in g
    assert g["cache_storage"] == dc.storage(g["pad_sets"], g["pad_ways"])
    assert g["T_pad"] == T
    assert info.events == 4 * 1 * T
    assert info.padded_events == 0          # uniform-T: no padding paid
    d = info.as_dict()
    assert d["shard_check"]["bit_exact"] is True
    # every timing names the device it was taken on
    dev = jax.devices()[0]
    assert (d["platform"], d["device_kind"]) == (dev.platform,
                                                 dev.device_kind)


def test_exec_cache_accounting_two_run_sequence():
    """First-class executable-cache counters on RunInfo: a cold run is
    all misses with nothing reused; re-executing the same-tag plan is all
    hits with every group's executable predating the call — counted on
    the info object (and per group), never by poking at _EXEC_CACHE."""
    exp = Experiment(                 # T=901: unique exec key, cold start
        name="cache_seq", T=901,
        axes=(workload_axis(["LU", "bfs"]),
              flag_axis("variant", {"base": BASE, "dram": DRAM})))
    r1 = exp.run()
    assert r1.info.planned_groups == 1
    assert r1.info.exec_cache_misses == 1 and r1.info.exec_cache_hits == 0
    assert r1.info.groups_reused == 0 and r1.info.compiles == 1
    assert r1.info.groups[0]["exec_cache_hit"] is False
    r2 = exp.run()
    assert r2.info.exec_cache_hits == 1 and r2.info.exec_cache_misses == 0
    assert r2.info.groups_reused == 1 == r2.info.planned_groups
    assert r2.info.compiles == 0
    assert r2.info.groups[0]["exec_cache_hit"] is True
    for key in ("exec_cache_hits", "exec_cache_misses", "groups_reused"):
        assert key in r2.info.as_dict()
    # the planner-level oracle agrees with what execute actually did, and
    # is deterministic across plan re-resolutions
    from repro.experiments import group_cache_keys
    keys = group_cache_keys(exp.plan())
    assert len(keys) == 1 and keys == group_cache_keys(exp.plan())
    # both runs returned identical metrics (cache reuse is invisible)
    for m1, m2 in zip(r1.metrics, r2.metrics):
        for k in m1:
            np.testing.assert_array_equal(m1[k], m2[k])


def test_result_coordinate_lookup(small_result):
    out = small_result.get(workload="LU", variant="dram")
    assert out["ipc"].shape == (1,)
    with pytest.raises(KeyError, match="variant"):
        small_result.get(workload="LU", variant="nope")


# ---------------------------------------------------------------------------
# seeds
# ---------------------------------------------------------------------------

def test_seed_threads_to_node_traces():
    """Repeated points that differ only in seed must simulate different
    traces (ResolvedPoint.seed -> traces.node_seed)."""
    res = Experiment(name="seeds", T=T, workloads=("LU",),
                     axes=(seed_axis([0, 1]),)).run()
    a0 = res.get(seed=0)
    a1 = res.get(seed=1)
    assert not np.array_equal(a0["ipc"], a1["ipc"])
    assert not np.array_equal(a0["fam_latency"], a1["fam_latency"])
    # and the executor's trace assembly derives per-node seeds through
    # traces.node_seed, like famsim.simulate
    addrs, _ = trace_arrays(("LU", "bfs"), 600, seed=7)
    for i, w in enumerate(("LU", "bfs")):
        np.testing.assert_array_equal(addrs[i],
                                      generate(w, 600, node_seed(7, i))[0])


def test_point_seed_regression_through_shim():
    """The deprecated run_points path must thread Point.seed too."""
    import benchmarks.common as common
    from benchmarks.common import Point, run_points
    pts = [Point(FamConfig(), DRAM, ("LU",), seed=0),
           Point(FamConfig(), DRAM, ("LU",), seed=3)]
    common._SHIM_WARNED = False          # re-arm the once-per-process warn
    with pytest.warns(DeprecationWarning):
        results, info = run_points(pts, T)
    assert not np.array_equal(results[0]["ipc"], results[1]["ipc"])


# ---------------------------------------------------------------------------
# deprecation shim
# ---------------------------------------------------------------------------

def test_run_points_deprecated_but_equivalent(small_result):
    """run_points warns, and returns exactly what the Experiment path
    produced for the same grid (same default trace backend included)."""
    import benchmarks.common as common
    from benchmarks.common import Point, run_points
    pts = [Point(FamConfig(), fl, (w,))
           for w in ("LU", "bfs") for fl in (BASE, DRAM)]
    common._SHIM_WARNED = False          # re-arm the once-per-process warn
    with pytest.warns(DeprecationWarning, match="Experiment"):
        results, info = run_points(pts, T)
    assert info.planned_groups == 1
    names = {"base": BASE, "dram": DRAM}
    for pt, got in zip(pts, results):
        label = next(k for k, v in names.items() if v == pt.flags)
        ref = small_result.get(workload=pt.workloads[0], variant=label)
        for k, v in ref.items():
            np.testing.assert_array_equal(v, got[k])


def test_shim_warns_exactly_once_per_process():
    """The Point/run_points DeprecationWarning fires on the first shim
    call only — repeated calls (from any call site) stay silent."""
    import warnings

    import benchmarks.common as common
    from benchmarks.common import Point, run_points
    pts = [Point(FamConfig(), DRAM, ("LU",))]
    common._SHIM_WARNED = False
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        run_points(pts, 600)
        run_points(pts, 600)
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)
           and "run_points" in str(w.message)]
    assert len(dep) == 1, [str(w.message) for w in rec]


def test_run_info_records_cache_storage():
    """Each group row of ``RunInfo`` names the storage its cache tables
    used: fig08's quick plan pads every point to 16,384 sets x 16 ways,
    which are stored lane-dense."""
    import dataclasses

    from benchmarks import fig08_blocksize

    exp = fig08_blocksize.experiment(quick=True)
    small = dataclasses.replace(
        exp, T=64, axes=tuple(dataclasses.replace(a, values=a.values[:1])
                              if a.name == "workload" else a
                              for a in exp.axes))
    plan = small.plan()
    assert [(g.pad_sets, g.pad_ways) for g in plan.groups] == [(16384, 16)]
    groups = execute(plan).info.as_dict()["groups"]
    assert [g["cache_storage"] for g in groups] == ["lane_dense"]


def test_runtime_compile_count_matches_plan_for_fig08_fig16():
    """The planner's "exactly ONE group" promise for fig08/fig16, proved
    at runtime: ``assert_compiles=True`` counts actual XLA compilations
    of the named group runner via ``jax.log_compiles`` and requires
    observed == accounted == planned (1 when the executable cache is
    cold, 0 when warm — an unplanned recompile fails the run)."""
    import dataclasses

    from benchmarks import fig08_blocksize, fig16_cachesize
    from repro.experiments import executor as ex

    for mod in (fig08_blocksize, fig16_cachesize):
        exp = mod.experiment(quick=True)
        small = dataclasses.replace(
            exp, T=512,
            axes=tuple(dataclasses.replace(a, values=a.values[:2])
                       if a.name == "workload" else a
                       for a in exp.axes))
        saved = dict(ex._EXEC_CACHE)
        ex._EXEC_CACHE.clear()
        try:
            cold = small.run(assert_compiles=True).info
            assert cold.planned_groups == 1, (mod.__name__, cold.groups)
            assert cold.compiles == cold.xla_compiles == 1, \
                (mod.__name__, cold.compiles, cold.xla_compiles)
            assert cold.as_dict()["xla_compiles"] == 1
            warm = small.run(assert_compiles=True).info
            assert warm.compiles == warm.xla_compiles == 0, \
                (mod.__name__, warm.compiles, warm.xla_compiles)
        finally:
            ex._EXEC_CACHE.clear()
            ex._EXEC_CACHE.update(saved)
