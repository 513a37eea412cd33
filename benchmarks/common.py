"""Shared harness for the paper-figure benchmarks.

Each figure module exposes ``run(quick: bool) -> list[dict]`` returning rows
with at least {name, us_per_call, derived}; ``benchmarks.run`` prints the
``name,us_per_call,derived`` CSV (scaffold contract) and dumps the full rows
to results/benchmarks/<figure>.json.

Figures of merit follow paper §V-A: IPC gain is measured against the
*baseline config* (no core prefetch, no DRAM-cache prefetch) of the same
workload/node-count; relative FAM latency likewise; relative prefetches are
against the non-adaptive (FIFO) prefetcher.

Execution goes through :mod:`repro.experiments`: every figure declares its
grid as an :class:`~repro.experiments.Experiment` (named axes over config
overrides x flags x workloads), ``plan()`` resolves it into compile groups
keyed by ``(geometry_free_shape, N, T_bucket)`` — cache geometry pads to
each group's maximum and the system axis to canonical widths, so even
block-size/cache-size sweeps (fig08/fig16) are ONE group — and
``execute()`` runs each group as ONE ahead-of-time compile and ONE
(optionally device-sharded) vmapped call. Traces come from the selected
``repro.traces`` backend: ``device`` (default) synthesizes them IN GRAPH
inside the group executable (zero host-side generation), ``numpy`` keeps
the host reference generators (``--trace-backend`` on benchmarks.run).
Compile time is measured separately from steady-state run time, so
reported us_per_call never includes compilation; under the device
backend the steady-state group call DOES include the fused in-graph
trace generation (its standalone cost is recorded as
``device_kernel_gen_s`` in fig14's ``trace_gen_compare``), so
cross-backend us_per_call comparisons compare generation+simulation
against simulation-after-host-staging.

``Point``/``run_points`` remain as a deprecated shim over the same
machinery; new code should declare an ``Experiment``.
"""
from __future__ import annotations

import json
import os
import time
import warnings
from contextlib import contextmanager
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.configs.base import FamConfig, fam_replace
from repro.core.famsim import SimFlags, build_sim
from repro.core.ipc_model import geomean
from repro.experiments import (ExperimentResult, ResolvedPoint, RunInfo,
                               execute, plan_points, trace_arrays)

RESULTS = Path(__file__).resolve().parent.parent / "results" / "benchmarks"
#: JAX's persistent compilation cache where ``JAX_COMPILATION_CACHE_DIR``
#: is not set. Fixed, because the path is part of every entry's key.
COMPILE_CACHE = RESULTS.parent.parent / ".jax_cache"

# default workload subset (one per suite + the cache/BW-sensitive ones the
# paper highlights); --full runs all 19
QUICK_WORKLOADS = ["603.bwaves_s", "628.pop2_s", "LU", "bfs", "canneal",
                   "mg"]

BASELINE = SimFlags(core_prefetch=False, dram_prefetch=False)
CORE = SimFlags(dram_prefetch=False)
DRAM = SimFlags()
ADAPT = SimFlags(bw_adapt=True)


def WFQ(w: int) -> SimFlags:
    return SimFlags(wfq=True, wfq_weight=w)


def use_compile_cache() -> str:
    """Keep compiled executables across processes; call before the first
    compile. JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so where it
    is set this changes nothing; otherwise the cache goes to
    :data:`COMPILE_CACHE`. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    return str(COMPILE_CACHE)


# ---------------------------------------------------------------------------
# Deprecated Point/run_points shim (use repro.experiments instead)
# ---------------------------------------------------------------------------

#: Kept as an import-compatible alias; the accounting object now lives in
#: ``repro.experiments.executor``.
SweepInfo = RunInfo


@dataclass(frozen=True)
class Point:
    """One simulated system of a figure's sweep grid (DEPRECATED — declare
    an :class:`repro.experiments.Experiment` instead)."""

    cfg: FamConfig
    flags: SimFlags
    workloads: Tuple[str, ...]     # one entry per node
    seed: int = 0


#: The Point/run_points deprecation fires exactly ONCE per process (the
#: default ``warnings`` filter already dedupes per call site, but the shim
#: is reached from many call sites — tests reset this flag to re-arm it).
_SHIM_WARNED = False


def _warn_shim_deprecated() -> None:
    global _SHIM_WARNED
    if _SHIM_WARNED:
        return
    _SHIM_WARNED = True
    warnings.warn(
        "benchmarks.common.run_points/Point are deprecated; declare a "
        "repro.experiments.Experiment (see docs/experiments.md)",
        DeprecationWarning, stacklevel=3)


def run_points(points: Sequence[Point], T: int
               ) -> Tuple[List[Dict[str, np.ndarray]], RunInfo]:
    """DEPRECATED: run every point, batching shared compiled shapes.

    Thin shim over ``repro.experiments.plan_points`` + ``execute``; returns
    (metrics aligned with ``points`` — each a dict of (N,) arrays — and the
    wall-clock/compile accounting), exactly like the PR-1 harness did.
    """
    _warn_shim_deprecated()
    resolved = [ResolvedPoint(cfg=p.cfg, flags=p.flags,
                              workloads=tuple(p.workloads), T=T,
                              seed=p.seed, coords=(("point", str(i)),))
                for i, p in enumerate(points)]
    result = execute(plan_points(resolved, name="run_points"))
    return list(result.metrics), result.info


_DEV_TRACE_CACHE: Dict = {}


def _traces(workloads: Sequence[str], T: int, seed: int,
            trace_backend: str = "numpy") -> Tuple[np.ndarray, np.ndarray]:
    """Node traces for one system. The numpy backend shares the executor's
    memoized cache; the device backend pulls the device-generated bits to
    host (identical to what the in-graph path feeds the simulation at the
    same T — see repro.traces.device), memoized per (workloads, T, seed)
    so engine_check points differing only in cfg/flags pull them once."""
    if trace_backend == "device":
        from repro.traces import system_traces
        key = (tuple(workloads), T, seed)
        if key not in _DEV_TRACE_CACHE:
            _DEV_TRACE_CACHE[key] = system_traces(workloads, T, seed,
                                                  backend="device")
        return _DEV_TRACE_CACHE[key]
    return trace_arrays(workloads, T, seed)


# ---------------------------------------------------------------------------
# Per-point reference path (kept for the engine cross-check + unit tests)
# ---------------------------------------------------------------------------

_SIM_CACHE: Dict = {}
_SIM_COMPILE_S: Dict = {}


def run_sim(cfg: FamConfig, flags: SimFlags, workloads: Sequence[str],
            T: int, seed: int = 0, trace_backend: str = "numpy"
            ) -> Tuple[Dict[str, np.ndarray], float]:
    """One system through the classic per-point path.

    Returns (metrics, steady-state wall seconds): the first call per
    (cfg, flags, N, T) warms the jit cache and its compile time is recorded
    separately (``per_point_compile_seconds``) — the timed call is a second,
    fully synchronized execution (``block_until_ready``), so the returned
    seconds reflect simulation only. ``trace_backend`` selects the trace
    source (pre-staged device traces reproduce the executor's in-graph
    generation bit-exactly at the same T).
    """
    import jax
    import jax.numpy as jnp
    N = len(workloads)
    key = (cfg, flags, N)
    if key not in _SIM_CACHE:
        _SIM_CACHE[key] = build_sim(cfg, flags, N)
    run = _SIM_CACHE[key]
    addrs, gaps = _traces(workloads, T, seed, trace_backend)
    addrs, gaps = jnp.asarray(addrs), jnp.asarray(gaps)
    warm_key = (cfg, flags, N, T)
    if warm_key not in _SIM_COMPILE_S:
        t0 = time.perf_counter()
        jax.block_until_ready(run(addrs, gaps))
        _SIM_COMPILE_S[warm_key] = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(run(addrs, gaps))
    dt = time.perf_counter() - t0
    return {k: np.asarray(v) for k, v in out.items()}, dt


def engine_check(points: Sequence[ResolvedPoint],
                 batched: Sequence[Dict[str, np.ndarray]],
                 T: Optional[int] = None,
                 trace_backend: str = "numpy") -> dict:
    """Cross-check a subset of batched results against the per-point path
    (fed by the SAME trace backend, so the comparison stays bit-level).

    Each point's true T comes from ``pt.T`` (``T`` is a fallback for bare
    Point shims). Returns a JSON-able record with the max relative metric
    difference plus the per-point cost split: one steady run per point,
    and — for compile keys first warmed during THIS check — the compile
    time alone (warm-up minus that point's steady run, matching what the
    old one-compile-per-point paradigm actually paid)."""
    max_rel = 0.0
    steady = 0.0
    compile_s = 0.0
    for pt, got in zip(points, batched):
        T_pt = getattr(pt, "T", None) or T
        key = (pt.cfg, pt.flags, len(pt.workloads), T_pt)
        fresh = key not in _SIM_COMPILE_S
        ref, dt = run_sim(pt.cfg, pt.flags, list(pt.workloads), T_pt,
                          pt.seed, trace_backend)
        steady += dt
        if fresh:
            compile_s += max(_SIM_COMPILE_S[key] - dt, 0.0)
        for k, v in ref.items():
            rel = float(np.max(np.abs(v - got[k]) /
                               np.maximum(np.abs(v), 1e-9)))
            max_rel = max(max_rel, rel)
    return {"points_checked": len(points), "max_rel_diff": max_rel,
            "per_point_steady_s": round(steady, 3),
            "per_point_compile_s": round(compile_s, 3),
            "matches_1e-5": bool(max_rel < 1e-5)}


def engine_row(name: str, result: ExperimentResult,
               check_pts: Sequence[ResolvedPoint]) -> dict:
    """The ``*_engine`` acceptance row shared by fig08/fig16: per-point
    cross-check + recorded wall-clock comparison (and the sharded-vs-vmap
    bit-exactness record in ``engine.shard_check``).

    The per-point estimate scales the checked subset's cost to the whole
    figure the way the old path would have paid it: one compile per unique
    (cfg, flags, N) key plus one steady run per point. The cross-check
    inherits the result's trace backend, so device-backend figures verify
    in-graph generation against pre-staged traces bit-exactly — which
    requires every checked point to have executed at its own true T
    (device threefry draws are shaped, so a point padded to a LONGER
    group t_pad carries a different trace prefix than a standalone
    T-length generation). All figures are uniform-T per group, so the
    per-point assertion below is a tripwire for future mixed-T figures,
    not a live path."""
    info = result.info
    points = result.points
    if info.trace_backend == "device":
        bad = [(p.coords, p.T, result.t_pad_for(p)) for p in check_pts
               if result.t_pad_for(p) != p.T]
        assert not bad, (
            "device-backend engine_check needs check points that executed "
            "at their own true T (own group's t_pad); pre-stage at t_pad "
            "and truncate to extend it to mixed-T groups", bad)
    check = engine_check(check_pts,
                         [result.metrics_for(p) for p in check_pts],
                         trace_backend=info.trace_backend)
    uniq = lambda pts: len({(p.cfg, p.flags, len(p.workloads)) for p in pts})
    est_full = (check["per_point_compile_s"] *
                uniq(points) / max(uniq(check_pts), 1) +
                check["per_point_steady_s"] *
                len(points) / max(len(check_pts), 1))
    batched_total = info.compile_s + info.run_s
    return {
        "name": name,
        "us_per_call": info.us_per_call(),
        # derived carries only deterministic metric content (acceptance:
        # identical derived strings across processes); timings go in the
        # JSON-only fields below
        "derived": (f"max_rel_diff={check['max_rel_diff']:.2e};"
                    f"matches_1e-5={check['matches_1e-5']}"),
        "engine": info.as_dict(),
        "check": check,
        "per_point_est_wall_s": round(est_full, 3),
        "batched_wall_s": round(batched_total, 3),
        "speedup_vs_per_point": round(est_full / max(batched_total, 1e-9), 2),
    }


def info_row(name: str, info: RunInfo, **extra) -> dict:
    """The lightweight ``*_engine`` row used by figures without a per-point
    cross-check: planned groups + the full accounting (per-group compile
    and run wall-clock, trace backend + host-trace counter, sharding
    record). ``extra`` JSON-only fields (e.g. fig14's
    ``trace_gen_compare``) ride along; ``derived`` stays deterministic."""
    return {"name": name, "us_per_call": info.us_per_call(),
            "derived": f"groups={info.planned_groups}",
            "engine": info.as_dict(), **extra}


def trace_gen_compare(plan) -> dict:
    """Device-vs-numpy trace *generation* wall-clock at this figure's
    scale — the acceptance record fig14 dumps into its engine JSON row.

    The number that matters to the executor's steady-state path is the
    HOST wall-clock each backend spends before the simulator can run:

    * ``numpy_host_gen_s`` — generating every node trace and staging the
      group's padded ``(S_exec, N, T_pad)`` arrays, measured with a cold
      memo cache (what a fresh process pays; the executor can only hide
      it under the previous group's simulation, and the first group has
      no previous group);
    * ``device_host_stage_s`` — stacking the per-node ``TraceParams``
      scalars (the device backend's ENTIRE host-side cost; generation
      itself happens in graph, fused with the simulation) — measured
      with the spec-encoding lru caches cleared too, so both backends
      pay fresh-process cost symmetrically.

    ``device_not_slower`` is ``device_host_stage_s <= numpy_host_gen_s``.
    The fused in-graph generation is also measured standalone
    (``device_kernel_gen_s``, steady-state, compile separate) so the JSON
    records what the device actually spends inside the group call — on a
    single CPU device that throughput is comparable to numpy's; the
    architectural win is that it leaves the host path entirely and
    scales with ``vmap``/``shard_map`` across devices.

    Deliberately coupled to executor internals (``_prepare`` /
    ``_pad_systems`` / ``_TRACE_CACHE``): the whole point is to time the
    executor's OWN staging path, not a reimplementation of it. The
    forced-cold measurement evicts the process-global spec-encoding lru
    caches; the timed device ``_prepare`` repopulates them for this
    plan's workloads, so only unrelated workloads repay their (~ms)
    encoding afterwards."""
    import jax

    from repro.experiments import executor as _ex
    from repro.traces import device as dev

    host_np = host_dev = kernel_dev = compile_dev = 0.0
    events = 0
    for g in plan.groups:
        idxs = _ex._pad_systems(g.indices, g.s_pad, 1)
        saved = dict(_ex._TRACE_CACHE)
        _ex._TRACE_CACHE.clear()
        try:
            d_np = _ex._prepare(plan.points, idxs, g.t_pad, 0.2, "numpy")
        finally:
            _ex._TRACE_CACHE.update(saved)
        dev.trace_params.cache_clear()        # symmetric fresh-process cost
        dev._head_cdf.cache_clear()
        d_dev = _ex._prepare(plan.points, idxs, g.t_pad, 0.2, "device")
        host_np += d_np.prep_s
        host_dev += d_dev.prep_s
        (tp,) = d_dev.inputs
        fn = jax.jit(jax.vmap(jax.vmap(dev.node_generator(g.t_pad))))
        t0 = time.perf_counter()
        compiled = fn.lower(tp).compile()
        compile_dev += time.perf_counter() - t0
        jax.block_until_ready(compiled(tp))           # warm dispatch
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(tp))
        kernel_dev += time.perf_counter() - t0
        events += len(idxs) * g.key.num_nodes * g.t_pad
    return {
        "events_staged": events,
        "numpy_host_gen_s": round(host_np, 4),
        "device_host_stage_s": round(host_dev, 4),
        "device_kernel_gen_s": round(kernel_dev, 4),
        "device_kernel_compile_s": round(compile_dev, 4),
        "host_speedup": round(host_np / max(host_dev, 1e-9), 1),
        "device_not_slower": bool(host_dev <= host_np),
    }


# ---------------------------------------------------------------------------
# observability surfacing (repro.obs — docs/observability.md)
# ---------------------------------------------------------------------------

TRACE_DIR = RESULTS.parent / "trace"
TELEMETRY_DIR = RESULTS.parent / "telemetry"


@contextmanager
def obs_tracer(figure: str, telemetry: int):
    """Install a host span tracer for one figure run (``--telemetry``).

    With ``telemetry == 0`` this is an exact no-op (the default path
    records nothing). Otherwise every instrumented layer under the block
    — Experiment.plan, the executor's compile/trace_stage/run/fetch —
    lands in one nested timeline saved to ``results/trace/<figure>.json``
    (Chrome trace-event JSON; load it in ui.perfetto.dev)."""
    if not telemetry:
        yield None
        return
    from repro.obs import SpanTracer, set_tracer
    tracer = SpanTracer(process_name=f"benchmarks:{figure}")
    prev = set_tracer(tracer)
    try:
        with tracer.span(figure, cat="figure", telemetry=telemetry):
            yield tracer
    finally:
        set_tracer(prev)
        tracer.save(TRACE_DIR / f"{figure}.json")


def save_telemetry(figure: str, result: ExperimentResult,
                   n_windows: int) -> Optional[Path]:
    """Dump every point's windowed counter matrix to
    ``results/telemetry/<figure>.json`` — the payload ``python -m
    repro.obs report`` renders. Returns None when the result carries no
    telemetry (the flag was off)."""
    from repro.obs import COUNTERS, LAT_EDGES
    points = []
    for pt in result.points:
        m = result.metrics_for(pt)
        if "telemetry" not in m:
            continue
        points.append({"coords": dict(pt.coords),
                       "nodes": len(pt.workloads), "T": pt.T,
                       "windows": np.asarray(m["telemetry"]).tolist()})
    if not points:
        return None
    TELEMETRY_DIR.mkdir(parents=True, exist_ok=True)
    path = TELEMETRY_DIR / f"{figure}.json"
    path.write_text(json.dumps(
        {"figure": figure, "n_windows": n_windows,
         "counters": list(COUNTERS), "lat_edges": list(LAT_EDGES),
         "points": points}))
    return path


def windowed_tail(metrics) -> Optional[dict]:
    """JSON-only windowed tail-latency summary (None when telemetry is
    off): per-window p95/p99 plus overall p50/p95/p99, estimated from
    the in-graph histogram buckets (``repro.obs.report``). Accepts one
    point's metrics dict or a raw ``(n_windows, N_COUNTERS)`` matrix
    (histogram counts sum across points, so callers may aggregate).
    Rides the JSON rows of fig10/fig12 — never the deterministic
    ``derived`` string."""
    if isinstance(metrics, dict):
        if "telemetry" not in metrics:
            return None
        w = np.asarray(metrics["telemetry"])
    else:
        w = np.asarray(metrics)
    from repro.obs.report import overall_percentiles, window_percentiles
    return {"overall": overall_percentiles(w),
            **window_percentiles(w, qs=(95, 99))}


# ---------------------------------------------------------------------------
# misc row helpers
# ---------------------------------------------------------------------------

def save_rows(figure: str, rows: List[dict]):
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / f"{figure}.json").write_text(json.dumps(rows, indent=2))


def plan_lines(plan, axes=None) -> List[str]:
    """The ``--plan`` dry-run text for one resolved plan: the summary
    line, an ``axes:`` line naming every axis and its size (so
    programmatic ``grid_axis`` grids — e.g. fig_pond's fleet cells — are
    inspectable before running), and one line per compile group. Shared
    by ``run.py --plan`` and ``fig_pond --plan``; deterministic, so
    tests assert the one-group ceilings on this exact output."""
    events = plan.events()
    padded = plan.padded_events()
    lines = [f"{plan.name}: {plan.num_groups} group(s), "
             f"{plan.num_points} points, {events} events "
             f"(+{padded} padded, {padded / max(events, 1):.1%} overhead)"]
    if axes:
        lines.append("  axes: " + " x ".join(
            f"{a.name}({len(a.values)})" for a in axes))
    for i, d in enumerate(plan.describe()):
        lines.append(f"  group {i}: S={d['S']} S_pad={d['S_pad']} "
                     f"N={d['N']} T_pad={d['T_pad']} "
                     f"pad_geom=({d['pad_sets']}x{d['pad_ways']}) "
                     f"key={d['static_shape']}")
    return lines


def workloads(quick: bool) -> List[str]:
    if quick:
        return QUICK_WORKLOADS
    from repro.traces import WORKLOAD_NAMES
    return list(WORKLOAD_NAMES)
