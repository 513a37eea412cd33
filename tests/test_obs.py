"""The repro.obs observability layer: the telemetry compile tag must be
OFF-by-default and bit-neutral (telemetry=0 builds the exact
pre-telemetry program; telemetry>0 changes no shared metric bit), window
sums must equal end-of-run totals at warmup 0 and padded tail steps must
contribute exact zeros; the span tracer must emit valid Chrome
trace-event JSON with well-nested spans, be an exact no-op when not
installed, and the executor must attribute compiles/spans per group."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FamConfig, fam_replace
from repro.core.famsim import SimFlags, build_sim
from repro.core.traces import generate, node_seed
from repro.experiments import (Axis, AxisValue, Experiment, execute,
                               flag_axis, workload_axis)
from repro.obs import (COUNTERS, LAT_EDGES, N_BUCKETS, N_COUNTERS, SpanTracer,
                       counter_index, current_tracer, init_windows,
                       maybe_span, set_tracer, window_index)
from repro.obs.report import (derived_streams, overall_percentiles,
                              render_report, validate_trace_events,
                              window_percentiles)

BASE = SimFlags(core_prefetch=False, dram_prefetch=False)
DRAM = SimFlags()
T, N = 1100, 2
WL = ["LU", "bfs"]


def _node_traces(T_true=T):
    tr = [generate(w, T_true, node_seed(0, i)) for i, w in enumerate(WL)]
    return (np.stack([a for a, _ in tr]), np.stack([g for _, g in tr]))


# ---------------------------------------------------------------------------
# the compile tag
# ---------------------------------------------------------------------------

def test_telemetry_tag_is_static_and_off_by_default():
    """``FamConfig.telemetry`` defaults to 0 and rides the END of
    ``geometry_free_shape()`` (the planner's membership key keeps its
    policy-tag suffix layout)."""
    cfg = FamConfig()
    assert cfg.telemetry == 0
    assert cfg.geometry_free_shape()[-1] == 0
    on = fam_replace(cfg, telemetry=8)
    assert on.geometry_free_shape()[-1] == 8
    assert on.geometry_free_shape()[:-1] == cfg.geometry_free_shape()[:-1]
    assert on.static_shape() != cfg.static_shape()


def test_telemetry_registered_with_analyzer_and_search_guard():
    """The analyzer's static-field registry picks the tag up (zero new
    allowlist waivers) and repro.search refuses to sweep it silently."""
    from repro.analysis.registry import build_registry
    from repro.search.space import STATIC_CFG_FIELDS
    reg, findings = build_registry()
    assert "telemetry" in reg.static_config_fields
    assert not findings
    assert "telemetry" in STATIC_CFG_FIELDS


def test_plan_groups_unchanged_by_telemetry():
    """Turning telemetry on splits NO group: it is uniform across every
    point (it rides the base config), so group COUNT and membership are
    identical — only the group keys gain the tag."""
    def _exp(tele):
        return Experiment(
            name="obs_groups", T=T,
            base=fam_replace(FamConfig(), telemetry=tele),
            axes=(workload_axis(WL),
                  flag_axis("variant", {"base": BASE, "dram": DRAM})))
    off, on = _exp(0).plan(), _exp(6).plan()
    assert off.num_groups == on.num_groups == 1
    assert [g.indices for g in off.groups] == [g.indices for g in on.groups]
    assert off.groups[0].key != on.groups[0].key
    # group static_shape = (pad_sets, pad_ways) + geometry_free_shape +
    # policy tags; the telemetry tag closes the geometry-free part
    gfs_end = 2 + len(FamConfig().geometry_free_shape())
    assert on.groups[0].key.static_shape[gfs_end - 1] == 6
    assert off.groups[0].key.static_shape[gfs_end - 1] == 0


# ---------------------------------------------------------------------------
# in-graph windowed counters
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def on_off_metrics():
    """One build_sim run per tag value on identical traces, warmup 0
    (so window sums can be compared against end-of-run totals)."""
    addrs, gaps = _node_traces()
    a, g = jnp.asarray(addrs), jnp.asarray(gaps)
    off = build_sim(FamConfig(), DRAM, N)(a, g, warmup_frac=0.0)
    on = build_sim(fam_replace(FamConfig(), telemetry=8), DRAM, N)(
        a, g, warmup_frac=0.0)
    return ({k: np.asarray(v) for k, v in off.items()},
            {k: np.asarray(v) for k, v in on.items()})


def test_telemetry_off_adds_no_metric(on_off_metrics):
    off, _ = on_off_metrics
    assert "telemetry" not in off


def test_telemetry_is_purely_observational(on_off_metrics):
    """The tentpole bit-neutrality bar: every shared metric is
    bit-identical with the accumulator on — telemetry reads the step's
    signals, never feeds back."""
    off, on = on_off_metrics
    assert set(on) == set(off) | {"telemetry"}
    assert on["telemetry"].shape == (8, N_COUNTERS)
    for k, v in off.items():
        np.testing.assert_array_equal(v, on[k], err_msg=k)


def test_window_sums_equal_end_of_run_totals(on_off_metrics):
    """At warmup 0 the windowed streams partition the run exactly:
    events sum to N*T, pf_issued sums to the end-of-run accumulator,
    and the latency histogram holds one count per FAM-bound demand."""
    _, on = on_off_metrics
    tele = on["telemetry"].astype(np.float64)
    assert tele[:, counter_index("events")].sum() == N * T
    np.testing.assert_allclose(
        tele[:, counter_index("pf_issued")].sum(),
        on["prefetches_issued"].sum(), rtol=1e-6)
    hist = tele[:, len(COUNTERS) - len(LAT_EDGES) - 1:]
    np.testing.assert_allclose(hist.sum(),
                               tele[:, counter_index("demand_fam")].sum(),
                               rtol=1e-6)
    # demand_hit <= demand_fam per window; lat_sum positive when fam > 0
    assert (tele[:, counter_index("demand_hit")] <=
            tele[:, counter_index("demand_fam")]).all()


def test_window_index_partitions_evenly():
    idx = np.asarray(window_index(jnp.arange(1000), jnp.int32(1000), 8))
    assert idx.min() == 0 and idx.max() == 7
    assert (np.bincount(idx) == 125).all()          # even partition
    assert (np.diff(idx) >= 0).all()                # monotone
    # padded steps (i >= t_true) clip into the last window
    tail = np.asarray(window_index(jnp.arange(1000, 1200),
                                   jnp.int32(1000), 8))
    assert (tail == 7).all()
    assert init_windows(8).shape == (8, N_COUNTERS)


def test_padded_tail_contributes_exact_zero():
    """A T=700 point executed inside a t_pad=900 group must carry
    telemetry bit-identical to the classic fixed-T runner over the same
    700 events — the 200 masked tail steps add exact zero rows. (The
    device backend generates at t_pad, so the reference is the first 700
    events of the T=900 device trace, as in test_experiments.)"""
    from repro.traces.device import system_traces as dev_traces

    base = fam_replace(FamConfig(), telemetry=5)
    mixed = Experiment(
        name="obs_pad", workloads=("LU",), base=base,
        axes=(Axis("t", (AxisValue("700", T=700),
                         AxisValue("900", T=900))),))
    plan = mixed.plan()
    assert plan.num_groups == 1 and plan.groups[0].t_pad == 900
    padded = execute(plan)
    a, g = dev_traces(["LU"], 900, 0)
    run = build_sim(base, SimFlags(), 1)
    for T_true in (700, 900):
        ref = run(jnp.asarray(a[:, :T_true]), jnp.asarray(g[:, :T_true]))
        np.testing.assert_array_equal(np.asarray(ref["telemetry"]),
                                      padded.get(t=T_true)["telemetry"],
                                      err_msg=f"T={T_true}")


def test_executor_one_compile_group_with_telemetry_on():
    """The fig08/fig16 promise under the tag: a telemetry-on run still
    compiles exactly ONE group executable (proved by the runtime
    watcher), and its per-group row attributes that compile by the
    digest-suffixed runner name."""
    exp = Experiment(                    # T=903: unique exec key -> cold
        name="obs_compiles", T=903,
        base=fam_replace(FamConfig(), telemetry=4),
        axes=(workload_axis(WL),
              flag_axis("variant", {"base": BASE, "dram": DRAM})))
    cold = exp.run(assert_compiles=True).info
    assert cold.planned_groups == 1
    assert cold.compiles == cold.xla_compiles == 1
    assert cold.groups[0]["xla_compiles"] == 1
    assert len(cold.groups[0]["key_digest"]) == 8
    warm = exp.run(assert_compiles=True).info
    assert warm.xla_compiles == 0
    assert warm.groups[0]["xla_compiles"] == 0


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------

def test_span_tracer_emits_valid_nested_chrome_trace(tmp_path):
    tracer = SpanTracer(process_name="test")
    with tracer.span("outer", kind="a"):
        with tracer.span("inner"):
            pass
        tracer.instant("tick")
    payload = tracer.chrome_trace()
    assert validate_trace_events(payload) == []
    names = [e["name"] for e in payload["traceEvents"]]
    assert names[0] == "process_name"            # "M" metadata first
    assert {"outer", "inner", "tick"} <= set(names)
    inner, outer = (next(e for e in payload["traceEvents"]
                         if e["name"] == n) for n in ("inner", "outer"))
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    s = tracer.summary()
    assert s["outer"]["count"] == 1 and s["inner"]["count"] == 1
    assert "tick" not in s                       # instants are not spans
    # save/validate round trip (the CLI's validate path)
    from repro.obs.report import validate_trace
    path = tracer.save(tmp_path / "t.json")
    assert validate_trace(path) == []
    assert json.loads(path.read_text())["traceEvents"]


def test_validate_trace_events_catches_problems():
    ok = {"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0, "pid": 0, "tid": 0}
    assert validate_trace_events({"traceEvents": [ok]}) == []
    # metadata events legitimately carry no ts
    meta = {"name": "process_name", "ph": "M", "pid": 0,
            "args": {"name": "x"}}
    assert validate_trace_events({"traceEvents": [meta, ok]}) == []
    missing = validate_trace_events({"traceEvents": [{"name": "b",
                                                      "ph": "X"}]})
    assert missing and "missing" in missing[0]
    bad_nest = validate_trace_events({"traceEvents": [
        ok, {"name": "child", "ph": "X", "ts": 5.0, "dur": 10.0,
             "pid": 0, "tid": 0}]})
    assert bad_nest and "overlaps" in bad_nest[0]
    assert validate_trace_events({}) == ["traceEvents missing or empty"]


def test_maybe_span_is_noop_without_tracer():
    assert current_tracer() is None
    with maybe_span("nothing") as t:
        assert t is None
    tracer = SpanTracer()
    prev = set_tracer(tracer)
    try:
        assert prev is None and current_tracer() is tracer
        with maybe_span("something", tag=1) as t:
            assert t is tracer
    finally:
        set_tracer(prev)
    assert current_tracer() is None
    assert tracer.summary()["something"]["count"] == 1


def test_executor_records_spans_per_group():
    """With a tracer installed, execute() wraps its phases in spans and
    summarizes them onto RunInfo.spans (and as_dict)."""
    exp = Experiment(name="obs_spans", T=600,
                     axes=(workload_axis(WL),))
    tracer = SpanTracer()
    prev = set_tracer(tracer)
    try:
        info = exp.run().info
    finally:
        set_tracer(prev)
    assert info.spans is not None
    for name in ("execute", "trace_stage", "run", "device_call", "fetch"):
        assert info.spans[name]["count"] >= 1, (name, info.spans)
    assert info.spans["execute"]["count"] == 1
    assert validate_trace_events(tracer.chrome_trace()) == []
    d = info.as_dict()
    assert d["spans"] == info.spans
    assert d["us_per_event"] == round(info.us_per_call(), 4)
    # without a tracer, spans stay None and off the dict
    info2 = exp.run().info
    assert info2.spans is None and "spans" not in info2.as_dict()


def test_span_tracer_keeps_absolute_times_and_calls_listeners():
    import threading
    import time

    tracer = SpanTracer()
    heard = []
    tracer.listeners.append(lambda *a: heard.append(a))
    t_before = time.perf_counter()
    with tracer.span("outer"):
        with tracer.span("inner"):
            pass
        th = threading.Thread(target=_span_in, args=(tracer, "worker"))
        th.start()
        th.join(timeout=10)
        assert not th.is_alive()
    t_after = time.perf_counter()
    by = {n: (s, e) for n, s, e in tracer.spans}
    assert [n for n, _, _ in tracer.spans] == ["inner", "worker", "outer"]
    assert t_before <= by["outer"][0] <= by["inner"][0] <= by["inner"][1] \
        <= by["worker"][0] <= by["worker"][1] <= by["outer"][1] <= t_after
    assert [(n, w) for n, w, _ in heard] == [
        ("outer", "start"), ("inner", "start"), ("inner", "end"),
        ("worker", "start"), ("worker", "end"), ("outer", "end")]
    assert [t for _, _, t in heard] == [
        by["outer"][0], by["inner"][0], by["inner"][1], by["worker"][0],
        by["worker"][1], by["outer"][1]]
    # the Chrome events are the same spans, relative to the tracer's start
    ev = {e["name"]: e for e in tracer.chrome_trace()["traceEvents"]}
    assert ev["inner"]["ts"] == pytest.approx(
        (by["inner"][0] - tracer._t0) * 1e6, abs=0.1)
    assert ev["worker"]["tid"] != ev["outer"]["tid"]


def _span_in(tracer, name):
    with tracer.span(name):
        pass


def test_executor_stages_in_three_spans_inside_trace_stage():
    """``trace_stage`` holds one span per staging phase: trace encodings,
    the per-system params, their stacking."""
    exp = Experiment(name="obs_stage_spans", T=600,
                     axes=(workload_axis(WL),))
    tracer = SpanTracer()
    prev = set_tracer(tracer)
    try:
        info = exp.run().info
    finally:
        set_tracer(prev)
    stages = [s for s in tracer.spans if s[0] == "trace_stage"]
    assert len(stages) == info.planned_groups >= 1
    for name in ("stage.traces", "stage.params", "stage.stack"):
        inside = [(s, e) for n, s, e in tracer.spans if n == name]
        assert len(inside) == len(stages), name
        for s, e in inside:
            assert any(a <= s <= e <= b for _, a, b in stages), name
    assert validate_trace_events(tracer.chrome_trace()) == []


def test_run_info_us_per_call_zero_event_guard():
    from repro.experiments.executor import RunInfo
    info = RunInfo(planned_groups=0, run_s=1.0)
    assert info.events == 0
    assert info.us_per_call() == 0.0
    assert info.as_dict()["us_per_event"] == 0.0


def test_compile_watcher_by_name_attribution():
    import jax

    from repro.analysis.runtime import CompileWatcher

    def famsim_group(x):
        return x * 2.0
    famsim_group.__name__ = famsim_group.__qualname__ = \
        "famsim_group__feedf00d"
    with CompileWatcher() as w:
        jax.jit(famsim_group)(jnp.float32(3.0))
    assert w.count == 1
    assert w.by_name == {"famsim_group__feedf00d": 1}


# ---------------------------------------------------------------------------
# report rendering
# ---------------------------------------------------------------------------

def _synthetic_windows(n=4):
    w = np.zeros((n, N_COUNTERS))
    w[:, counter_index("events")] = 100.0
    w[:, counter_index("demand_fam")] = 40.0
    # hit rate ramps 0.25 -> 1.0 across windows
    w[:, counter_index("demand_hit")] = 10.0 * (1 + np.arange(n))
    w[:, counter_index("pf_issued")] = 40.0
    # all demands in the 256-edge bucket except window 0 (all overflow)
    hist0 = counter_index("lat_le_128")
    w[1:, hist0 + 2] = 40.0
    w[0, counter_index(f"lat_gt_{int(LAT_EDGES[-1])}")] = 40.0
    return w


def test_derived_streams_and_percentiles():
    w = _synthetic_windows()
    d = derived_streams(w)
    np.testing.assert_allclose(d["hit_rate"], [0.25, 0.5, 0.75, 1.0])
    np.testing.assert_allclose(d["pf_accuracy"], d["hit_rate"])
    tails = window_percentiles(w)
    assert tails["p50"][0] > LAT_EDGES[-1]          # overflow bucket
    assert LAT_EDGES[1] <= tails["p50"][1] <= LAT_EDGES[2]
    overall = overall_percentiles(w)
    assert overall["p50"] <= overall["p95"] <= overall["p99"]
    with pytest.raises(ValueError, match="telemetry"):
        derived_streams(np.zeros((4, 3)))


def test_render_report_dashboard():
    payload = {"figure": "synthetic", "n_windows": 4,
               "counters": list(COUNTERS), "lat_edges": list(LAT_EDGES),
               "points": [{"coords": {"workload": "LU", "variant": "dram"},
                           "nodes": 1, "T": 400,
                           "windows": _synthetic_windows().tolist()}]}
    text = render_report(payload, fmt="text")
    assert "hit-rate ramp" in text and "time-to-warm" in text
    assert "workload=LU" in text
    md = render_report(payload, fmt="md")
    assert "| win |" in md and "|---" in md


# ---------------------------------------------------------------------------
# the shared bucket estimators (repro.obs.report — imported by
# repro.tenants.metrics; the single percentile implementation)
# ---------------------------------------------------------------------------

def test_bucket_percentile_exact_interpolation():
    from repro.obs import bucket_percentile

    counts = np.zeros(N_BUCKETS)
    counts[0] = 10.0                       # bucket [0, 128)
    counts[-1] = 10.0                      # overflow [4096, 6144]
    # p50 lands exactly at the top of bucket 0
    assert bucket_percentile(counts, 50.0) == pytest.approx(128.0)
    # p75 is 5/10 into the overflow bucket: 4096 + 0.5 * 2048
    assert bucket_percentile(counts, 75.0) == pytest.approx(5120.0)
    # q=100 tops out at the capped overflow edge
    assert bucket_percentile(counts, 100.0) == pytest.approx(6144.0)
    # single mid bucket [181, 256): p50 interpolates to the midpoint
    one = np.zeros(N_BUCKETS)
    one[2] = 8.0
    assert bucket_percentile(one, 50.0) == pytest.approx(218.5)
    # empty histogram reports 0, not NaN
    assert bucket_percentile(np.zeros(N_BUCKETS), 99.0) == 0.0
    # accepts plain lists (np coercion happens inside)
    assert bucket_percentile([0.0] * 11 + [4.0], 50.0) > LAT_EDGES[-1]


def test_bucket_exceedance_interpolates_threshold():
    from repro.obs import bucket_exceedance

    counts = np.zeros(N_BUCKETS)
    counts[2] = 8.0                        # all mass in [181, 256)
    # threshold at the bucket floor: everything exceeds
    assert bucket_exceedance(counts, 181.0) == pytest.approx(8.0)
    # midpoint: half the bucket exceeds (uniform-in-bucket assumption)
    assert bucket_exceedance(counts, 218.5) == pytest.approx(4.0)
    # at/above the bucket ceiling: nothing does
    assert bucket_exceedance(counts, 256.0) == pytest.approx(0.0)
    assert bucket_exceedance(counts, 10_000.0) == 0.0
    # threshold <= 0 counts the whole histogram
    assert bucket_exceedance(counts, 0.0) == pytest.approx(8.0)
    # round-trip with the percentile estimator: by construction ~5% of
    # the mass sits above the p95 estimate
    mixed = np.arange(N_BUCKETS, dtype=float)
    from repro.obs import bucket_percentile
    p95 = bucket_percentile(mixed, 95.0)
    assert bucket_exceedance(mixed, p95) == pytest.approx(
        0.05 * mixed.sum(), rel=1e-6)
