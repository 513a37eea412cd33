"""Reading a profiler capture by the program's names: its host spans (one
``TraceAnnotation`` per ``repro.obs`` span) and its in-graph scopes (the
``op_name`` metadata of the executable's optimized HLO)."""
import glob
import importlib.util
import json
import threading
from pathlib import Path

import pytest

DATA = Path(__file__).resolve().parent / "data"


def _capture(tmp_path, body):
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return ProfileData.from_file(path)


def test_program_spans_land_on_the_host_plane(tmp_path):
    """With or without a tracer installed, every ``maybe_span`` is on the
    capture's host plane, a worker thread's too, and ``host_spans``
    returns them on the trace's clock."""
    import profile_scopes
    from repro.obs.spans import SpanTracer, maybe_span, set_tracer

    def staged():
        with maybe_span("trace_stage"):
            with maybe_span("stage.traces"):
                pass
            with maybe_span("stage.params"):
                pass
            with maybe_span("stage.stack"):
                pass

    def body():
        staged()
        worker = threading.Thread(target=staged)
        worker.start()
        worker.join(timeout=10)
        assert not worker.is_alive()
        prev = set_tracer(tracer)
        try:
            with maybe_span("device_call"):
                pass
        finally:
            set_tracer(prev)

    tracer = SpanTracer()
    pd = _capture(tmp_path, body)
    spans = profile_scopes.host_spans(pd)
    names = [n for n, _, _ in spans]
    for name in ("trace_stage", "stage.traces", "stage.params",
                 "stage.stack"):
        assert names.count(name) == 2, (name, names)
    assert names.count("device_call") == 1
    assert [n for n, _, _ in tracer.spans] == ["device_call"]
    stages = [(s, e) for n, s, e in spans if n == "trace_stage"]
    for n, s, e in spans:
        assert s <= e
        if n.startswith("stage."):
            assert any(a <= s <= e <= b for a, b in stages), n
    # ordered by start; the annotation holds the tracer's span
    assert [s for _, s, _ in spans] == sorted(s for _, s, _ in spans)
    (_, t0, t1), = [x for x in spans if x[0] == "device_call"]
    (_, a0, a1), = tracer.spans
    assert a1 - a0 <= (t1 - t0) + 1e-5
    assert profile_scopes.host_spans(pd, names=("fetch",)) == []


def test_scope_path_unwraps_vmap_and_keeps_nesting():
    import profile_scopes as ps
    name = ("jit(famsim_group__ab)/vmap()/while/body/closed_call/phase_a/"
            "vmap(cache_lookup)/gather")
    assert ps.scope_path(name) == ("phase_a", "cache_lookup")
    assert ps.scope_path("jit(f)/vmap(trace_gen)/vmap(jit(_uniform))/add") \
        == ("trace_gen",)
    assert ps.scope_path("jit(f)/while/body/dynamic_slice") == ()
    assert ps._step_op("jit(f)/while/body/closed_call/sched/sort")
    # an op in a loop of its own runs more than once a step
    assert not ps._step_op(
        "jit(f)/while/body/closed_call/phase_c/vmap(cache_fill)/while/body/"
        "scatter")
    assert not ps._step_op("jit(f)/vmap(metrics)/div")


def test_op_names_and_labels_from_hlo_text():
    import profile_scopes as ps
    text = "\n".join([
        '  %fusion.12 = s32[4]{0} fusion(%p.1), kind=kLoop, calls=%f.3, '
        'metadata={op_name="jit(f)/while/body/phase_a/cache_lookup/gather" '
        'source_file="x.py" source_line=3}',
        '  ROOT %copy.2 = s32[4]{0} copy(%fusion.12), '
        'metadata={op_name="jit(f)/while/body/sched/sort"}',
        '  %bitcast.7 = s32[4]{0} bitcast(%copy.2)',
    ])
    names = ps.op_names(text)
    assert names == {"fusion.12": "jit(f)/while/body/phase_a/cache_lookup/"
                                  "gather",
                     "copy.2": "jit(f)/while/body/sched/sort"}
    assert ps.label("fusion.12", names) == "phase_a/cache_lookup/fusion.12"
    assert ps.label("copy.2", names) == "sched/copy.2"
    assert ps.label("bitcast.7", names) == "bitcast.7"


def test_recorded_scoped_scan():
    """A jitted 48-step ``lax.scan`` whose body has the step's three
    scopes (a row gather under ``phase_a/cache_lookup``, a sort under
    ``sched``, a row scatter under ``phase_c/cache_fill``), input
    arithmetic under ``trace_gen`` and a reduction under ``metrics``;
    one call recorded on one TPU v5e with its ``device_call`` span, and
    the executable's optimized HLO text."""
    from jax.profiler import ProfileData

    import profile_reduce as pr
    import profile_scopes as ps
    facts = json.loads((DATA.parents[1] / "chips.json").read_text())
    facts = facts["kinds"]["TPU v5 lite"]
    pre, lines = facts["trace_plane_prefix"], facts["trace_op_lines"]
    pd = ProfileData.from_file(str(DATA / "scoped.xplane.pb"))
    hlo = (DATA / "scoped.hlo.txt").read_text()
    lo, hi = pr.slice_bounds(pd)
    out = ps.scopes(pd, hlo, lo, hi, pre, lines)
    assert out["iterations"] == 48
    for k in ("phase_a", "sched", "phase_c"):
        assert out[k] > 0, (k, out)
    # every op's self time is counted once, under one scope or unscoped
    ops = pr.device_ops(pd, pre, lines)
    total = sum(d for evs in ops.values()
                for d in pr.self_times(evs, lo, hi).values())
    parts = sum(v for k, v in out.items()
                if k not in ("iterations", "unscoped_body"))
    assert parts * 1e9 == pytest.approx(total, rel=1e-9)
    assert 0 < out["unscoped_body"] <= out["unscoped"]
    # the scan's own ops: its while, its body's unscoped bookkeeping; not
    # the input arithmetic before it nor the reduction after it
    names = ps.op_names(hlo)
    in_scan = ps.scan_ops(hlo, names)
    assert "while.3" in in_scan
    assert any(op not in names for op in in_scan)      # no metadata
    outside = [op for op, n in names.items()
               if ps.scope_path(n)[:1] in (("trace_gen",), ("metrics",))]
    assert outside and not set(outside) & in_scan
    # the gaps are named on the trace's own clock, no offset needed
    red = pr.reduce(pd, ps.host_spans(pd), 0, pre, lines)
    assert "stage.params" in red["idle_by_span"]
    labelled = [ps.label(op, names) for op, _ in red["device_ops"]]
    assert any(x.startswith(("phase_a/", "sched/", "phase_c/"))
               for x in labelled), labelled


def test_scopes_reads_nothing_from_an_unscoped_executable():
    import profile_scopes as ps

    class Empty:
        planes = []
    assert ps.scopes(Empty(), "%a.1 = s32[] add(), "
                     'metadata={op_name="jit(f)/add"}', 0, 1,
                     "/device:TPU:", ["XLA Ops"]) is None


def _metric(name):
    path = DATA.parents[1] / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_stage_ms_leaves_out_the_traced_call():
    read = _metric("stage_ms")
    calls = [{"index": i, "info": {"trace_gen_s": s}}
             for i, s in ((1, 0.5), (2, 0.9), (3, 0.7))]
    assert read({"calls": calls}) == pytest.approx(600.0)
    assert read({"calls": calls[1:2]}) is None
    assert read({"calls": [{"index": 1, "info": {}}]}) is None
