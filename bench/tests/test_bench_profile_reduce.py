"""The trace reduction: busy union, idle share and gap attribution, on
synthetic intervals and on a small trace recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_clips():
    import profile_reduce as pr
    got = pr.union([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 15)
    assert got == [(1, 4), (5, 8), (9, 15)]
    assert pr.gaps_of(got, 0, 16) == [(0, 1), (4, 5), (8, 9), (15, 16)]


def test_gap_is_cut_at_span_edges():
    import profile_reduce as pr
    spans = [("fetch", 10, 20), ("plan", 20, 25)]
    assert pr.split((5, 30), spans) == [("none", 5), ("fetch", 10),
                                        ("plan", 5), ("none", 5)]


def test_gap_is_named_by_the_innermost_open_span():
    import profile_reduce as pr
    spans = [("execute", 0, 100), ("fetch", 40, 60), ("plan", 110, 120)]
    assert pr.label(50, spans) == "fetch"
    assert pr.label(20, spans) == "execute"
    assert pr.label(105, spans) == "none"
    assert pr.label(115, spans) == "plan"


def test_recorded_tpu_trace():
    """Two calls of a small jitted scan with 20 ms of ``fetch`` and 10 ms
    of ``plan`` between them, recorded on one TPU v5e."""
    from jax.profiler import ProfileData

    import profile_reduce as pr
    facts = json.loads((DATA.parents[1] / "chips.json").read_text())
    facts = facts["kinds"]["TPU v5 lite"]
    side = json.loads((DATA / "tiny_spans.json").read_text())
    pd = ProfileData.from_file(str(DATA / "tiny.xplane.pb"))
    lo, hi = pr.slice_bounds(pd)
    offset = lo - side["t_begin"] * 1e9
    out = pr.reduce(pd, [tuple(s) for s in side["spans"]], offset,
                    facts["trace_plane_prefix"], facts["trace_op_lines"])
    assert out["devices"] == 1
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["idle_share"] == pytest.approx(
        100 * (1 - out["busy_s"] / out["window_s"]))
    # the host sleeps are the two longest gaps, named by their spans
    (first, d1), (second, d2) = out["idle_gaps"][:2]
    assert {first, second} == {"fetch", "plan"}
    by = dict(out["idle_gaps"][:2])
    assert by["fetch"] == pytest.approx(0.02, abs=0.004)
    assert by["plan"] == pytest.approx(0.01, abs=0.004)
    assert out["device_ops"] and out["device_ops"][0][1] > 0


def test_self_time_leaves_out_nested_ops():
    import profile_reduce as pr
    evs = [(0, 100, "while.1"), (10, 30, "fusion.2"), (40, 50, "fusion.3"),
           (45, 48, "copy.4"), (90, 120, "fusion.5")]
    got = pr.self_times(evs, 0, 110)
    assert got == {"while.1": 60, "fusion.2": 20, "fusion.3": 7,
                   "copy.4": 3, "fusion.5": 20}
    assert pr.op_name("%fusion.829 = s32[1536]{0} fusion(...)") == \
        "fusion.829"
