"""How a cell's files become systems and a reference: the traffic
expansion (``grid``'s own or a traffic file's ``expand`` module), the
configuration's ``reference`` module, and the ``replicates`` axis."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

STUB_REFERENCE = "tests/data/stub_reference.py"
STUB_EXPAND = "tests/data/stub_expand.py"
POLICY_FIELDS = ("prefetch", "scheduler", "replacement", "adaptation",
                 "overrides")
#: sha256 prefixes of the systems and of the Experiment points of calls 0, 1
#: and 5 of seeds 3 and 2**31 + 7, as the harness expanded them before it
#: took expansion modules, replicates and a seed per system
PINNED = {"node1_blocksweep": ("1adcd91f3fd7a72d", "7fa99dd8fd29349a"),
          "pool4_mixes": ("0128093e5a3767c8", "b9eddc6466a2bcca"),
          "pool4_search": ("e2d2623e296d641b", "542814f5f4c7fc8b")}


def _point(pt, config, flag_names):
    keys = sorted(set(config["system"]) | set(config.get("program", {})))
    pol = None if pt.policies is None else \
        [getattr(pt.policies, k) for k in POLICY_FIELDS]
    return [pt.coords, pt.workloads, pt.T, pt.seed, pt.t_live,
            [getattr(pt.cfg, k, None) for k in keys],
            [getattr(pt.flags, k) for k in flag_names], pol]


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_existing_traffic_expands_as_before(cell):
    import grid
    import run
    spec = run.load_cell(cell)
    traffic, config = spec["traffic"], spec["config"]
    expand_systems, to_experiment = grid.expansion(traffic)
    assert (expand_systems, to_experiment) == (grid.systems,
                                               grid.to_experiment)
    hs, hp = hashlib.sha256(), hashlib.sha256()
    for seed in (3, 2**31 + 7):
        for call in (0, 1, 5):
            systems = expand_systems(traffic, config, seed, call)
            hs.update(json.dumps(systems, sort_keys=True).encode())
            points = to_experiment(systems, config, cell).points()
            hp.update(json.dumps([_point(p, config,
                                         sorted(grid.FLAG_DEFAULTS))
                                  for p in points]).encode())
    assert (hs.hexdigest()[:16], hp.hexdigest()[:16]) == PINNED[cell]


def _stub(name):
    import grid
    mod = grid.bench_module(name)
    mod.CALLS.clear()
    return mod


@pytest.mark.parametrize("scale", [1.0, 1.5])
def test_run_checks_against_the_configurations_reference(tiny_run, scale,
                                                         monkeypatch):
    stub = _stub(STUB_REFERENCE)
    monkeypatch.setattr(stub, "SCALE", scale)

    def edit(spec):
        spec["config"]["reference"] = STUB_REFERENCE
    out = tiny_run("pool4_search", edit=edit)
    assert len(stub.CALLS) == 6
    assert all(dtype is np.float32 for _, dtype in stub.CALLS)
    assert out["correct"] == (scale == 1.0), out["check"]
    if scale != 1.0:
        gap = 1 - 1 / scale
        assert out["check"]["ipc"]["value"] == pytest.approx(gap, rel=1e-5)


def test_run_takes_systems_from_the_traffics_expand_module(tiny_run):
    stub = _stub(STUB_EXPAND)

    def edit(spec):
        spec["traffic"]["expand"] = STUB_EXPAND
    out = tiny_run("pool4_search", edit=edit)
    assert out["correct"], out["check"]
    made = [c for k, c in stub.CALLS if k == "systems"]
    assert made == list(range(len(made))) and len(made) >= 2
    assert ("to_experiment", 24) in stub.CALLS
    assert out["attempted"] == 24 * (len(made) - 1)


def test_calibrate_takes_both_from_the_cells_files(monkeypatch):
    import ml_dtypes

    import calibrate
    from conftest import tiny_spec
    ref, exp = _stub(STUB_REFERENCE), _stub(STUB_EXPAND)

    def edit(spec):
        spec["config"]["reference"] = STUB_REFERENCE
        spec["traffic"]["expand"] = STUB_EXPAND
    spec = tiny_spec(monkeypatch, "pool4_search", edit=edit)
    *lines, summary = calibrate.readings(spec, 2**31 + 77, seeds=1,
                                         control=1, require_tpu=False)
    assert [ln["seed"] for ln in lines] == [2**31 + 78]
    assert set(lines[0]["control"]) == set(ref.METRICS)
    assert summary["lower"]["ipc"] == 0.0 < summary["upper"]["ipc"]
    assert [c for k, c in exp.CALLS if k == "systems"] == [1, 1]
    dtypes = [d for _, d in ref.CALLS]
    assert dtypes.count(np.float32) == 6
    assert dtypes.count(ml_dtypes.bfloat16) == 6


#: the marker of a test that runs once for every cell of BENCHMARK.json
PER_CELL = 'parametrize("cell", CELLS)'
NEW_CELL = "pool4_qos"
HERE = os.path.basename(__file__)


def test_a_new_cell_passes_every_per_cell_test(tmp_path):
    """A cell whose traffic names an expansion module with an axis kind
    ``grid`` does not know passes every per-cell test, in a copy of the
    benchmark to which only the cell's own files and entry were added."""
    import grid
    root = grid.BENCH.parent
    shutil.copytree(grid.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(root / "src")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": NEW_CELL, "config": "paper_4node_pool", "traffic": "qos",
        "chips": 1, "why": "fig14 mixes under two service classes"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    traffic = grid.load_json(grid.BENCH / "traffic" / "mixes.json")
    mix, variant = traffic["axes"]
    traffic.update(name="qos", expand="tests/data/stub_qos.py", axes=[
        {"name": "qos", "kind": "qos", "classes": {
            "gold": {"weight": 4, "min_issue_rate": 0.1},
            "bronze": {"weight": 1, "min_issue_rate": 0.05}}},
        mix, dict(variant, values={k: variant["values"][k]
                                   for k in ("base", "fifo", "adapt")})])
    traffic["check"]["stratify"] = ["qos", "mix", "variant"]
    (tmp_path / "bench" / "traffic" / "qos.json").write_text(
        json.dumps(traffic))
    limits = grid.load_json(grid.BENCH / "limits" / "pool4_mixes.json")
    (tmp_path / "bench" / "limits" / f"{NEW_CELL}.json").write_text(
        json.dumps(dict(limits, cell=NEW_CELL)))
    with pytest.raises(ValueError, match="unknown axis kind 'qos'"):
        grid.systems(traffic, {}, 1, 0)

    files = sorted(str(p) for p in (tmp_path / "bench" / "tests").glob(
        "test_*.py") if PER_CELL in p.read_text() and p.name != HERE)
    assert len(files) >= 3
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", "-k", NEW_CELL, *files],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    passed = re.search(r"(\d+) passed", p.stdout)
    assert passed and int(passed.group(1)) >= len(files), p.stdout[-2000:]


def test_expand_names_a_module_under_bench_only():
    import grid
    with pytest.raises(ValueError):
        grid.expansion({"expand": "../src/repro/__init__.py"})
    with pytest.raises(ValueError):
        grid.bench_module("workloads.json")


# ---------------------------------------------------------------------------
# replicates
# ---------------------------------------------------------------------------

def _replicated(traffic, count):
    out = dict(traffic)
    out["axes"] = [{"name": "replicate", "kind": "replicates",
                    "count": count}] + list(traffic["axes"])
    return out


def test_replicate_zero_is_the_grid_without_the_axis():
    import grid
    import run
    base = run.load_cell("node1_blocksweep")
    x4 = grid.load_json(grid.BENCH / "traffic" / "blocksweep_x4.json")
    assert x4["axes"][1:] == base["traffic"]["axes"]
    for call in (0, 3):
        want = grid.systems(base["traffic"], base["config"], 2**31 + 5, call)
        got = grid.systems(x4, base["config"], 2**31 + 5, call)
        assert len(got) == 4 * len(want)
        seeds = {s["coords"]["replicate"]: s["seed"] for s in got}
        assert len(set(seeds.values())) == 4
        assert seeds["0"] == grid.call_seed(2**31 + 5, call)
        first = got[:len(want)]
        for s in first:
            assert s["coords"].pop("replicate") == "0"
        assert first == want


def test_replicate_seeds_never_meet():
    """No two (call, replicate, node) of a run share a trace seed, in the
    reference's and in the program's derivation of node seeds."""
    import grid
    import run
    from repro.traces import node_seed
    reference = run.reference(run.load_cell("pool4_mixes")["config"])
    assert reference.node_seed(0, 1) == node_seed(0, 1) == grid.NODE_STRIDE
    spec = run.load_cell("pool4_mixes")
    traffic = _replicated(spec["traffic"], 5)
    seen = {}
    for call in (0, 1, 2, grid.MAX_CALLS - 1):
        for s in grid.systems(traffic, spec["config"], 2**31 + 9, call):
            r = int(s["coords"]["replicate"])
            for n in range(traffic["nodes"]):
                seen.setdefault(reference.node_seed(s["seed"], n),
                                set()).add((call, r, n))
    assert all(len(v) == 1 for v in seen.values())
    assert len(seen) == 4 * 5 * traffic["nodes"]
    # the far corners: last call, last node a replicate may have
    corners = {grid.replicate_seed(7, c, r) + grid.NODE_STRIDE * n
               for c in (0, grid.MAX_CALLS - 1) for r in range(4)
               for n in (0, grid.MAX_NODES - 1)}
    assert len(corners) == 16
    with pytest.raises(ValueError):
        grid.call_seed(7, grid.MAX_CALLS)
    with pytest.raises(ValueError):
        grid.systems(dict(traffic, nodes=grid.MAX_NODES + 1),
                     spec["config"], 7, 0)


def test_program_and_reference_agree_on_a_replicated_grid():
    import check
    import grid
    import run
    from repro.experiments import execute
    spec = run.load_cell("pool4_mixes")
    traffic = _replicated(dict(spec["traffic"], T=96), 3)
    traffic["axes"] = [traffic["axes"][0],
                       dict(traffic["axes"][1], values={
                           k: v for k, v in list(
                               traffic["axes"][1]["values"].items())[:2]}),
                       dict(traffic["axes"][2], values={
                           k: v for k, v in list(
                               traffic["axes"][2]["values"].items())[:1]})]
    systems = grid.systems(traffic, spec["config"], 2**31 + 21, 1)
    assert len(systems) == 6
    res = execute(grid.to_experiment(systems, spec["config"], "rep").plan(),
                  devices=1, warmup_frac=traffic["warmup_frac"])
    assert [p.seed for p in res.points] == [s["seed"] for s in systems]
    reference = run.reference(spec["config"])
    refs = [reference.simulate(s) for s in systems]
    limits = spec["limits"]["numbers"]
    ok, table = check.judge(check.gaps(res.metrics, refs, list(limits)),
                            limits)
    assert ok, table
    # the replicates of one system simulate different traces
    ipc = [tuple(m["ipc"]) for m in res.metrics]
    assert ipc[0] != ipc[2] != ipc[4]
