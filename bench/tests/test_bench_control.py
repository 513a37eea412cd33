"""The control: each configuration's reference computed in bfloat16, the
precision below the configurations' float32, put in the program's place,
fails the check."""
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_reference_fails_the_check(cell):
    import ml_dtypes

    import check
    import grid
    import run
    spec = run.load_cell(cell)
    reference = run.reference(spec["config"])
    traffic = dict(spec["traffic"], T=400)
    expand_systems, _ = grid.expansion(traffic)
    systems = expand_systems(traffic, spec["config"], 2**31 + 99, 1)
    chk = traffic["check"]
    picked = check.sample(5, [(1, systems)], 3, chk["stratify"])
    chosen = [systems[i] for _, i in picked]
    f32 = [reference.simulate(s) for s in chosen]
    bf16 = [reference.simulate(s, ml_dtypes.bfloat16) for s in chosen]
    limits = spec["limits"]["numbers"]
    same, _ = check.judge(check.gaps(f32, f32, list(limits)), limits)
    assert same
    ok, table = check.judge(check.gaps(bf16, f32, list(limits)), limits)
    assert not ok, table
