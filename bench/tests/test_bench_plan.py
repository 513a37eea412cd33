"""Each traffic file, through its expansion (``grid``'s own or its
``expand`` module), plans into exactly one compile group at the stated S,
N, T and cache padding."""
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_traffic_plans_one_group(cell):
    import grid
    import run
    spec = run.load_cell(cell)
    traffic, config = spec["traffic"], spec["config"]
    want = traffic["expect"]
    expand_systems, to_experiment = grid.expansion(traffic)
    for call in (0, 1):
        systems = expand_systems(traffic, config, 2**31 + 7, call)
        plan = to_experiment(systems, config, cell).plan()
        assert plan.num_groups == want["groups"] == 1
        (g,) = plan.groups
        assert len(systems) == g.size == want["systems"]
        assert g.s_pad == want["S_pad"]
        assert g.key.num_nodes == want["N"] == traffic["nodes"]
        assert g.t_pad == want["T_pad"] == traffic["T"]
        assert g.pad_sets == want["pad_sets"]
        assert sum(len(s["workloads"]) * s["T"] for s in systems) == \
            plan.events()


def test_calls_draw_new_traces_and_repeat_by_seed():
    import grid
    import run
    spec = run.load_cell("pool4_search")
    a = grid.systems(spec["traffic"], spec["config"], 11, 1)
    b = grid.systems(spec["traffic"], spec["config"], 11, 1)
    c = grid.systems(spec["traffic"], spec["config"], 11, 2)
    assert a == b
    assert a[0]["seed"] != c[0]["seed"]
    assert a[0]["params"] != c[0]["params"]
