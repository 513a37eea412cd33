"""Every cell of BENCHMARK.json resolves, by name, to its files."""
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    import run
    spec = run.load_cell(cell)
    assert spec["config"]["name"] == spec["cell"]["config"]
    assert spec["traffic"]["name"] == spec["cell"]["traffic"]
    assert spec["limits"]["cell"] == cell
    names = {m["name"] for m in spec["end_to_end"]}
    assert {"setup_s", "events_per_s"} <= names
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        assert callable(run.reader(m["name"]))


def test_every_metric_and_config_has_its_file():
    for m in BENCH["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(cfg["published"]) == set(c["reduced"])


def test_chip_facts_name_the_accepted_kind():
    facts = json.loads((ROOT / "bench" / "chips.json").read_text())
    assert "TPU v5 lite" in facts["kinds"]
    assert facts["source"]
