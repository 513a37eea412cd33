"""A whole run of `pool4_mixes` on the CPU: sound, it reads correct; with
its timed path broken underneath, it reads not correct."""
import pytest

CELL = "pool4_mixes"


def test_sound_run_is_correct(tiny_run):
    out = tiny_run(CELL)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(row["value"] == 0.0 for row in out["check"].values())


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_broken_run_is_not_correct(tiny_run, plant, fault):
    import run
    plant(fault, run.load_cell(CELL)["limits"]["numbers"])
    out = tiny_run(CELL)
    assert not out["correct"], out["check"]
