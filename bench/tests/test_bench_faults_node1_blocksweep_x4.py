"""`node1_blocksweep_x4` (`table2_1node`, Table II at its published widths,
x `blocksweep_x4`, fig08 over four seed replicates) on four host CPU
devices, each case in a process of its own (the device count is fixed when
JAX starts): the sharded call is bit-equal to the one-device call, a whole
run reads correct, and with its timed path broken underneath it reads not
correct. On one device, program and reference agree at the cell's widths
(a 256-entry prefetch queue, 8 cores a node)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

CELL = "node1_blocksweep_x4"
TRAFFIC = "blocksweep_x4"
HELPER = Path(__file__).resolve().parent / "multidev.py"


def _helper(*args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run([sys.executable, str(HELPER), *args], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_shard_map_equals_vmap():
    out = _helper("shard", CELL, TRAFFIC)
    assert out == {"bit_exact": True, "systems": 912, "lanes": 1024,
                   "devices": [4, 1]}


def test_sound_run_is_correct():
    out = _helper("run", CELL, TRAFFIC, "sound")
    assert out["correct"], out["check"]
    assert out["device"]["count"] == 4
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(row["value"] == 0.0 for row in out["check"].values())


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered", "exchange_left_out"])
def test_broken_run_is_not_correct(fault):
    out = _helper("run", CELL, TRAFFIC, fault)
    assert not out["correct"], out["check"]


def test_program_agrees_with_reference_at_table2_widths(tiny_run):
    """fig08's grid on one device under the cell's configuration, at
    T = 200: every compared number of the sampled systems equal to the
    reference's."""
    def one_device(spec):
        import grid
        spec["traffic"] = dict(grid.load_json(
            grid.BENCH / "traffic" / "blocksweep.json"), T=200)
        spec["cell"] = dict(spec["cell"], traffic="blocksweep", chips=1)
        system = spec["config"]["system"]
        assert (system["prefetch_queue"], system["cores_per_node"]) == \
            (256, 8)
    out = tiny_run(CELL, T=200, edit=one_device)
    assert out["correct"], out["check"]
    assert out["device"]["count"] == 1
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(row["value"] == 0.0 for row in out["check"].values())
