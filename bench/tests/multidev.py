"""Drives the harness on four host CPU devices, for the tests of the
sharded path; prints one JSON line.

    python bench/tests/multidev.py run <cell> <traffic> <sound|fault>
    python bench/tests/multidev.py shard <cell> <traffic>

Each takes the cell's configuration and limits with the traffic file
``bench/traffic/<traffic>.json``, as a four-chip cell. ``run``: a whole run
(as ``tiny_run`` makes it), sound or with a fault of ``conftest._plant``
planted. ``shard``: one call of the traffic through ``execute`` on the four
devices (shard_map) and on one (vmap), and whether every metric of every
system is bit-equal. Both at a short trace and a small cache (``SMALL``).
"""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import conftest  # noqa: E402

#: trace length and DRAM-cache bytes (64 B blocks: 256 sets x 16 ways)
SMALL = {"T": 64, "dram_cache_bytes": 262144}


def on_four(traffic_name):
    """An ``edit`` for ``conftest.tiny_spec``: the traffic file
    ``traffic_name`` on four chips, at the small sizes."""
    def edit(spec):
        import grid
        traffic = grid.load_json(grid.BENCH / "traffic" /
                                 f"{traffic_name}.json")
        traffic["T"] = SMALL["T"]
        spec["traffic"] = traffic
        spec["cell"] = dict(spec["cell"], traffic=traffic_name, chips=4)
        spec["config"]["system"]["dram_cache_bytes"] = \
            SMALL["dram_cache_bytes"]
    return edit


def whole_run(mp, cell, traffic, fault):
    import run
    if fault != "sound":
        conftest._plant(mp, fault, run.load_cell(cell)["limits"]["numbers"])
    out = conftest.tiny_run_of(mp, cell, T=SMALL["T"],
                               edit=on_four(traffic))
    return {k: out[k] for k in ("correct", "attempted", "failed", "check",
                                "device")}


def shard_vs_vmap(mp, cell, traffic_name):
    import grid
    from repro.experiments import execute
    from repro.experiments import executor as ex
    spec = conftest.tiny_spec(mp, cell, SMALL["T"], on_four(traffic_name))
    traffic, config = spec["traffic"], spec["config"]
    expand_systems, to_experiment = grid.expansion(traffic)
    systems = expand_systems(traffic, config, 2**31 + 4321, 1)
    plan = to_experiment(systems, config, cell).plan()
    (g,) = plan.groups
    four = execute(plan, devices=4, warmup_frac=traffic["warmup_frac"])
    one = execute(plan, devices=1, warmup_frac=traffic["warmup_frac"])
    equal = all(np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
                for a, b in zip(four.metrics, one.metrics) for k in a)
    return {"bit_exact": bool(equal), "systems": len(systems),
            "lanes": len(ex._pad_systems(g.indices, g.s_pad, 4)),
            "devices": [four.info.devices, one.info.devices]}


def main(argv):
    mp = pytest.MonkeyPatch()
    try:
        if argv[0] == "run":
            out = whole_run(mp, argv[1], argv[2], argv[3])
        else:
            out = shard_vs_vmap(mp, argv[1], argv[2])
    finally:
        mp.undo()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
