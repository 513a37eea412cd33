"""Readings for the limits of the output check, on the chip.

    python bench/calibrate.py --workload <cell> --seed <n> [--seeds 12]
        [--control 3]

Builds the cell as ``run.py`` does (set-up, warm call), then for each of
``--seeds`` seeds makes one call of the timed path at the cell's own size,
samples systems from it as a run does and compares them with the
configuration's reference: the lower readings. For the first ``--control``
seeds it also puts the reference computed in bfloat16 (the precision below
the configuration's float32) in the program's place: the upper readings.
Prints one JSON line per seed and, last, the largest lower and smallest
upper reading of each number. The benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time

import ml_dtypes

import check
import grid
import run


def readings(spec: dict, seed: int, seeds: int, control: int,
             require_tpu: bool = True):
    """One dict per seed, then the summary of them all."""
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    chips = int(cell["chips"])

    import jax
    devices = jax.devices()
    run.chip_facts(devices, chips, require_tpu=require_tpu)
    run.use_compile_cache()
    from repro.experiments import execute

    ref = run.reference(config)
    expand_systems, to_experiment = grid.expansion(traffic)
    names = list(ref.METRICS)
    lower = {k: 0.0 for k in names}
    upper = {k: float("inf") for k in names}
    for j in range(seeds + 1):
        s = seed + j
        systems = expand_systems(traffic, config, s, 1)
        plan = to_experiment(systems, config, cell["name"]).plan()
        t0 = time.perf_counter()
        res = execute(plan, devices=chips,
                      warmup_frac=traffic["warmup_frac"])
        call_s = time.perf_counter() - t0
        if j == 0:          # the warm call: compile from the cache
            continue
        chk = traffic["check"]
        picked = check.sample(s, [(1, systems)], chk["points"],
                              chk["stratify"])
        prog = [res.metrics[i] for _, i in picked]
        t1 = time.perf_counter()
        refs = [ref.simulate(systems[i]) for _, i in picked]
        ref_s = time.perf_counter() - t1
        got = check.gaps(prog, refs, names)
        for k in names:
            lower[k] = max(lower[k], got[k])
        line = {"seed": s, "call_s": call_s, "reference_s": ref_s,
                "program": got,
                "systems": [systems[i]["coords"] for _, i in picked]}
        if j <= control:
            ctl = [ref.simulate(systems[i], ml_dtypes.bfloat16)
                   for _, i in picked]
            line["control"] = check.gaps(ctl, refs, names)
            for k in names:
                upper[k] = min(upper[k], line["control"][k])
        yield line
    yield {"cell": cell["name"], "seeds": seeds, "control_seeds": control,
           "devices": chips, "lower": lower, "upper": upper,
           "ratio": {k: (upper[k] / lower[k] if lower[k] > 0
                         else float("inf")) for k in names}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    for line in readings(spec, args.seed, args.seeds, args.control):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
