"""Tracked steady-state throughput benchmark over the famsim cache engine.

Measures simulated events/sec/device at fig08 scale (the block-size
sweep: the figure the paper's headline DRAM-cache results ride on) for
each ``FamConfig.kernel_backend`` — the pure-XLA hot path and the fused
Pallas cache-step kernel — from the SAME planner/executor path the
figures use, so the number tracked across PRs is the number the figures
actually pay.

Every timing comes from the executor's own accounting
(``RunInfo.run_s`` / ``compile_s``: AOT-compiled group executables,
``block_until_ready``-synchronized steady-state calls); this module
never reads a clock, so its outputs stay inside the determinism lints
(``derived`` carries only the metric digest — the CI bit-identity
contract between backends — while wall-clock numbers ride in JSON-only
fields).

Artifacts:

* ``BENCH_famsim.json`` (repo root) — the append-only throughput
  trajectory, one entry per backend per invocation;
* ``results/benchmarks/bench_famsim.json`` — this invocation's full rows
  (the scaffold contract, like every figure);
* ``results/roofline/famsim_step.json`` — ``repro.roofline`` terms of
  each backend's compiled group executable (loop-aware HLO costing),
  priced at the peaks of the device it ran on, next to the measured
  throughput. Written only on a TPU: a CPU has no published peaks, so
  there the roofline is "not measured".

Every row names the device it ran on (``platform``, ``device_kind``,
``devices``).

Usage (via the ``bench`` subcommand)::

    python -m benchmarks.run bench                    # both backends
    python -m benchmarks.run bench --quick            # CI scale
    python -m benchmarks.run bench --kernel-backend pallas --repeats 5
"""
from __future__ import annotations

import argparse
import hashlib
import json
from pathlib import Path

import numpy as np

from benchmarks import fig08_blocksize
from benchmarks.common import (BASELINE, DRAM, obs_tracer, save_rows,
                               workloads)
from repro.obs.spans import maybe_span
from repro.experiments import (config_axis, execute, flag_axis,
                               workload_axis)
from repro.experiments import executor as _ex
from repro.kernels.famsim_step import KERNEL_BACKENDS

ROOT = Path(__file__).resolve().parent.parent
TRAJECTORY = ROOT / "BENCH_famsim.json"
ROOFLINE = ROOT / "results" / "roofline" / "famsim_step.json"
SCHEMA = "bench_famsim/v1"

#: CI scale. The quick grid is a SUBSAMPLE of fig08 (same axes, fewer
#: values) because the Pallas backend runs in interpret mode off-TPU and
#: the emulation pays a full padded-(sets, ways) array copy per masked
#: store per event — cost scales with pad_sets x points x T, so quick
#: drops the 64 B block size (16384-set padding -> 4096) and trims the
#: grid to what both backends can execute in CI minutes. The full
#: (non-quick) grid is the exact fig08 sweep — the scale the tracked
#: XLA number and any compiled-TPU Pallas number are quoted at.
QUICK_T = 400
QUICK_BLOCKS = [256, 1024]
QUICK_WORKLOADS = 2


def _experiment(backend: str, quick: bool):
    """fig08's experiment, subsampled to the CI-affordable grid when
    ``quick`` (identical grid across backends — the digest contract)."""
    exp = fig08_blocksize.experiment(quick=quick, kernel_backend=backend)
    if not quick:
        return exp
    import dataclasses
    return dataclasses.replace(
        exp, T=QUICK_T,
        axes=(config_axis("block", QUICK_BLOCKS, param="block_bytes"),
              workload_axis(workloads(True)[:QUICK_WORKLOADS]),
              flag_axis("variant", {"base": BASELINE, "dram": DRAM})))


def _digest(result) -> str:
    """Order-stable digest over every point's every metric array — the
    backends' bit-identity contract compressed into one token that CI
    can compare across CSV rows."""
    h = hashlib.sha256()
    for m in result.metrics:
        for k in sorted(m):
            h.update(k.encode())
            h.update(np.ascontiguousarray(m[k]).tobytes())
    return h.hexdigest()[:16]


def _measure(backend: str, quick: bool, repeats: int) -> dict:
    """Run the fig08-scale experiment ``repeats`` times on ``backend``;
    best-of steady-state throughput from the executor's accounting."""
    exp = _experiment(backend, quick)
    with maybe_span("plan", experiment=exp.name, backend=backend):
        plan = exp.plan()
    runs, result, compile_s = [], None, 0.0
    for rep in range(max(repeats, 1)):
        with maybe_span("repeat", backend=backend, repeat=rep):
            result = execute(plan, assert_compiles=True)
        runs.append(result.info.run_s)
        compile_s += result.info.compile_s
    info = result.info
    best = min(runs)
    return {
        "backend": backend,
        "digest": _digest(result),
        "events": info.events,
        "points": len(result.points),
        "devices": info.devices,
        "platform": info.platform,
        "device_kind": info.device_kind,
        "planned_groups": info.planned_groups,
        "run_s_best": round(best, 4),
        "run_s_all": [round(r, 4) for r in runs],
        "compile_s": round(compile_s, 3),
        "us_per_event": info.events and best / info.events * 1e6,
        "events_per_sec_per_device": round(
            info.events / max(best, 1e-12) / max(info.devices, 1), 1),
        "plan": plan,             # stripped before serialization
        "engine": info.as_dict(),
    }


def _roofline_record(measured: dict) -> dict:
    """Roofline terms of the backend's compiled group executable (already
    in the executor cache after ``_measure``), joined with the measured
    steady-state throughput."""
    from repro.roofline.analysis import analyze

    plan = measured["plan"]
    keys = _ex.group_cache_keys(plan)
    recs = []
    for g, key in zip(plan.groups, keys):
        compiled = _ex._EXEC_CACHE[key]
        terms = analyze(compiled, chips=measured["devices"], model_flops=0.0,
                        device_kind=measured["device_kind"])
        recs.append({"static_shape": str(g.key.static_shape),
                     **terms.to_dict()})
    return {
        "backend": measured["backend"],
        "events": measured["events"],
        "run_s_best": measured["run_s_best"],
        "events_per_sec_per_device": measured["events_per_sec_per_device"],
        "groups": recs,
    }


def _append_trajectory(entries: list) -> None:
    doc = {"schema": SCHEMA, "unit": "events_per_sec_per_device",
           "runs": []}
    if TRAJECTORY.exists():
        old = json.loads(TRAJECTORY.read_text())
        if old.get("schema") == SCHEMA:
            doc = old
    doc["runs"].extend(entries)
    TRAJECTORY.write_text(json.dumps(doc, indent=2) + "\n")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        prog="benchmarks.run bench",
        description="Steady-state famsim throughput (events/sec/device) "
                    "per kernel backend, at fig08 scale")
    ap.add_argument("--kernel-backend", default="both",
                    choices=("both",) + KERNEL_BACKENDS,
                    help="which cache-engine backend(s) to measure "
                         "(default: both, asserting their metric digests "
                         "are bit-identical)")
    ap.add_argument("--quick", action="store_true",
                    help="CI scale: fig08 grid subsampled to "
                         f"{len(QUICK_BLOCKS)} block sizes x "
                         f"{QUICK_WORKLOADS} workloads, T={QUICK_T} "
                         "(the interpret-mode Pallas path is affordable "
                         "at this scale)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="steady-state executions per backend; best-of "
                         "is reported (default: 3)")
    ap.add_argument("--no-roofline", action="store_true",
                    help="skip the compiled-executable roofline report")
    ap.add_argument("--telemetry", action="store_true",
                    help="record a host span timeline (plan/repeat/compile/"
                         "run/fetch per backend) to results/trace/"
                         "bench_famsim.json — see docs/observability.md")
    args = ap.parse_args(argv)

    backends = KERNEL_BACKENDS if args.kernel_backend == "both" \
        else (args.kernel_backend,)
    with obs_tracer("bench_famsim", int(args.telemetry)):
        measured = [_measure(b, args.quick, args.repeats) for b in backends]

    digests = {m["backend"]: m["digest"] for m in measured}
    if len(measured) > 1:
        assert len(set(digests.values())) == 1, (
            "kernel backends disagree on derived metrics — the fused "
            "kernel must be bit-identical to the XLA path", digests)

    platform = measured[0]["platform"]
    if not args.no_roofline and platform == "tpu":
        ROOFLINE.parent.mkdir(parents=True, exist_ok=True)
        ROOFLINE.write_text(json.dumps(
            [_roofline_record(m) for m in measured], indent=2) + "\n")
    elif not args.no_roofline:
        print(f"# roofline: not measured on {platform} (peaks are "
              "published for TPUs only)", flush=True)

    rows = []
    for m in measured:
        m.pop("plan")
        rows.append({
            "name": f"bench_famsim_{m['backend']}",
            "us_per_call": m["us_per_event"],
            # deterministic: digest + true event count only
            "derived": f"digest={m['digest']};events={m['events']}",
            **{k: v for k, v in m.items() if k != "us_per_event"},
        })
    save_rows("bench_famsim", rows)
    _append_trajectory([{k: v for k, v in r.items()
                         if k not in ("engine", "us_per_call")}
                        | {"quick": bool(args.quick)} for r in rows])

    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.3f},\"{r['derived']}\"",
              flush=True)
    if len(measured) > 1:
        base, other = measured[0], measured[1]
        speedup = base["run_s_best"] / max(other["run_s_best"], 1e-12)
        print(f"# {other['backend']} vs {base['backend']}: "
              f"{speedup:.2f}x, digests match", flush=True)


if __name__ == "__main__":
    main()
