"""Run the simulator's main path on TPU chips and check what comes out.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded path, on a four-chip host

One chip:

* fig08 at full size on the ``xla`` backend, through the code behind
  ``python -m benchmarks.run --full fig08``: 6 block sizes x 19 workloads
  x {base, dram} = 228 points in one compile group. It must plan one
  group, compile exactly what it planned, match the per-point
  ``build_sim`` runs within 1e-5 and its shard-vs-vmap cross-check bit
  for bit;
* fig08's quick grid (72 points) on both cache-engine backends. Their
  metric digests must be equal, the ``pallas`` group must hold the
  compiled kernel (``tpu_custom_call``), and the per-block-size
  ``ipc_gain`` and ``rel_fam_latency`` must agree with the CPU rows in
  ``results/benchmarks/fig08_blocksize.json`` within 1 % (transcendentals
  in the in-graph trace generator may round differently on the chip).

Four chips (``--chips 4``): fig08 at full size with its system axis
sharded over the four devices, checked bit for bit against the
one-device vmap run, and nothing else.

Runs in one process and starts none. Exits non-zero, with no result
line, unless JAX finds a TPU. Writes nothing git tracks: the figure rows
go to ``.chip_smoke/``. Compiled executables persist in JAX's
compilation cache (``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/``),
so a second run compiles from the cache. Timings printed here are
bring-up observations of the device named on the first line. The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / ".chip_smoke"
CPU_ROWS = ROOT / "results" / "benchmarks" / "fig08_blocksize.json"
FULL_POINTS, QUICK_POINTS = 228, 72
MAX_REL_VS_CPU = 0.01


def say(*parts) -> None:
    print(*parts, flush=True)


def _peak_bytes(dev):
    return (dev.memory_stats() or {}).get("peak_bytes_in_use",
                                         "not reported")


def _engine_line(tag: str, info: dict, dev) -> None:
    say(f"{tag}: points={info['systems']} groups={info['planned_groups']} "
        f"compiles={info['compiles']} "
        f"xla_compiles={info.get('xla_compiles')} devices={info['devices']} "
        f"compile_s={info['compile_s']} run_s={info['run_s']} "
        f"events={info['events']} "
        f"events_per_s={info['events'] / max(info['run_s'], 1e-12)} "
        f"peak_bytes_in_use={_peak_bytes(dev)}")


def full_grid(dev) -> dict:
    """fig08 full on xla, as ``benchmarks.run --full fig08`` runs it."""
    from benchmarks import fig08_blocksize

    rows = fig08_blocksize.run(quick=False)
    *blocks, eng = rows
    info = eng["engine"]
    for r in blocks:
        say(f"fig08 full {r['name']}: {r['derived']}")
    say(f"fig08 full per-point check: {eng['check']}")
    say(f"fig08 full shard_check: {info['shard_check']}")
    _engine_line("fig08 full xla", info, dev)
    return {
        f"full: {FULL_POINTS} points": info["systems"] == FULL_POINTS,
        "full: one planned group": info["planned_groups"] == 1,
        "full: xla_compiles == compiles":
            info["xla_compiles"] == info["compiles"],
        "full: per-point within 1e-5": eng["check"]["matches_1e-5"],
        "full: shard-vs-vmap bit-exact": info["shard_check"]["bit_exact"],
    }


def quick_grid(dev, cpu_rows: list) -> dict:
    """fig08 quick on both backends: equal digests, compiled kernel, and
    agreement with the CPU rows."""
    from benchmarks import fig08_blocksize
    from benchmarks.bench_famsim import _digest
    from benchmarks.common import workloads
    from repro.experiments import executor as ex

    digests, rows, checks = {}, {}, {}
    for backend in ("xla", "pallas"):
        plan = fig08_blocksize.experiment(quick=True,
                                          kernel_backend=backend).plan()
        res = ex.execute(plan, assert_compiles=True)
        info = res.info.as_dict()
        digests[backend] = _digest(res)
        rows[backend] = fig08_blocksize.block_rows(res, workloads(True))
        _engine_line(f"fig08 quick {backend}", info, dev)
        say(f"fig08 quick {backend} digest={digests[backend]}")
        checks[f"quick {backend}: {QUICK_POINTS} points"] = \
            info["systems"] == QUICK_POINTS
        checks[f"quick {backend}: xla_compiles == compiles"] = \
            info["xla_compiles"] == info["compiles"]
        if backend == "pallas":
            (key,) = ex.group_cache_keys(plan)
            checks["quick pallas: kernel compiled (tpu_custom_call)"] = \
                "tpu_custom_call" in ex._EXEC_CACHE[key].as_text()
    checks["quick: xla digest == pallas digest"] = \
        digests["xla"] == digests["pallas"]

    cpu = {r["name"]: r for r in cpu_rows}
    worst = 0.0
    for r in rows["xla"]:
        ref = cpu[r["name"]]
        for k in ("ipc_gain_geomean", "rel_fam_latency_geomean"):
            rel = abs(r[k] - ref[k]) / abs(ref[k])
            worst = max(worst, rel)
            say(f"fig08 quick {r['name']} {k}: chip={r[k]!r} "
                f"cpu={ref[k]!r} rel_diff={rel!r}")
    say(f"fig08 quick chip-vs-CPU max relative difference: {worst!r}")
    checks[f"quick: chip vs CPU within {MAX_REL_VS_CPU:.0%}"] = \
        worst <= MAX_REL_VS_CPU
    return checks


def sharded_grid(dev, n: int) -> dict:
    """fig08 full with S sharded over ``n`` devices, against the
    one-device vmap run."""
    from benchmarks import fig08_blocksize
    from benchmarks.bench_famsim import _digest

    res = fig08_blocksize.experiment(quick=False).run(
        devices=n, cross_check_shard=True, assert_compiles=True)
    info = res.info.as_dict()
    _engine_line(f"fig08 full sharded over {n}", info, dev)
    say(f"fig08 full sharded digest={_digest(res)}")
    say(f"fig08 full shard_check: {info['shard_check']}")
    sc = info["shard_check"]
    return {
        f"sharded: devices={n}": info["devices"] == n,
        f"sharded: {FULL_POINTS} points": info["systems"] == FULL_POINTS,
        "sharded: xla_compiles == compiles":
            info["xla_compiles"] == info["compiles"],
        "sharded: bit-exact vs one-device vmap":
            sc["alt"] == "vmap" and sc["bit_exact"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the sharded "
                         "phase and the run it is compared with")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = devs[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu" or len(devs) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), found "
              f"{len(devs)} {dev.platform} device(s)", file=sys.stderr)
        return 1

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks import common
    say(f"compile cache: {common.use_compile_cache()}")
    cpu_rows = json.loads(CPU_ROWS.read_text())
    common.RESULTS = OUT     # leave the tracked CPU rows as they are

    phases = ([lambda: sharded_grid(dev, args.chips)] if args.chips > 1
              else [lambda: full_grid(dev),
                    lambda: quick_grid(dev, cpu_rows)])
    checks = {}
    for i, phase in enumerate(phases):
        try:
            checks.update(phase())
        except Exception:
            traceback.print_exc()
            checks[f"phase {i} ran"] = False
    for name, ok in checks.items():
        say(f"check {name}: {'ok' if ok else 'FAILED'}")
    if not all(checks.values()):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
