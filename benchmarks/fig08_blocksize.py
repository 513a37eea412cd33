"""Fig. 8 — sub-page block size vs IPC gain and relative FAM latency.

Paper claim: IPC gain flat for 64-512 B (slight peak at 128-256 B), falling
beyond; 4096 B (page-on-touch) blows FAM latency up ~17x and IPC collapses.

Block size is fully *dynamic* since the padded-geometry refactor: the
planner pads the cache allocation to the largest swept geometry (64 B
blocks -> 16384 sets) and every block size's effective geometry rides
along as traced ``FamParams`` scalars, so the WHOLE figure — every block
size x workload x variant — plans into ONE compile group and one vmapped
device call (bit-exact vs the per-point exact-geometry runs). The
variants are dynamic feature gates over the default ``PolicySet`` (spp +
fifo chain + lru + token_bucket), so they share the group too. The
per-point cross-check + wall-clock comparison for the acceptance gate
lands in the ``fig08_engine`` row.
"""
from __future__ import annotations

from benchmarks.common import (BASELINE, DRAM, FamConfig, engine_row,
                               fam_replace, geomean, obs_tracer, save_rows,
                               save_telemetry, workloads)
from repro.experiments import Experiment, config_axis, flag_axis, workload_axis

BLOCK_SIZES = [64, 128, 256, 512, 1024, 4096]
T = 12_000


def experiment(quick: bool = True, trace_backend: str = "device",
               kernel_backend: str = "xla",
               telemetry: int = 0) -> Experiment:
    return Experiment(
        name="fig08_blocksize", T=T,
        base=fam_replace(FamConfig(), num_nodes=1,
                         kernel_backend=kernel_backend,
                         telemetry=telemetry),
        trace_backend=trace_backend,
        axes=(config_axis("block", BLOCK_SIZES, param="block_bytes"),
              workload_axis(workloads(quick)),
              flag_axis("variant", {"base": BASELINE, "dram": DRAM})))


def block_rows(res, wls) -> list:
    """One row per block size: geomean over ``wls`` of the dram variant's
    IPC gain and FAM latency relative to the base variant."""
    rows = []
    for bs in BLOCK_SIZES:
        gains, rels = [], []
        for w in wls:
            base = res.get(block=bs, workload=w, variant="base")
            out = res.get(block=bs, workload=w, variant="dram")
            gains.append(float(out["ipc"][0] / max(base["ipc"][0], 1e-9)))
            rels.append(float(out["fam_latency"][0] /
                              max(base["fam_latency"][0], 1e-9)))
        rows.append({
            "name": f"fig08_block{bs}",
            "us_per_call": res.info.us_per_call(),
            "derived": f"ipc_gain={geomean(gains):.3f};"
                       f"rel_fam_latency={geomean(rels):.3f}",
            "block_bytes": bs,
            "ipc_gain_geomean": geomean(gains),
            "rel_fam_latency_geomean": geomean(rels),
        })
    return rows


def run(quick: bool = True, trace_backend: str = "device",
        kernel_backend: str = "xla", telemetry: int = 0):
    wls = workloads(quick)
    # assert_compiles: the runtime sanitizer proves the one-executable
    # promise — actual XLA compiles == accounted groups (== 1 when cold);
    # the telemetry tag splits NO group (it rides geometry_free_shape
    # uniformly), so the 1-group assert below holds either way
    with obs_tracer("fig08_blocksize", telemetry):
        res = experiment(quick, trace_backend, kernel_backend,
                         telemetry).run(cross_check_shard=True,
                                        assert_compiles=True)
    info = res.info
    assert info.planned_groups == 1, info.groups  # dynamic geometry: 1 compile
    rows = block_rows(res, wls)

    # engine acceptance: batched == per-point within 1e-5, the recorded
    # wall-clock comparison (per-point pays a compile per (flags, shape)),
    # and the sharded-vs-vmap bit-exactness record
    check_pts = [p for p in res.points
                 if p.cfg.block_bytes == BLOCK_SIZES[0]]
    rows.append(engine_row("fig08_engine", res, check_pts))
    if telemetry:
        save_telemetry("fig08_blocksize", res, telemetry)
    save_rows("fig08_blocksize", rows)
    return rows
