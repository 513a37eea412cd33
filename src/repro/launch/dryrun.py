import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on the
production mesh with ShapeDtypeStruct stand-ins (no allocation), proving the
distribution config is coherent, and dump memory/cost/collective analysis
for EXPERIMENTS.md (§Dry-run / §Roofline).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --multi-pod
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES_BY_NAME, ShapeSpec
from repro.configs.registry import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.models.model_zoo import batch_specs, build_model, cache_specs
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import (DEFAULT_RULES, ParallelContext,
                                     logical_axes_for_leaf, param_specs)
from repro.roofline.analysis import analyze
from repro.train.steps import (abstract_train_state, build_decode_step,
                               build_prefill_step, build_train_step)
import dataclasses

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"
#: the chip the production mesh is planned for; prices the roofline terms
TARGET_KIND = "TPU v5 lite"

# activation budget for picking microbatch count (bytes per device)
_ACT_BUDGET = 2 << 30


def _needs_fsdp(cfg) -> bool:
    # fp32 master params per device with TP-only sharding over model=16
    return cfg.param_count() * 4 / 16 > 4e9


def _wants_offload(cfg) -> bool:
    # moments don't fit on device even fully sharded -> pooled-memory tier
    return cfg.param_count() * 12 / 256 > 8e9


def _pick_microbatches(cfg, shape: ShapeSpec, dp: int) -> int:
    if shape.kind != "train":
        return 1
    b_loc = max(shape.global_batch // dp, 1)
    per_sample = shape.seq_len * cfg.d_model * 2 * max(cfg.num_layers, 1)
    mb = 1
    while b_loc // mb > 1 and (b_loc // mb) * per_sample > _ACT_BUDGET:
        mb *= 2
    return min(mb, b_loc)


def make_context(cfg, shape: ShapeSpec, mesh, *, fsdp=None,
                 schedule: str = "rect") -> ParallelContext:
    rules = dict(DEFAULT_RULES)
    fsdp = _needs_fsdp(cfg) if fsdp is None else fsdp
    if shape.kind == "train" and fsdp:
        rules["param_embed"] = "data"
        rules["expert_mlp"] = "data"
    if shape.kind == "decode":
        rules["kv_seq"] = "model"   # flash-decoding style KV-seq sharding
    return ParallelContext(mesh=mesh, rules=rules,
                           dp_axes=("pod", "data"),
                           attn_schedule=schedule)


def model_flops_for(cfg, shape: ShapeSpec) -> float:
    n_active = cfg.active_param_count()
    n_total = cfg.param_count()
    if shape.kind == "train":
        return 6.0 * (n_active if cfg.moe else n_total) * shape.tokens
    return 2.0 * n_active * shape.tokens


def _shardings(ctx, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(ctx.mesh, s), spec_tree)


def lower_cell(arch: str, shape_name: str, multi_pod: bool, *,
               offload: str = "auto", schedule: str = "rect"):
    """Build + lower + compile one cell; returns (compiled, info dict)."""
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    # pool-scale strategy (arctic-class): ZeRO-3 sharding + bf16 params +
    # int8 moments + bf16 grad accumulation. ``--offload on`` additionally
    # uses pinned_host moments (real-TPU path; the CPU dry-run backend
    # rejects host-placement annotations under SPMD — DESIGN.md §2c).
    pool_scale = _wants_offload(cfg) and shape.kind == "train"
    optimizer = "adamw_q8" if pool_scale else "adamw"
    if pool_scale:
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    ctx = make_context(cfg, shape, mesh, schedule=schedule)
    model = build_model(cfg, ctx)
    dp = int(np.prod([mesh.shape[a] for a in ctx.dp_axes]))

    batch_struct = model.batch_struct(shape)
    batch_sh = _shardings(ctx, batch_specs(ctx, batch_struct))

    t0 = time.time()
    if shape.kind == "train":
        mb = _pick_microbatches(cfg, shape, dp)
        state = abstract_train_state(model, optimizer=optimizer)
        state_specs = param_specs(ctx, state)   # handles params + q8 moments
        state_in = _shardings(ctx, state_specs)
        do_offload = offload == "on"   # real-TPU path only; see above
        if do_offload:
            def _host(sh, leaf):
                # Offload sharded, non-trivial moment slabs to the pooled
                # tier; tiny/replicated leaves stay in HBM (XLA SPMD rejects
                # host-placement annotations on replicated values).
                nbytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                if any(e is not None for e in sh.spec) and nbytes >= (1 << 20):
                    return sh.with_memory_kind("pinned_host")
                return sh

            for mom in ("mu", "nu"):
                state_in["opt"][mom] = jax.tree.map(
                    _host, state_in["opt"][mom], state["opt"][mom])
            # out_shardings: explicit host for offloaded slabs, None (infer)
            # elsewhere — explicit *replicated* out-shardings next to host
            # annotations trip XLA's SPMD side-effect checks.
            state_out = jax.tree.map(
                lambda s: s if (s.memory_kind == "pinned_host"
                                or any(e is not None for e in s.spec)) else None,
                state_in, is_leaf=lambda x: x is None or hasattr(x, "spec"))
        else:
            state_out = state_in
        step = build_train_step(
            model, AdamWConfig(), microbatches=mb, optimizer=optimizer,
            accum_dtype=jnp.bfloat16 if pool_scale else jnp.float32)
        jitted = jax.jit(step, in_shardings=(state_in, batch_sh),
                         out_shardings=(state_out, None), donate_argnums=0)
        lowered = jitted.lower(state, batch_struct)
        extra = {"microbatches": mb, "fsdp": ctx.rules.get("param_embed") == "data",
                 "offload": bool(do_offload), "optimizer": optimizer}
    elif shape.kind == "prefill":
        state = abstract_train_state(model)   # only .params used
        psh = _shardings(ctx, param_specs(ctx, state["params"]))
        step = build_prefill_step(model)
        jitted = jax.jit(step, in_shardings=(psh, batch_sh))
        lowered = jitted.lower(state["params"], batch_struct)
        extra = {}
    else:  # decode
        state = abstract_train_state(model)
        psh = _shardings(ctx, param_specs(ctx, state["params"]))
        cache_struct = model.cache_struct(shape)
        cache_sh = _shardings(ctx, cache_specs(ctx, cache_struct))
        step = build_decode_step(model)
        jitted = jax.jit(step, in_shardings=(psh, cache_sh, batch_sh),
                         out_shardings=(None, cache_sh), donate_argnums=1)
        lowered = jitted.lower(state["params"], cache_struct, batch_struct)
        extra = {}
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    terms = analyze(compiled, chips, model_flops_for(cfg, shape),
                    TARGET_KIND)
    info = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "kind": shape.kind,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "host_argument_bytes": mem.host_argument_size_in_bytes,
            "host_temp_bytes": mem.host_temp_size_in_bytes,
            "peak_device_bytes": (mem.argument_size_in_bytes
                                  + mem.output_size_in_bytes
                                  + mem.temp_size_in_bytes
                                  - mem.alias_size_in_bytes),
        },
        "roofline": terms.to_dict(),
        **extra,
    }
    return compiled, info


def run_cell(arch, shape_name, multi_pod, out_dir: Path, offload="auto",
             keep_hlo=False, schedule="rect") -> dict:
    try:
        compiled, info = lower_cell(arch, shape_name, multi_pod,
                                    offload=offload, schedule=schedule)
        info["status"] = "ok"
        if keep_hlo:
            hlo_path = out_dir / f"{arch}__{shape_name}.hlo.txt"
            hlo_path.write_text(compiled.as_text())
    except Exception as e:  # recorded, not silently skipped
        info = {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "error", "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-4000:]}
    out_dir.mkdir(parents=True, exist_ok=True)
    out = out_dir / f"{arch}__{shape_name}.json"
    out.write_text(json.dumps(info, indent=2))
    status = info["status"]
    extra = "" if status == "ok" else info["error"][:160]
    print(f"[{info['mesh']}] {arch:24s} {shape_name:12s} {status} "
          f"compile={info.get('compile_s', '-')}s "
          f"bottleneck={info.get('roofline', {}).get('bottleneck', '-')} {extra}",
          flush=True)
    return info


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--offload", default="auto", choices=["auto", "on", "off"])
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--schedule", default="rect", choices=["rect", "grouped"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    mesh_tag = "pod2" if args.multi_pod else "pod1"
    if args.schedule != "rect":
        mesh_tag += f"_{args.schedule}"
    out_dir = Path(args.out) if args.out else RESULTS_DIR / mesh_tag

    n_ok = n_err = n_skip = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = ([s.name for s in cfg.shapes()] if args.shape == "all"
                  else args.shape.split(","))
        for shape_name in shapes:
            if shape_name in cfg.skipped_shapes():
                print(f"[{mesh_tag}] {arch:24s} {shape_name:12s} SKIP "
                      "(full attention; see DESIGN.md §Arch-applicability)",
                      flush=True)
                n_skip += 1
                continue
            info = run_cell(arch, shape_name, args.multi_pod, out_dir,
                            offload=args.offload, keep_hlo=args.keep_hlo,
                            schedule=args.schedule)
            n_ok += info["status"] == "ok"
            n_err += info["status"] != "ok"
    print(f"done: ok={n_ok} err={n_err} skip={n_skip}")
    raise SystemExit(1 if n_err else 0)


if __name__ == "__main__":
    main()
