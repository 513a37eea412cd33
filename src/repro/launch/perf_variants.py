import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
"""§Perf hillclimb harness: lower + analyze optimization VARIANTS of the
three chosen cells against their baselines, recording
hypothesis -> change -> before -> after in results/perf/.

Variants:
  qwen2 decode:  buffered    — read-only cache + write buffer (+ amortized
                               flush step), killing the sharded-DUS select
                 f32probe    — f32 activations/cache (quantifies the CPU
                               backend's bf16-emulation inflation)
                 int8kv      — int8 KV cache blocks (2x read traffic cut)
  arctic train:  gradsync    — accumulate grads locally in the microbatch
                               scan, reduce once per step (vs per-microbatch)
                 cf10        — MoE capacity factor 1.25 -> 1.0
  xlstm train:   chunked     — (documented design; baseline re-measured with
                               fused gates) — see EXPERIMENTS.md
"""
import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES_BY_NAME
from repro.configs.registry import get_config
from repro.launch.dryrun import (TARGET_KIND, _shardings,
                                 abstract_train_state, make_context,
                                 model_flops_for)
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.models.model_zoo import batch_specs, build_model, cache_specs
from repro.roofline.analysis import analyze

RESULTS = Path(__file__).resolve().parents[3] / "results" / "perf"


def record(cell: str, variant: str, compiled, chips, model_flops, extra=None):
    terms = analyze(compiled, chips, model_flops, TARGET_KIND)
    mem = compiled.memory_analysis()
    info = {"cell": cell, "variant": variant,
            "roofline": terms.to_dict(),
            "peak_device_bytes": (mem.argument_size_in_bytes
                                  + mem.output_size_in_bytes
                                  + mem.temp_size_in_bytes
                                  - mem.alias_size_in_bytes),
            **(extra or {})}
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / f"{cell}__{variant}.json").write_text(json.dumps(info, indent=2))
    r = info["roofline"]
    print(f"{cell} [{variant}] compute={r['compute_s']:.3f} "
          f"memory={r['memory_s']:.3f} coll={r['collective_s']:.3f} "
          f"bottleneck={r['bottleneck']} mfu_bound={r['mfu_bound']:.4f}",
          flush=True)
    return info


# ---------------------------------------------------------------------------
# qwen2-vl-72b decode_32k variants
# ---------------------------------------------------------------------------

def qwen_buffered(window: int = 64, kv_dtype="bfloat16"):
    arch, shape_name = "qwen2-vl-72b", "decode_32k"
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh()
    chips = 256
    ctx = make_context(cfg, shape, mesh)
    model = build_model(cfg, ctx)
    state = abstract_train_state(model)
    psh = _shardings(ctx, __import__("repro.parallel.sharding",
                                     fromlist=["param_specs"]).param_specs(
        ctx, state["params"]))

    B, S = shape.global_batch, shape.seq_len
    kvdt = jnp.dtype(kv_dtype)
    sd = jax.ShapeDtypeStruct
    kv = (cfg.num_layers, B, S, cfg.num_kv_heads, cfg.head_dim)
    buf = (cfg.num_layers, B, window, cfg.num_kv_heads, cfg.head_dim)
    cache = {"k": sd(kv, kvdt), "v": sd(kv, kvdt)}
    buffer = {"k": sd(buf, jnp.bfloat16), "v": sd(buf, jnp.bfloat16)}
    cache_sh = _shardings(ctx, cache_specs(ctx, cache))
    buf_sh = jax.tree.map(
        lambda l: NamedSharding(mesh, P(None, ("pod", "data") if "pod" in
                                        mesh.axis_names else "data")),
        buffer)
    tok = sd((B, 1), jnp.int32)
    scalars = sd((), jnp.int32)

    def serve_step(params, cache, buffer, tokens, base_len, buf_len):
        if kvdt == jnp.int8:
            # int8 KV: dequantize per-layer inside the scan via scale=1/64
            cache = jax.tree.map(
                lambda c: (c.astype(jnp.bfloat16) * (1.0 / 64.0)).astype(
                    jnp.bfloat16) if c.dtype == jnp.int8 else c, cache)
        logits, new_buf = T.decode_step_buffered(
            cfg, ctx, params, cache, buffer, tokens, base_len, buf_len)
        return jnp.argmax(logits, -1).astype(jnp.int32), new_buf

    jitted = jax.jit(serve_step,
                     in_shardings=(psh, cache_sh, buf_sh, None, None, None),
                     out_shardings=(None, buf_sh), donate_argnums=2)
    t0 = time.time()
    compiled = jitted.lower(state["params"], cache, buffer, tok,
                            scalars, scalars).compile()
    dt = time.time() - t0

    # the amortized flush step (runs once every `window` tokens)
    def flush(cache, buffer, base_len):
        return T.flush_buffer(cfg, cache, buffer, base_len)

    fl = jax.jit(flush, in_shardings=(cache_sh, buf_sh, None),
                 out_shardings=cache_sh, donate_argnums=0)
    flushed = fl.lower(cache, buffer, scalars).compile()
    f_terms = analyze(flushed, chips, 0.0, TARGET_KIND)

    variant = f"buffered_w{window}" + ("_int8" if kvdt == jnp.int8 else "")
    info = record("qwen2-vl-72b__decode_32k", variant, compiled, chips,
                  model_flops_for(cfg, shape),
                  extra={"compile_s": round(dt, 1),
                         "flush_memory_s": f_terms.memory_s,
                         "flush_amortized_memory_s": f_terms.memory_s / window})
    return info


def qwen_f32probe():
    import repro.configs.registry as reg
    orig = reg.get_config
    cfg = dataclasses.replace(orig("qwen2-vl-72b"), dtype="float32")
    from repro.launch import dryrun as DR
    old = DR.get_config
    DR.get_config = lambda a: cfg if a == "qwen2-vl-72b" else old(a)
    try:
        compiled, info = DR.lower_cell("qwen2-vl-72b", "decode_32k", False)
    finally:
        DR.get_config = old
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / "qwen2-vl-72b__decode_32k__f32probe.json").write_text(
        json.dumps(info, indent=2))
    r = info["roofline"]
    print(f"qwen2-vl-72b__decode_32k [f32probe] memory={r['memory_s']:.3f} "
          f"(bf16-projected ~{r['memory_s']/2:.3f})", flush=True)
    return info


# ---------------------------------------------------------------------------
# arctic-480b train_4k variants
# ---------------------------------------------------------------------------

def arctic_variant(variant: str):
    from repro.launch import dryrun as DR
    arch, shape_name = "arctic-480b", "train_4k"
    if variant == "cf10":
        import repro.parallel.sharding as SH
        # tighter MoE capacity via context default
        old_init = SH.ParallelContext.__post_init__

        def patched(self):
            old_init(self)
            self.capacity_factor = 1.0
        SH.ParallelContext.__post_init__ = patched
        try:
            compiled, info = DR.lower_cell(arch, shape_name, False)
        finally:
            SH.ParallelContext.__post_init__ = old_init
    elif variant == "combined":
        # cf=1.0 + microbatches=8: stack both confirmed wins at a peak-memory
        # point between the baseline and gradsync
        import repro.parallel.sharding as SH
        old_init = SH.ParallelContext.__post_init__

        def patched(self):
            old_init(self)
            self.capacity_factor = 1.0
        SH.ParallelContext.__post_init__ = patched
        old_mb = DR._pick_microbatches
        DR._pick_microbatches = lambda cfg, shape, dp: 8
        try:
            compiled, info = DR.lower_cell(arch, shape_name, False)
        finally:
            SH.ParallelContext.__post_init__ = old_init
            DR._pick_microbatches = old_mb
    elif variant == "gradsync":
        # accumulate grads with per-microbatch psum deferred: emulate by
        # raising microbatch size (fewer accumulation rounds => fewer
        # per-round reduce-scatters). Implemented as _pick_microbatches
        # override mb=4 (vs auto 16).
        old = DR._pick_microbatches
        DR._pick_microbatches = lambda cfg, shape, dp: 4
        try:
            compiled, info = DR.lower_cell(arch, shape_name, False)
        finally:
            DR._pick_microbatches = old
    else:
        raise ValueError(variant)
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / f"arctic-480b__train_4k__{variant}.json").write_text(
        json.dumps(info, indent=2))
    r = info["roofline"]
    print(f"arctic-480b__train_4k [{variant}] compute={r['compute_s']:.2f} "
          f"memory={r['memory_s']:.2f} coll={r['collective_s']:.2f} "
          f"peak={info['memory']['peak_device_bytes']/2**30:.1f}GiB",
          flush=True)
    return info


def grouped_prefill(arch="qwen2-vl-72b"):
    """Triangular attention schedule for a prefill cell (predict ~0.56x on
    the attention flops slice; see attention.attend_grouped)."""
    from repro.launch import dryrun as DR
    import repro.parallel.sharding as SH
    old_init = SH.ParallelContext.__post_init__

    def patched(self):
        old_init(self)
        self.attn_schedule = "grouped"
    SH.ParallelContext.__post_init__ = patched
    try:
        compiled, info = DR.lower_cell(arch, "prefill_32k", False)
    finally:
        SH.ParallelContext.__post_init__ = old_init
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / f"{arch}__prefill_32k__grouped.json").write_text(
        json.dumps(info, indent=2))
    r = info["roofline"]
    print(f"{arch}__prefill_32k [grouped] compute={r['compute_s']:.3f} "
          f"memory={r['memory_s']:.3f} coll={r['collective_s']:.3f} "
          f"mfu_bound={r['mfu_bound']:.4f}", flush=True)
    return info


def xlstm_chunked(chunk: int = 128):
    from repro.launch import dryrun as DR
    cfg0 = get_config("xlstm-350m")
    cfg = dataclasses.replace(
        cfg0, xlstm=dataclasses.replace(cfg0.xlstm, chunk=chunk,
                                        parallel_mlstm=True))
    old = DR.get_config
    DR.get_config = lambda a: cfg if a == "xlstm-350m" else old(a)
    try:
        compiled, info = DR.lower_cell("xlstm-350m", "train_4k", False)
    finally:
        DR.get_config = old
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / f"xlstm-350m__train_4k__chunked{chunk}.json").write_text(
        json.dumps(info, indent=2))
    r = info["roofline"]
    print(f"xlstm-350m__train_4k [chunked{chunk}] "
          f"compute={r['compute_s']:.3f} memory={r['memory_s']:.3f} "
          f"coll={r['collective_s']:.3f} mfu_bound={r['mfu_bound']:.4f}",
          flush=True)
    return info


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--which", required=True)
    ap.add_argument("--window", type=int, default=64)
    args = ap.parse_args()
    if args.which == "qwen-buffered":
        qwen_buffered(args.window)
    elif args.which == "qwen-buffered-int8":
        qwen_buffered(args.window, kv_dtype="int8")
    elif args.which == "qwen-f32probe":
        qwen_f32probe()
    elif args.which in ("cf10", "gradsync", "combined"):
        arctic_variant(args.which)
    elif args.which == "xlstm-chunked":
        xlstm_chunked(args.window if args.window != 64 else 128)
    elif args.which == "grouped-prefill":
        grouped_prefill()
    else:
        raise SystemExit(f"unknown {args.which}")


if __name__ == "__main__":
    main()
