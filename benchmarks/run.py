"""Benchmark entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the scaffold contract and saves
full JSON rows under results/benchmarks/.

Select figures positionally and pass ``--full`` through to each figure's
``run(quick=)``; ``--plan`` dry-runs the planner instead of executing::

    python -m benchmarks.run                  # all figures, quick subset
    python -m benchmarks.run fig08 fig16      # just these two
    python -m benchmarks.run --full fig14     # fig14 over all 19 workloads
    python -m benchmarks.run --plan           # print compile groups, run nothing
    python -m benchmarks.run --trace-backend numpy fig14   # host ref traces
    python -m benchmarks.run --check fig08    # static-analysis gate first

``--policies`` sweeps the repro.policies zoo as a policy matrix on the
figures that support it (fig12)::

    python -m benchmarks.run --policies scheduler=fifo,wfq,strict \\
        --policies prefetch=spp,nextline fig12

``search`` hands the remaining arguments to the design-space search
driver (``benchmarks.fig_search`` over ``repro.search``)::

    python -m benchmarks.run search --proposer evolutionary
    python -m benchmarks.run search --proposer random --generations 2
    python -m benchmarks.run search --replay results/search/best.json

``bench`` runs the tracked famsim throughput benchmark
(``benchmarks.bench_famsim`` — see docs/performance.md)::

    python -m benchmarks.run bench                    # both backends
    python -m benchmarks.run bench --quick            # CI scale

``pond`` runs the multi-tenant fleet scenario (``benchmarks.fig_pond``
over ``repro.tenants`` — see docs/tenants.md)::

    python -m benchmarks.run pond --quick             # CI-scale fleets
    python -m benchmarks.run pond --full              # up to 1024 tenants
    python -m benchmarks.run pond --plan              # dry-run the grids

``--kernel-backend pallas`` routes the figures' cache engine through the
fused Pallas kernel (bit-identical to the default ``xla`` path; see
docs/performance.md)::

    python -m benchmarks.run --kernel-backend pallas fig08

``--telemetry [N]`` turns on the observability layer (``repro.obs``, see
docs/observability.md): in-graph windowed counters saved to
results/telemetry/<figure>.json (render with ``python -m repro.obs
report``) plus a host span timeline saved to results/trace/<figure>.json
(load in ui.perfetto.dev)::

    python -m benchmarks.run fig10 --telemetry
    python -m benchmarks.run fig10 --telemetry 64       # explicit windows
    python -m repro.obs report results/telemetry/fig10_bw_adaptation.json
"""
from __future__ import annotations

import argparse
import inspect
import os
import sys
import time

# allow `python benchmarks/run.py` (script path on sys.path, repo root not)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FIGURE_NAMES = ("fig08", "fig10", "fig12", "fig14", "fig15", "fig16")


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else list(argv)
    from benchmarks.common import use_compile_cache
    if argv and argv[0] in ("search", "bench", "pond"):
        use_compile_cache()
    if argv and argv[0] == "search":
        # the search subcommand owns its whole argument tail
        from benchmarks import fig_search
        fig_search.main(argv[1:])
        return
    if argv and argv[0] == "bench":
        # so does the throughput-benchmark subcommand
        from benchmarks import bench_famsim
        bench_famsim.main(argv[1:])
        return
    if argv and argv[0] == "pond":
        # multi-tenant fleet scenario (benchmarks.fig_pond over
        # repro.tenants — see docs/tenants.md)
        from benchmarks import fig_pond
        fig_pond.main(argv[1:])
        return
    ap = argparse.ArgumentParser(
        description="Run paper-figure benchmarks through repro.experiments")
    ap.add_argument("figures", nargs="*", metavar="figure",
                    help=f"figure names to run (default: all of "
                         f"{', '.join(FIGURE_NAMES)})")
    ap.add_argument("--full", action="store_true",
                    help="all 19 workloads per figure (default: quick subset)")
    ap.add_argument("--plan", action="store_true",
                    help="dry-run: print each figure's resolved compile "
                         "groups (key, point count, pad overhead) without "
                         "executing anything")
    ap.add_argument("--kernel-backend", choices=("xla", "pallas"),
                    default="xla",
                    help="cache-engine implementation (a STATIC compile "
                         "tag on every figure's base config): 'xla' keeps "
                         "the classic hot path, 'pallas' routes the "
                         "per-event DRAM-cache work through the fused "
                         "kernel — bit-identical metrics either way (see "
                         "docs/performance.md)")
    ap.add_argument("--trace-backend", choices=("device", "numpy"),
                    default="device",
                    help="trace synthesis backend: 'device' generates "
                         "traces in-graph on device (default; zero "
                         "host-side generation), 'numpy' stages the host "
                         "reference generators (never changes compile "
                         "groups, only the trace source)")
    ap.add_argument("--telemetry", nargs="?", const=32, default=0, type=int,
                    metavar="N_WINDOWS",
                    help="observability mode (repro.obs): accumulate "
                         "in-graph windowed telemetry counters (N_WINDOWS "
                         "windows per run; bare flag = 32) into "
                         "results/telemetry/<figure>.json and record a "
                         "host span timeline (plan/compile/stage/run/"
                         "fetch) into results/trace/<figure>.json. A "
                         "STATIC compile tag: 0 (default) runs the exact "
                         "pre-telemetry programs (see "
                         "docs/observability.md)")
    ap.add_argument("--policies", action="append", default=None,
                    metavar="KIND=NAME[,NAME...]",
                    help="policy-matrix mode (repeatable): sweep the named "
                         "repro.policies per kind (prefetch / scheduler / "
                         "replacement / adaptation) as the cross-product of "
                         "PolicySet combos, on figures that support it "
                         "(fig12). Unlisted kinds keep their defaults; the "
                         "all-default combo is the required baseline")
    ap.add_argument("--check", action="store_true",
                    help="run the repro.analysis static gate first (src/ + "
                         "benchmarks/, strict mode) and abort on any "
                         "non-allowlisted finding — the pre-flight that "
                         "catches a compile-key leak before paying for the "
                         "run (see docs/analysis.md)")
    ap.add_argument("--only", default=None,
                    help="deprecated comma-list alternative to positional "
                         "figure names (fig08,fig10,...)")
    args = ap.parse_args(argv)

    if args.check:
        from repro.analysis import run_analysis
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        code = run_analysis([os.path.join(root, "src"),
                             os.path.join(root, "benchmarks")], strict=True)
        if code:
            sys.exit(code)
        print("# repro.analysis: clean", file=sys.stderr)

    from benchmarks import (fig08_blocksize, fig10_bw_adaptation, fig12_wfq,
                            fig14_mixes, fig15_allocation, fig16_cachesize)
    figures = {
        "fig08": fig08_blocksize, "fig10": fig10_bw_adaptation,
        "fig12": fig12_wfq, "fig14": fig14_mixes,
        "fig15": fig15_allocation, "fig16": fig16_cachesize,
    }
    keep = set(args.figures)
    if args.only:
        keep |= set(args.only.split(","))
    if keep:
        unknown = keep - set(figures)
        if unknown:
            ap.error(f"unknown figures: {sorted(unknown)} "
                     f"(choose from {list(figures)})")
        figures = {k: v for k, v in figures.items() if k in keep}

    combos = None
    if args.policies:
        combos = policy_combos(args.policies, ap.error)
        unsupported = [k for k, mod in figures.items()
                       if "policies" not in
                       inspect.signature(mod.run).parameters]
        if unsupported:
            ap.error(f"--policies is not supported by {unsupported} "
                     "(supported: fig12); select supported figures "
                     "explicitly")

    if args.plan:
        print_plans(figures, quick=not args.full, policies=combos,
                    kernel_backend=args.kernel_backend,
                    telemetry=args.telemetry)
        return

    use_compile_cache()
    print("name,us_per_call,derived")
    for key, mod in figures.items():
        t0 = time.time()
        kw = {} if combos is None else {"policies": combos}
        rows = mod.run(quick=not args.full,
                       trace_backend=args.trace_backend,
                       kernel_backend=args.kernel_backend,
                       telemetry=args.telemetry, **kw)
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.3f},\"{r['derived']}\"",
                  flush=True)
        print(f"# {key} wall={time.time() - t0:.1f}s", file=sys.stderr)


def policy_combos(specs, error):
    """Parse repeated ``KIND=NAME[,NAME...]`` args into the cross-product
    of labelled PolicySets. Labels join the swept kinds' policy names in
    canonical kind order (``spp+fifo``), so the all-default combo — the
    baseline the drivers measure against — is labelled by its default
    names."""
    import itertools

    from repro.policies import POLICY_KINDS, PolicySet, available

    swept = {}
    for spec in specs:
        kind, eq, names = spec.partition("=")
        if not eq or not names:
            error(f"--policies expects KIND=NAME[,NAME...], got {spec!r}")
        if kind not in POLICY_KINDS:
            error(f"unknown policy kind {kind!r} (kinds: {POLICY_KINDS})")
        for n in names.split(","):
            if n not in available(kind):
                error(f"unknown {kind} policy {n!r} "
                      f"(available: {available(kind)})")
        swept[kind] = names.split(",")
    kinds = [k for k in POLICY_KINDS if k in swept]
    combos = {}
    for values in itertools.product(*(swept[k] for k in kinds)):
        label = "+".join(values)
        combos[label] = PolicySet(**dict(zip(kinds, values)))
    return combos


def print_plans(figures, quick: bool, policies=None,
                kernel_backend: str = "xla", telemetry: int = 0) -> None:
    """``--plan``: resolve and print every figure's compile groups without
    generating a trace or compiling anything. One summary line per figure
    (``<name>: G group(s), P points, E events (+X padded, O% overhead)``)
    plus one indented line per group — deterministic, so tests assert the
    one-group-per-figure ceilings on this exact output. With ``policies``
    (the --policies matrix) the figure's policy experiment is planned
    instead."""
    from benchmarks.common import plan_lines
    for key, mod in figures.items():
        if policies is not None:
            exp = mod.policy_experiment(
                policies, quick=quick, kernel_backend=kernel_backend,
                telemetry=telemetry)
        else:
            exp = mod.experiment(
                quick=quick, kernel_backend=kernel_backend,
                telemetry=telemetry)
        for line in plan_lines(exp.plan(), exp.axes):
            print(line)


if __name__ == "__main__":
    main()
