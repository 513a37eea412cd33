"""Which code the tracer-safety checks apply to.

The TC/HS checks only make sense *inside the jitted call graph* — the
functions that execute under ``jax.jit`` when a compile group runs:
everything rooted at ``famsim._make_step`` / ``_make_run*`` (the phase
functions, the cache/SPP/throttle/controller kernels, the policy
protocol methods, the in-graph trace generator). Host-side builders,
planners, and drivers legitimately branch on Python values and
materialize arrays, so they are *out* of scope by construction — scoping
is what keeps the analyzer at zero false positives on the real tree.

Scope is declared per file (suffix-matched) as include/exclude sets of
top-level function or ``Class.method`` names; nested functions inherit
their parent's scope (``famsim._make_step`` is in scope, therefore the
``step`` closure it returns is too). A module outside the table can
opt whole-file into a scope with a marker comment in its first lines::

    # analysis-scope: jit              (TC/HS checks apply to the file)
    # analysis-scope: deterministic    (DT checks apply to the file)

— that is how the fixture corpus under ``tests/fixtures/analysis/`` is
scoped, and how a future module can opt in without touching this table.

``@host_metric`` (see :mod:`repro.analysis.annotations`) is the
*opposite* marker: it declares one function inside an in-scope module as
deliberately host-side (e.g. a metrics reduction over already-fetched
numpy arrays), excluding it from TC/HS.

The DT (determinism) checks run on the modules whose outputs must be
bit-reproducible across processes — trace synthesis, plan/spec
construction, the simulator core, configs, and the benchmark drivers.
``experiments/executor.py`` is deliberately NOT in DT scope: measuring
wall-clock is its job (``time.perf_counter`` throughout), and its
outputs are timings, not plans.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import FrozenSet, Optional, Tuple

#: parameter names that are static by convention inside the jit scope
#: (builder arguments closed over before tracing ever starts)
STATIC_PARAM_NAMES: FrozenSet[str] = frozenset({
    "self", "cls", "cfg", "config", "policies", "pol_set",
    "num_nodes", "degree", "warmup_frac", "pad_sets", "pad_ways",
    "trace_gen", "trace_key",
})

#: annotations that mark a parameter static (Python-level value)
STATIC_ANNOTATIONS: FrozenSet[str] = frozenset({
    "int", "str", "bool", "float", "FamConfig", "PolicySet", "SimFlags",
})

#: attribute reads that yield static Python values off traced arrays
#: and pytrees (``w_pad``: ``dram_cache.CacheState``'s allocated ways, a
#: static field of the pytree)
STATIC_ATTRS: FrozenSet[str] = frozenset({
    "shape", "dtype", "ndim", "size", "at", "w_pad",
})


@dataclass(frozen=True)
class Scope:
    """In-jit-scope selection for one file: ``include`` limits scope to
    the named top-level symbols, ``exclude`` removes them; with neither,
    the whole file is in scope."""

    include: Optional[FrozenSet[str]] = None
    exclude: FrozenSet[str] = frozenset()

    def contains(self, symbol: str) -> bool:
        parts = set(symbol.split("."))
        if (self.exclude & parts) or symbol in self.exclude:
            return False
        if self.include is None:
            return True
        return bool(self.include & parts) or symbol in self.include


def _s(include=None, exclude=()):
    return Scope(include=frozenset(include) if include is not None else None,
                 exclude=frozenset(exclude))


#: file suffix -> jit Scope. Builders/drivers listed in ``exclude`` are
#: host-side: they run once at build/plan time, never under jit.
JIT_SCOPE = {
    "repro/core/famsim.py": _s(exclude={
        "_resolve", "build_sim", "build_sweep", "build_masked_vmap",
        "sweep", "simulate"}),
    "repro/core/dram_cache.py": _s(),
    "repro/core/spp.py": _s(exclude={"storage_bits"}),
    "repro/core/throttle.py": _s(),
    "repro/core/fam_controller.py": _s(),
    "repro/core/prefetch_queue.py": _s(),
    # only the dyn_* traced-geometry helpers run under jit; the classic
    # int-typed helpers are host-side shape math
    "repro/core/addresses.py": _s(include={
        "dyn_block_bits", "dyn_blocks_per_page", "dyn_split",
        "dyn_block_addr"}),
    # metric reductions: host-side by design, but kept in scope so any
    # NEW host sync must be explicitly @host_metric-annotated
    "repro/core/ipc_model.py": _s(),
    "repro/policies/prefetch.py": _s(exclude={"params_of"}),
    "repro/policies/scheduler.py": _s(exclude={"params_of", "__init__"}),
    "repro/policies/replacement.py": _s(exclude={"params_of", "__init__"}),
    "repro/policies/adaptation.py": _s(exclude={"params_of"}),
    # in-graph trace generation; the host-side param builders are out
    "repro/traces/device.py": _s(include={"node_generator",
                                          "_jitted_system"}),
    # fused cache-step kernel package: only the dispatch wrapper runs
    # under jit here; fused_replacement_mode is build-time validation on
    # the policy OBJECT (Python control flow on static attrs is its
    # job). kernel.py / ref.py opt whole-file in via the jit marker.
    "repro/kernels/famsim_step/ops.py": _s(include={"cache_step"}),
}

#: files/dirs (suffix-matched) under the determinism lints
DT_SCOPE_SUFFIXES: Tuple[str, ...] = (
    "repro/traces/", "repro/core/", "repro/configs/", "repro/policies/",
    "repro/experiments/plan.py", "repro/experiments/spec.py",
    # the search layer's trajectory/best artifacts are byte-identity
    # contracts: seeded-Generator-only RNG, no wall clock, no set-order
    # dependence anywhere in the package
    "repro/search/",
    # tenant fleets lower onto compile-keyed experiments: seed derivation,
    # admission order, and the per-tenant record schema are all
    # byte-identity contracts
    "repro/tenants/",
    "benchmarks/",
)

#: marker comments for whole-file opt-in (first MARKER_LINES lines)
MARKER_LINES = 8
JIT_MARKER = "# analysis-scope: jit"
DT_MARKER = "# analysis-scope: deterministic"

#: decorator that opts one function OUT of TC/HS (host-side metrics)
HOST_METRIC_DECORATOR = "host_metric"


def _norm(path: str) -> str:
    return path.replace("\\", "/")


def _has_marker(source: str, marker: str) -> bool:
    head = source.splitlines()[:MARKER_LINES]
    return any(line.strip().startswith(marker) for line in head)


def jit_scope_for(path: str, source: str) -> Optional[Scope]:
    """The jit Scope for ``path`` (None: TC/HS do not apply at all)."""
    norm = _norm(path)
    for suffix, scope in JIT_SCOPE.items():
        if norm.endswith(suffix):
            return scope
    if _has_marker(source, JIT_MARKER):
        return Scope()
    return None


def in_dt_scope(path: str, source: str) -> bool:
    norm = _norm(path)
    if any(s.rstrip("/") + "/" in norm or norm.endswith(s)
           for s in DT_SCOPE_SUFFIXES):
        return True
    return _has_marker(source, DT_MARKER)


def is_host_metric(node: ast.FunctionDef) -> bool:
    """True when the function is ``@host_metric``-decorated (by name —
    the analyzer never imports the code it scans)."""
    for dec in node.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = target.attr if isinstance(target, ast.Attribute) else \
            getattr(target, "id", None)
        if name == HOST_METRIC_DECORATOR:
            return True
    return False
