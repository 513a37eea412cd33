"""Plan executor: device sharding, in-graph trace synthesis, async overlap.

One :class:`~repro.experiments.plan.CompileGroup` is one AOT compile and
one device call: the group's S systems are vmapped together — the cache
state allocated at the group's padded ``(pad_sets, pad_ways)`` geometry
with each system's effective geometry masking it down (bit-exact, see
``repro.core.dram_cache``), the system axis padded to the group's
canonical ``s_pad`` width by repeating the last member (inert: vmap lanes
share no FAM-controller/WFQ state, and padded lanes' results are dropped
before they reach any metric) — and, when more than one device is
visible, the S axis is sharded across devices with
``repro.parallel.compat.shard_map`` (a 1-device run falls back to a plain
``jax.jit`` of the same vmapped program, so the two paths execute
identical per-system code and are cross-checked bit-exact).

Trace synthesis is a pluggable backend (``plan.trace_backend``, see
:mod:`repro.traces.backend`):

* ``device`` (default) — the NO-HOST fast path: each group's compiled
  program takes the numeric :class:`~repro.traces.device.TraceParams`
  encoding (a handful of scalars per node) and generates every node
  trace *in graph*, vmapped over (system, node), fused with the
  simulation. Zero host-side trace generation on the steady-state path
  (``RunInfo.host_trace_events == 0``) and nothing to overlap.
* ``numpy`` — the reference oracle: host-side generation for group i+1
  overlaps device simulation of group i (double-buffered through a
  one-worker thread pool); trace arrays are memoized per
  ``(workload, T, node_seed)`` so repeated points are free.

Either way ``ResolvedPoint.seed`` threads into
``traces.node_seed(seed, node_index)`` — repeated points that differ only
in seed simulate different traces.

Compile time is measured separately from steady-state run time
(``jit(...).lower(...).compile()`` + ``block_until_ready``) and recorded
per group, so ``us_per_event`` reflects simulation only;
``RunInfo.trace_gen_s`` records the host-side trace/param staging time,
and ``RunInfo.place_s`` the part of it that places the inputs on the mesh
when the group is sharded.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.dram_cache import storage as cache_storage
from repro.core.fam_params import FamParams, stack_params
from repro.core.famsim import build_masked_vmap
from repro.experiments.plan import CompileGroup, Plan, s_bucket
from repro.experiments.spec import ResolvedPoint
from repro.obs.spans import current_tracer, maybe_span
from repro.traces import generate, node_seed
from repro.traces.backend import DEFAULT_BACKEND


def _key_digest(key: Tuple) -> str:
    """Short stable digest of an executable-cache key — suffixes the
    group runner's jit name (``famsim_group__<digest>``) so the runtime
    CompileWatcher can attribute each XLA compile to its group, and tags
    the group's trace spans / ``info.groups`` row."""
    return hashlib.sha1(repr(key).encode()).hexdigest()[:8]


@dataclass
class RunInfo:
    """Wall-clock / compile accounting for one executed plan."""

    compiles: int = 0              # fresh compiles (0 if executables cached)
    planned_groups: int = 0        # deterministic, unlike ``compiles``
    #: actual XLA compilations of group executables observed by the
    #: ``jax.log_compiles`` watcher (``execute(assert_compiles=True)``);
    #: -1 = not watched. The runtime proof that the planner's one-
    #: executable promise held — counted by the ``famsim_group`` name,
    #: so incidental prim jits don't pollute it.
    xla_compiles: int = -1
    compile_s: float = 0.0
    run_s: float = 0.0
    #: executable-cache accounting (first-class so callers — e.g. the
    #: repro.search loop's cost model — never poke at ``_EXEC_CACHE``):
    #: per group-runner lookup during this execute, was the compiled
    #: executable already cached (hit) or freshly built (miss)?
    exec_cache_hits: int = 0
    exec_cache_misses: int = 0
    #: groups of THIS plan whose executable predated this execute call —
    #: the warm-start count a repeated sweep (or a search generation
    #: moving only traced params) should drive to ``planned_groups``
    groups_reused: int = 0
    systems: int = 0
    events: int = 0                # true simulated events (sum N*t_true)
    padded_events: int = 0         # extra events paid to T/S padding
    padded_systems: int = 0        # inert systems added for canonical S
    devices: int = 1
    #: ``jax.devices()[0]``'s platform and kind: every timing above was
    #: taken on this device
    platform: str = ""
    device_kind: str = ""
    trace_backend: str = DEFAULT_BACKEND
    #: events actually GENERATED host-side (memoized trace-cache reuse is
    #: free, padded lanes repeat real systems): 0 = the no-host fast path
    host_trace_events: int = 0
    trace_gen_s: float = 0.0       # host trace/param staging wall-clock
    #: the part of ``trace_gen_s`` spent placing the group's inputs on the
    #: mesh (span ``stage.place``); 0 on one device, where the call takes
    #: them as staged
    place_s: float = 0.0
    #: one row per executed group: its shape, padding, the storage of its
    #: cache tables (``"lane_dense"`` or ``"sets_ways"``,
    #: ``repro.core.dram_cache.storage``) and its compile/run accounting
    groups: List[dict] = field(default_factory=list)
    shard_check: Optional[dict] = None
    #: span summary ``{name: {count, total_s}}`` from the installed
    #: :mod:`repro.obs.spans` tracer, covering this execute call only;
    #: None when no tracer is installed (the default)
    spans: Optional[dict] = None

    def us_per_call(self) -> float:
        # a plan can legitimately carry zero true events (every point
        # fully padded away); 0.0 beats a nonsense per-event figure
        if self.events <= 0:
            return 0.0
        return self.run_s / self.events * 1e6

    def as_dict(self) -> dict:
        d = {"compiles": self.compiles,
             "planned_groups": self.planned_groups,
             "compile_s": round(self.compile_s, 3),
             "run_s": round(self.run_s, 3),
             "exec_cache_hits": self.exec_cache_hits,
             "exec_cache_misses": self.exec_cache_misses,
             "groups_reused": self.groups_reused,
             "systems": self.systems, "events": self.events,
             "padded_events": self.padded_events,
             "padded_systems": self.padded_systems,
             "devices": self.devices,
             "platform": self.platform,
             "device_kind": self.device_kind,
             "trace_backend": self.trace_backend,
             "host_trace_events": self.host_trace_events,
             "trace_gen_s": round(self.trace_gen_s, 4),
             "place_s": round(self.place_s, 4),
             "us_per_event": round(self.us_per_call(), 4),
             "groups": self.groups}
        if self.xla_compiles >= 0:
            d["xla_compiles"] = self.xla_compiles
        if self.shard_check is not None:
            d["shard_check"] = self.shard_check
        if self.spans is not None:
            d["spans"] = self.spans
        return d


class ExperimentResult:
    """Per-point metrics + accounting, addressable by axis coordinates."""

    def __init__(self, points: Sequence[ResolvedPoint],
                 metrics: Sequence[Dict[str, np.ndarray]], info: RunInfo,
                 t_pads: Optional[Sequence[int]] = None):
        self.points = tuple(points)
        self.metrics = list(metrics)
        self.info = info
        #: per-point executed trace length (the group's t_pad) — what the
        #: device backend generated at; == pt.T unless the point rode a
        #: mixed-T group
        self.t_pads = tuple(t_pads) if t_pads is not None \
            else tuple(p.T for p in self.points)
        self._by_coords = {frozenset(p.coords): i
                           for i, p in enumerate(self.points)}
        self._by_point = {p: i for i, p in enumerate(self.points)}

    def metrics_for(self, pt: ResolvedPoint) -> Dict[str, np.ndarray]:
        return self.metrics[self._by_point[pt]]

    def t_pad_for(self, pt: ResolvedPoint) -> int:
        return self.t_pads[self._by_point[pt]]

    def get(self, **coords) -> Dict[str, np.ndarray]:
        """Metrics for the point at the given axis coordinates, e.g.
        ``result.get(block=256, workload="LU", variant="dram")``. Every
        axis must be specified; values are coerced to their string labels.
        """
        key = frozenset((k, str(v)) for k, v in coords.items())
        try:
            return self.metrics[self._by_coords[key]]
        except KeyError:
            raise KeyError(
                f"no point at {dict(coords)!r}; axes present: "
                f"{sorted({k for p in self.points for k, _ in p.coords})}"
            ) from None


# ---------------------------------------------------------------------------
# Trace assembly (host side, overlappable)
# ---------------------------------------------------------------------------

_TRACE_CACHE: Dict = {}


def trace_arrays(workloads: Sequence[str], T: int, seed: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """(N, T) node traces for one system; per-node seeds derive through
    ``traces.node_seed`` (shared with ``famsim.simulate``), memoized."""
    pairs = []
    for i, w in enumerate(workloads):
        k = (w, T, node_seed(seed, i))
        if k not in _TRACE_CACHE:
            _TRACE_CACHE[k] = generate(w, T, node_seed(seed, i))
        pairs.append(_TRACE_CACHE[k])
    return (np.stack([a for a, _ in pairs]),
            np.stack([g for _, g in pairs]))


@dataclass
class _GroupData:
    """Device-ready inputs for one compile group (S systems, padded).

    ``inputs`` is the backend-dependent middle of the executable's
    signature: ``(addrs (S, N, T_pad) i32, gaps (S, N, T_pad) f32)`` for
    host-staged traces, or a single stacked
    :class:`~repro.traces.device.TraceParams` (leaves ``(S, N, ...)``)
    for in-graph generation."""

    params: FamParams
    inputs: Tuple
    t_true: np.ndarray         # (S,) int32
    warm_start: np.ndarray     # (S,) int32
    host_trace_events: int = 0
    prep_s: float = 0.0
    place_s: float = 0.0


def _mesh(D: int):
    """The one-axis mesh (``"dev"``) the sharded group program splits its
    S axis over."""
    from repro.parallel import compat
    return compat.make_mesh((D,), ("dev",))


def _prepare(points: Sequence[ResolvedPoint], idxs: Sequence[int],
             t_pad: int, warmup_frac: float,
             trace_backend: str = "numpy", devices: int = 1) -> _GroupData:
    """Stage one group's inputs. With ``devices`` > 1 they are placed on
    the mesh in the sharded program's layout (span ``stage.place``), each
    device's slice straight from the host."""
    import jax
    t0 = time.perf_counter()
    pts = [points[i] for i in idxs]
    N = len(pts[0].workloads)
    S = len(pts)
    host_events = 0
    # one span per staging phase, over all S systems (docs/observability.md)
    with maybe_span("stage.traces"):
        if trace_backend == "device":
            from repro.traces.device import (stack_system_params,
                                             system_params)
            tp = stack_system_params(
                [system_params(pt.workloads, pt.seed) for pt in pts])
            inputs = (tp,)
        else:
            addrs = np.zeros((S, N, t_pad), np.int32)
            gaps = np.zeros((S, N, t_pad), np.float32)
            for j, pt in enumerate(pts):
                # count events actually GENERATED host-side (memoized
                # reuse is free — repeated points and inert padded lanes
                # cost 0)
                host_events += sum(
                    pt.T for i, w in enumerate(pt.workloads)
                    if (w, pt.T, node_seed(pt.seed, i)) not in _TRACE_CACHE)
                a, g = trace_arrays(pt.workloads, pt.T, pt.seed)
                addrs[j, :, :pt.T] = a
                gaps[j, :, :pt.T] = g
            inputs = (addrs, gaps)
    with maybe_span("stage.params"):
        per_system = [FamParams.of(pt.cfg, pt.flags, pt.policy_set())
                      for pt in pts]
    with maybe_span("stage.stack"):
        params = stack_params(per_system)
        if devices == 1:
            # host numpy stack, then ONE transfer of the whole pytree
            params = jax.device_put(params)
    # ``pt.t_true`` == pt.T unless the point is lifetime-gated (t_live,
    # e.g. an admission-throttled tenant): the traced masked-runner input
    # no-ops the non-live tail, never the compile key
    t_true = np.array([pt.t_true for pt in pts], np.int32)
    # host-side int arithmetic, matching famsim._make_run's static
    # ``int(T * warmup_frac)`` exactly
    warm_start = np.array([int(pt.t_true * warmup_frac) for pt in pts],
                          np.int32)
    place_s = 0.0
    if devices > 1:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        t1 = time.perf_counter()
        with maybe_span("stage.place"):
            # every leaf split over the mesh as the program takes it, and
            # waited for, so the call starts with its inputs in place
            params, inputs, t_true, warm_start = jax.block_until_ready(
                jax.device_put((params, inputs, t_true, warm_start),
                               NamedSharding(_mesh(devices), P("dev"))))
        place_s = time.perf_counter() - t1
    return _GroupData(params, inputs, t_true, warm_start,
                      host_trace_events=host_events,
                      prep_s=time.perf_counter() - t0, place_s=place_s)


# ---------------------------------------------------------------------------
# Compilation (vmap single-device / shard_map multi-device)
# ---------------------------------------------------------------------------

_EXEC_CACHE: Dict = {}


def _exec_key(cfg, S: int, N: int, t_pad: int, mode, *,
              pad_sets: Optional[int] = None, pad_ways: Optional[int] = None,
              trace_backend: str = "numpy", policies=None) -> Tuple:
    """The executable-cache key one group resolves to — a pure function
    of the plan (geometry-free shape + padded allocation + execution
    widths + policy compile tags), deterministic across processes."""
    from repro.policies import DEFAULT_POLICY_SET

    policies = policies or DEFAULT_POLICY_SET
    pad_sets = pad_sets or cfg.num_sets
    pad_ways = pad_ways or cfg.cache_ways
    return (cfg.geometry_free_shape(), pad_sets, pad_ways,
            S, N, t_pad, mode, trace_backend == "device",
            policies.compile_tags())


def group_cache_keys(plan: Plan, *, devices: Optional[int] = None,
                     trace_backend: Optional[str] = None) -> Tuple[Tuple, ...]:
    """The executable-cache key each group of ``plan`` would resolve to
    under :func:`execute` — WITHOUT compiling or executing anything.

    This is the planner-level warm/cold oracle: two groups (across plans,
    generations, or whole experiments) with equal keys share one compiled
    executable, so a caller batching repeated sweeps (``repro.search``)
    can predict — deterministically, before paying for the run — which
    proposals land on warm executables and which recompile.
    """
    import jax

    from repro.traces.backend import validate_backend

    backend = validate_backend(trace_backend or plan.trace_backend)
    D = len(jax.devices()) if devices is None else devices
    mode = ("shard", D) if D > 1 else "vmap"
    keys = []
    for g in plan.groups:
        rep = plan.points[g.indices[0]]
        keys.append(_exec_key(
            rep.cfg, len(_pad_systems(g.indices, g.s_pad, D)),
            g.key.num_nodes, g.t_pad, mode, pad_sets=g.pad_sets,
            pad_ways=g.pad_ways, trace_backend=backend,
            policies=rep.policy_set()))
    return tuple(keys)


def group_program(cfg, S: int, N: int, t_pad: int, *, pad_sets: int,
                  pad_ways: int, trace_backend: str, policies):
    """One group's runner, vmapped over its S systems, and the abstract
    arguments it is compiled for: ``(params, *inputs, t_true,
    warm_start)``. :func:`_compiled` jits it (sharded when ``mode`` asks);
    ``tests/test_tpu_compile.py`` compiles the same program for a
    described TPU."""
    import jax
    import jax.numpy as jnp

    i32 = jnp.int32
    if trace_backend == "device":
        from repro.traces.device import abstract_params, node_generator
        fn = build_masked_vmap(cfg, N, pad_sets, pad_ways,
                               trace_gen=node_generator(t_pad),
                               trace_key=("device", t_pad),
                               policies=policies)
        input_shapes = (abstract_params(S, N),)
    else:
        fn = build_masked_vmap(cfg, N, pad_sets, pad_ways,
                               policies=policies)
        input_shapes = (
            jax.ShapeDtypeStruct((S, N, t_pad), i32),
            jax.ShapeDtypeStruct((S, N, t_pad), jnp.float32))
    p_proto = FamParams.of(cfg, policies=policies)
    params_shape = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((S,) + jnp.shape(x), x.dtype),
        p_proto)
    return fn, (params_shape, *input_shapes,
                jax.ShapeDtypeStruct((S,), i32),
                jax.ShapeDtypeStruct((S,), i32))


def _compiled(cfg, S: int, N: int, t_pad: int, mode,
              info: Optional[RunInfo] = None, *,
              pad_sets: Optional[int] = None, pad_ways: Optional[int] = None,
              trace_backend: str = "numpy", policies=None):
    """AOT-compiled group runner. ``mode`` is ``"vmap"`` or
    ``("shard", D)``; ``pad_sets``/``pad_ways`` size the shared cache
    allocation (default: ``cfg``'s own geometry); compile time lands in
    ``info`` (zero when cached, counted by the ``exec_cache_hits`` /
    ``exec_cache_misses`` accounting). ``trace_backend="device"``
    compiles the in-graph trace generator into the executable (its
    signature takes TraceParams instead of staged arrays). ``policies``
    is the group's representative :class:`~repro.policies.PolicySet` —
    the cache keys on its compile tags (group members share them by
    construction), and it donates the policy numeric-param *schema* for
    the abstract shapes."""
    import jax

    from repro.policies import DEFAULT_POLICY_SET

    policies = policies or DEFAULT_POLICY_SET
    pad_sets = pad_sets or cfg.num_sets
    pad_ways = pad_ways or cfg.cache_ways
    key = _exec_key(cfg, S, N, t_pad, mode, pad_sets=pad_sets,
                    pad_ways=pad_ways, trace_backend=trace_backend,
                    policies=policies)
    if info is not None:
        if key in _EXEC_CACHE:
            info.exec_cache_hits += 1
        else:
            info.exec_cache_misses += 1
    if key not in _EXEC_CACHE:
        fn, arg_shapes = group_program(
            cfg, S, N, t_pad, pad_sets=pad_sets, pad_ways=pad_ways,
            trace_backend=trace_backend, policies=policies)
        if mode != "vmap":
            from jax.sharding import PartitionSpec as P

            from repro.parallel import compat
            _, D = mode
            fn = compat.shard_map(fn, mesh=_mesh(D), in_specs=P("dev"),
                                  out_specs=P("dev"))
        # every group executable is jitted under the canonical name
        # prefix so the runtime CompileWatcher (repro.analysis.runtime)
        # can count real group compiles in jax's log_compiles stream,
        # ignoring incidental prim jits (convert_element_type & co.);
        # the per-key digest suffix attributes each compile record to
        # its group (CompileWatcher.by_name)
        from repro.analysis.runtime import GROUP_RUNNER_NAME

        def famsim_group(*call_args):
            return fn(*call_args)
        famsim_group.__name__ = famsim_group.__qualname__ = \
            f"{GROUP_RUNNER_NAME}__{_key_digest(key)}"
        t0 = time.perf_counter()
        with maybe_span("compile", key_digest=_key_digest(key),
                        S=S, N=N, T_pad=t_pad):
            compiled = jax.jit(famsim_group).lower(*arg_shapes).compile()
        dt = time.perf_counter() - t0
        _EXEC_CACHE[key] = compiled
        if info is not None:
            info.compiles += 1
            info.compile_s += dt
    return _EXEC_CACHE[key]


def _run_group(data: _GroupData, compiled) -> Dict[str, np.ndarray]:
    import jax
    with maybe_span("device_call"):
        out = compiled(data.params, *data.inputs, data.t_true,
                       data.warm_start)
        out = jax.block_until_ready(out)
    # one EXPLICIT fetch after the synchronized call (bit-identical to
    # np.asarray per leaf, but stays legal under a device-to-host
    # transfer guard — the runtime sanitizer's "disallow" only targets
    # implicit transfers)
    with maybe_span("fetch"):
        return dict(jax.device_get(out))


def _pad_systems(idxs: Sequence[int], s_pad: int, D: int) -> List[int]:
    """Pad the group's point-index list to the canonical S width, then —
    when sharding — further up the canonical grid until the device count
    divides it. Device counts with a prime factor outside the canonical
    {4,5,6,7}*2^k grid (9, 11, 13, ...) never divide ANY canonical width,
    so the search is bounded and falls back to the plain next multiple of
    D. Padded lanes repeat the last member (inert; dropped on the way
    out)."""
    idxs = list(idxs)
    target = max(s_pad, len(idxs))
    D = max(D, 1)
    if target % D:
        cand = target
        for _ in range(8):                    # bounded: <= ~16x growth
            cand = s_bucket(cand + 1)
            if cand % D == 0:
                break
        else:
            cand = -(-target // D) * D        # no canonical width fits D
        target = cand
    return idxs + [idxs[-1]] * (target - len(idxs))


# ---------------------------------------------------------------------------
# The executor
# ---------------------------------------------------------------------------

def execute(plan: Plan, *, devices: Optional[int] = None,
            overlap: bool = True, warmup_frac: float = 0.2,
            cross_check_shard: bool = False,
            trace_backend: Optional[str] = None,
            assert_compiles: bool = False) -> ExperimentResult:
    """Run every point of ``plan``; one device call per compile group.

    devices: shard each group's S axis over this many devices (default:
        all visible). 1 uses the plain vmapped path.
    overlap: double-buffer host trace generation for group i+1 under the
        device simulation of group i (numpy backend only — the device
        backend's no-host fast path has nothing to overlap: its per-group
        host work is stacking a handful of scalars).
    cross_check_shard: re-run the first group through the *other* path
        (shard_map vs vmap) and record whether the metrics are bit-exact
        in ``info.shard_check``.
    trace_backend: override ``plan.trace_backend`` ("device"/"numpy").
    assert_compiles: run the group loop under the runtime sanitizer
        (``repro.analysis.runtime``): a ``jax.log_compiles`` watcher
        counts actual XLA compilations of group executables into
        ``info.xla_compiles`` and the loop executes under a
        device-to-host transfer guard; on exit, asserts
        ``xla_compiles == compiles <= planned_groups`` — i.e. every
        observed compile is an accounted planned-group compile (the
        planner's one-executable promise, proven at runtime; with a
        cold executable cache the chain is an equality).
    """
    from contextlib import ExitStack

    import jax

    from repro.traces.backend import validate_backend

    backend = validate_backend(trace_backend or plan.trace_backend)
    D = len(jax.devices()) if devices is None else devices
    dev = jax.devices()[0]
    info = RunInfo(planned_groups=plan.num_groups, devices=D,
                   platform=dev.platform, device_kind=dev.device_kind,
                   trace_backend=backend)

    exec_idxs = [_pad_systems(g.indices, g.s_pad, D) for g in plan.groups]
    mode = ("shard", D) if D > 1 else "vmap"

    # snapshot BEFORE any compile: which planned groups already have a
    # cached executable from an earlier execute (the warm-start set a
    # repeated sweep should drive to planned_groups)
    pre_warm, digests = [], []
    for gi, g in enumerate(plan.groups):
        rep = plan.points[g.indices[0]]
        key = _exec_key(rep.cfg, len(exec_idxs[gi]), g.key.num_nodes,
                        g.t_pad, mode, pad_sets=g.pad_sets,
                        pad_ways=g.pad_ways, trace_backend=backend,
                        policies=rep.policy_set())
        pre_warm.append(key in _EXEC_CACHE)
        digests.append(_key_digest(key))
    info.groups_reused = sum(pre_warm)

    results: List[Optional[Dict[str, np.ndarray]]] = [None] * plan.num_points
    pool = ThreadPoolExecutor(max_workers=1) if overlap and \
        backend == "numpy" and len(plan.groups) > 1 else None
    tracer = current_tracer()
    span_mark = tracer.mark() if tracer is not None else 0
    sentry = ExitStack()       # closes BEFORE the shard cross-check: its
    watcher = None             # deliberate extra compile is not a group run
    # the whole-execute span enters FIRST so it closes LAST (ExitStack is
    # LIFO) — every per-group span nests inside it
    sentry.enter_context(maybe_span(
        "execute", groups=plan.num_groups, points=plan.num_points,
        backend=backend, devices=D))
    if assert_compiles:
        from repro.analysis.runtime import (GROUP_RUNNER_NAME,
                                            CompileWatcher,
                                            no_implicit_transfers)
        watcher = sentry.enter_context(CompileWatcher())
        sentry.enter_context(no_implicit_transfers())
    try:
        # trace staging gets its own span whether it runs inline or on
        # the overlap worker (worker spans land on their own tid lane)
        def staged_prepare(gi_, t_pad_):
            with maybe_span("trace_stage", group=gi_):
                return _prepare(plan.points, exec_idxs[gi_], t_pad_,
                                warmup_frac, backend, devices=D)

        pending: Optional[Future] = None
        if pool is not None:
            pending = pool.submit(staged_prepare, 0, plan.groups[0].t_pad)
        group0_data = group0_out = None
        for gi, g in enumerate(plan.groups):
            if pool is not None:
                data = pending.result()
                if gi + 1 < len(plan.groups):
                    nxt = plan.groups[gi + 1]
                    pending = pool.submit(staged_prepare, gi + 1, nxt.t_pad)
            else:
                data = staged_prepare(gi, g.t_pad)
            keep_group0 = gi == 0 and cross_check_shard

            S_exec = len(exec_idxs[gi])
            N, t_pad = g.key.num_nodes, g.t_pad
            before = info.compiles
            before_s = info.compile_s
            rep = plan.points[g.indices[0]]
            xla_before = watcher.by_name if watcher is not None else {}
            compiled = _compiled(rep.cfg, S_exec, N,
                                 t_pad, mode, info,
                                 pad_sets=g.pad_sets, pad_ways=g.pad_ways,
                                 trace_backend=backend,
                                 policies=rep.policy_set())
            compile_s = info.compile_s - before_s
            t0 = time.perf_counter()
            with maybe_span("run", group=gi, key_digest=digests[gi],
                            S=S_exec, N=N, T_pad=t_pad):
                out = _run_group(data, compiled)
            run_s = time.perf_counter() - t0
            if keep_group0:
                group0_data, group0_out = data, out

            true_events = sum(len(plan.points[i].workloads) *
                              plan.points[i].t_true for i in g.indices)
            info.run_s += run_s
            info.systems += g.size
            info.events += true_events
            info.padded_events += S_exec * N * t_pad - true_events
            info.padded_systems += S_exec - g.size
            info.host_trace_events += data.host_trace_events
            info.trace_gen_s += data.prep_s
            info.place_s += data.place_s
            entry = {
                "static_shape": str(g.key.static_shape),
                "S": g.size, "S_exec": S_exec, "N": N, "T_pad": t_pad,
                "pad_sets": g.pad_sets, "pad_ways": g.pad_ways,
                "cache_storage": cache_storage(g.pad_sets, g.pad_ways),
                "compile_s": round(compile_s, 3), "run_s": round(run_s, 3),
                "fresh_compile": info.compiles > before,
                "exec_cache_hit": pre_warm[gi],
                "key_digest": digests[gi]}
            if watcher is not None:
                # XLA compiles attributed to THIS group by its digest-
                # suffixed runner name (CompileWatcher.by_name delta)
                runner = f"{GROUP_RUNNER_NAME}__{digests[gi]}"
                entry["xla_compiles"] = (
                    watcher.by_name.get(runner, 0)
                    - xla_before.get(runner, 0))
            info.groups.append(entry)
            for j, i in enumerate(g.indices):
                results[i] = {k: v[j] for k, v in out.items()}
    finally:
        sentry.close()
        if pool is not None:
            pool.shutdown(wait=False)

    if watcher is not None:
        info.xla_compiles = watcher.count
        assert info.xla_compiles == info.compiles <= info.planned_groups, (
            "runtime compile-count assertion failed: observed "
            f"{info.xla_compiles} XLA compile(s) of group executables, "
            f"accounted {info.compiles} fresh AOT compile(s), planned "
            f"{info.planned_groups} group(s) — an unplanned recompile "
            "means something traced leaked into a compile key (run "
            "python -m repro.analysis)", info.groups)

    if cross_check_shard and plan.groups:
        info.shard_check = _shard_cross_check(plan, group0_data, group0_out,
                                              exec_idxs[0], mode, backend)
    if tracer is not None:
        # summarized AFTER sentry.close() so the whole-execute span (and
        # any cross-check spans) are included
        info.spans = tracer.summary(since=span_mark)
    t_pads = [0] * plan.num_points
    for g in plan.groups:
        for i in g.indices:
            t_pads[i] = g.t_pad
    return ExperimentResult(plan.points, results, info,  # type: ignore[arg-type]
                            t_pads=t_pads)


def _shard_cross_check(plan: Plan, data: _GroupData,
                       primary_out: Dict[str, np.ndarray],
                       idxs: Sequence[int], primary_mode,
                       trace_backend: str) -> dict:
    """Compare the first group's (already computed) primary-path output
    against a run through the *other* path — shard_map vs vmap — bit-exact
    (the ROADMAP-mandated scale path must not change a single bit of any
    metric)."""
    import jax

    g = plan.groups[0]
    rep = plan.points[g.indices[0]]
    S_exec, N, t_pad = len(idxs), g.key.num_nodes, g.t_pad
    alt_mode = "vmap" if primary_mode != "vmap" else ("shard", 1)
    if primary_mode != "vmap":
        # the primary's inputs are committed to the mesh; the one-device
        # program takes host copies of them
        params, inputs, t_true, warm_start = jax.device_get(
            (data.params, data.inputs, data.t_true, data.warm_start))
        data = dataclasses.replace(data, params=params, inputs=inputs,
                                   t_true=t_true, warm_start=warm_start)
    alt = _run_group(data, _compiled(rep.cfg, S_exec, N, t_pad, alt_mode,
                                     pad_sets=g.pad_sets,
                                     pad_ways=g.pad_ways,
                                     trace_backend=trace_backend,
                                     policies=rep.policy_set()))
    bit_exact = all(np.array_equal(primary_out[k], alt[k])
                    for k in primary_out)
    return {"group": 0, "primary": str(primary_mode), "alt": str(alt_mode),
            "systems": S_exec, "bit_exact": bool(bit_exact)}
