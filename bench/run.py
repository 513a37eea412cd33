"""Chip benchmark of the pooled-memory simulator: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/``) and a traffic file (``bench/traffic/``).
The run drives the program's experiment path in a closed loop: each call
expands the traffic file into a grid of simulated systems with new traces
(:func:`grid.expansion`: :mod:`grid`'s own generator, or the module the
traffic file names under ``expand``), plans it and executes it with
``repro.experiments.execute``.

* Set-up: process start, the compile of the cell's one group from the
  persistent compilation cache (``JAX_COMPILATION_CACHE_DIR``, else
  ``.jax_cache/`` in the checkout) and one warm call.
* Window: calls are started until ``--seconds`` have passed; the rates are
  taken over the span of the calls made. XLA compiles inside the window
  are counted (there should be none).
* ``--trace 1``: ``repro.obs`` spans are recorded around every layer and a
  ``jax.profiler`` trace (host spans and device ops, no Python tracer) is
  taken of one call boundary of the window; the per-layer metrics are read
  by the files in ``bench/metrics/``.
* Afterwards a sample of the window's systems is run through the plain
  reference that the configuration names under ``reference`` (a module in
  ``bench/``: ``METRICS`` and ``simulate(system, dtype)``) and each
  compared number is held to its limit in ``bench/limits/<cell>.json``
  (:mod:`check`).

Fails, with no result line, unless JAX finds a TPU of a kind listed in
``bench/chips.json`` and as many chips as the cell asks for. The last line
of standard output is the result as one JSON object.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from contextlib import contextmanager, nullcontext  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import check  # noqa: E402
import grid  # noqa: E402

#: device time traced on each side of the traced call boundary
TRACE_CONTEXT_S = 1.0
LOWERING_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",)


class RunError(RuntimeError):
    """The run cannot give a result (no chip, unknown cell, ...)."""


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_cell(name: str) -> dict:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    bench = grid.load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise RunError(f"no cell {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}

    def mine(m):
        return name in m.get("workloads", [name])

    return {
        "cell": cell,
        "config": grid.load_json(ROOT / configs[cell["config"]]["file"]),
        "traffic": grid.load_json(BENCH / "traffic" /
                                  f"{cell['traffic']}.json"),
        "limits": grid.load_json(BENCH / "limits" / f"{name}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def reader(metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    return grid.bench_module(f"metrics/{metric}.py").read


def reference(config: dict):
    """The plain reference module the configuration names (``reference``:
    a file in ``bench/``)."""
    return grid.bench_module(config["reference"])


def chip_facts(devices, chips: int, require_tpu: bool) -> dict:
    kinds = grid.load_json(BENCH / "chips.json")["kinds"]
    dev = devices[0]
    if not require_tpu:
        return next(iter(kinds.values()))
    if dev.platform != "tpu" or len(devices) < chips:
        raise RunError(f"needs {chips} TPU chip(s); JAX found "
                       f"{len(devices)} {dev.platform} device(s)")
    if dev.device_kind not in kinds:
        raise RunError(f"unknown device kind {dev.device_kind!r}; "
                       f"bench/chips.json knows {sorted(kinds)}")
    return kinds[dev.device_kind]


class CompileCounter:
    """Counts programs lowered while ``active`` (jax.monitoring events)."""

    def __init__(self):
        self.active = False
        self.count = 0

    def __call__(self, event, duration, **kw):
        if self.active and event in LOWERING_EVENTS:
            self.count += 1


def span_tracer():
    """A ``repro.obs`` tracer that keeps every span as (name, start, end)
    on the host's perf_counter clock and calls hooks as spans open and
    close."""
    from repro.obs.spans import SpanTracer

    class Tracer(SpanTracer):
        def __init__(self):
            super().__init__("bench")
            self.spans = []
            self.hooks = []
            self._spans_lock = threading.Lock()

        @contextmanager
        def span(self, name, cat="host", **args):
            t0 = time.perf_counter()
            for h in self.hooks:
                h(name, "start", t0)
            try:
                yield
            finally:
                t1 = time.perf_counter()
                with self._spans_lock:
                    self.spans.append((name, t0, t1))
                for h in self.hooks:
                    h(name, "end", t1)

    return Tracer()


class BoundaryTrace:
    """A profiler trace of one call boundary of the window: from
    ``context`` seconds before call ``k``'s device work is expected to end
    to ``context`` seconds into call ``k + 1``'s (or its end)."""

    def __init__(self, tracer, k: int, expect_s: float, context: float):
        self.k, self.expect, self.ctx = k, expect_s, context
        self.logdir = tempfile.mkdtemp(prefix="bench_trace_")
        self.starts, self.ends = {}, {}
        self.calls = 0
        self.done = threading.Event()
        self.t_begin = None
        self.error = None
        tracer.hooks.append(self._hook)
        self.thread = threading.Thread(target=self._work, daemon=True)
        self.thread.start()

    def _hook(self, name, what, t):
        if name != "device_call":
            return
        if what == "start":
            self.calls += 1
            self.starts[self.calls] = t
        else:
            self.ends[self.calls] = t

    def _wait(self, cond, until=None):
        while not cond() and not self.done.is_set():
            if until is not None and time.perf_counter() >= until:
                return
            time.sleep(0.002)

    def _work(self):
        import jax
        try:
            k = self.k
            self._wait(lambda: k in self.starts)
            if k not in self.starts:
                return
            t = self.starts[k] + max(self.expect - self.ctx, 0.0)
            self._wait(lambda: k in self.ends, until=t)
            # no Python tracer: it slows the host work in the slice, where
            # the idle gaps and host_ms are read
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.logdir, profiler_options=opts)
            self.t_begin = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench_slice_begin"):
                pass
            self._wait(lambda: k + 1 in self.starts)
            if k + 1 in self.starts:
                self._wait(lambda: k + 1 in self.ends,
                           until=self.starts[k + 1] + self.ctx)
            with jax.profiler.TraceAnnotation("bench_slice_end"):
                pass
            jax.profiler.stop_trace()
        except Exception as e:  # reported by reduce(); the run goes on
            self.error = e

    def reduce(self, spans, facts) -> dict:
        from jax.profiler import ProfileData

        import profile_reduce
        self.done.set()
        self.thread.join()
        try:
            if self.error is not None:
                raise RunError(f"trace failed: {self.error!r}")
            files = glob.glob(os.path.join(self.logdir, "**",
                                           "*.xplane.pb"), recursive=True)
            if not files or self.t_begin is None:
                raise RunError("no trace was written")
            pd = ProfileData.from_file(files[0])
            begin, _ = profile_reduce.slice_bounds(pd)
            offset = begin - self.t_begin * 1e9
            return profile_reduce.reduce(
                pd, spans, offset, facts["trace_plane_prefix"],
                facts["trace_op_lines"])
        finally:
            shutil.rmtree(self.logdir, ignore_errors=True)


def use_compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def run(args, require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result object."""
    spec = load_cell(args.workload)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    chips = int(cell["chips"])

    import jax

    devices = jax.devices()
    facts = chip_facts(devices, chips, require_tpu)
    say(f"device: platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)}")
    say(f"compile cache: {use_compile_cache()}")

    from repro.experiments import execute
    from repro.experiments import executor as ex
    from repro.obs.spans import set_tracer

    tracer = span_tracer() if args.trace else None
    if tracer is not None:
        set_tracer(tracer)
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    seed = int(args.seed)
    expand_systems, to_experiment = grid.expansion(traffic)

    def one_call(i: int) -> dict:
        sp = tracer.span if tracer is not None else \
            (lambda name: nullcontext())
        t0 = time.perf_counter()
        with sp("proposals"):
            systems = expand_systems(traffic, config, seed, i)
        with sp("plan"):
            plan = to_experiment(systems, config, cell["name"]).plan()
        res = execute(plan, devices=chips,
                      warmup_frac=traffic["warmup_frac"])
        t1 = time.perf_counter()
        rec = {"index": i, "start": t0, "end": t1, "wall_s": t1 - t0,
               "info": res.info.as_dict(), "systems": systems,
               "metrics": res.metrics, "plan": plan}
        if tracer is not None:
            rec["device_s"] = sum(e - s for n, s, e in tracer.spans
                                  if n == "device_call" and s >= t0)
        return rec

    warm = one_call(0)
    setup_s = time.perf_counter() - _T0
    say(f"setup: {setup_s:.3f} s (warm call {warm['wall_s']:.3f} s, "
        f"groups {warm['info']['planned_groups']}, "
        f"compile_s {warm['info']['compile_s']})")

    trace = None
    if tracer is not None:
        warm_dev = [e - s for n, s, e in tracer.spans if n == "device_call"]
        trace = BoundaryTrace(tracer, 1, warm_dev[-1], TRACE_CONTEXT_S)
    calls = []
    counter.active = True
    t_start = time.perf_counter()
    while time.perf_counter() - t_start < args.seconds:
        calls.append(one_call(len(calls) + 1))
        say(f"call {calls[-1]['index']}: {calls[-1]['wall_s']:.4f} s")
    t_end = calls[-1]["end"]
    counter.active = False
    window_s = t_end - t_start
    say(f"window: {len(calls)} calls in {window_s:.4f} s; "
        f"programs lowered in the window: {counter.count}")

    used = devices[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    run_rec = {"calls": calls, "window_s": window_s, "trace": None,
               "executable": None}
    if trace is not None:
        run_rec["trace"] = trace.reduce(tracer.spans, facts)
        set_tracer(None)
    keys = ex.group_cache_keys(calls[0]["plan"], devices=chips)
    run_rec["executable"] = ex._EXEC_CACHE.get(keys[0]) if keys else None

    events = sum(c["info"]["events"] for c in calls)
    walls = [c["wall_s"] for c in calls]
    e2e = {"events_per_s": events / window_s / chips,
           "setup_s": setup_s,
           "gen_p95_s": float(np.percentile(walls, 95))}
    say(f"calls: {len(calls)}; call wall s: min {min(walls):.4f} "
        f"median {float(np.median(walls)):.4f} max {max(walls):.4f}")
    if args.trace:
        metrics = {}
        for m in spec["per_layer"]:
            v = reader(m["name"])(run_rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}

    # the output check: a sample of the window's systems, after the window
    t_ref = time.perf_counter()
    ref = reference(config)
    chk = traffic["check"]
    picked = check.sample(seed, [(c["index"], c["systems"]) for c in calls],
                          chk["points"], chk["stratify"])
    by_index = {c["index"]: c for c in calls}
    prog = [by_index[c]["metrics"][i] for c, i in picked]
    refs = [ref.simulate(by_index[c]["systems"][i]) for c, i in picked]
    numbers = check.gaps(prog, refs, list(spec["limits"]["numbers"]))
    ok, table = check.judge(numbers, spec["limits"]["numbers"])
    say(f"reference: {len(picked)} systems in "
        f"{time.perf_counter() - t_ref:.2f} s: "
        + ", ".join(str(by_index[c]["systems"][i]["coords"])
                    for c, i in picked))
    attempted = sum(len(c["systems"]) for c in calls)
    failed = sum(1 for c in calls for m in c["metrics"]
                 if m is None or not all(np.all(np.isfinite(np.asarray(v)))
                                         for v in m.values()))

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(ok and failed == 0), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if run_rec["trace"] is not None:
        tr = run_rec["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
        say(f"traced slice: {tr['window_s']:.4f} s, busy "
            f"{tr['busy_s']:.4f} s, idle by host span {tr['idle_by_span']}")
    out["check"] = table
    for name, row in table.items():
        say(f"check {name}: {row['value']!r} limit {row['limit']!r}")
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        out = run(args)
    except RunError as e:
        say(f"bench: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
