"""Host span tracing: a dependency-free Chrome/Perfetto trace emitter.

One :class:`SpanTracer` records complete ("ph": "X") trace events with
microsecond timestamps relative to its creation; :meth:`SpanTracer.save`
writes the standard Chrome trace-event JSON object format, loadable in
``chrome://tracing`` or https://ui.perfetto.dev (docs/observability.md
has the how-to).

Instrumented code never talks to a tracer directly — it calls
:func:`maybe_span`, which records to a tracer only when one has been
installed with :func:`set_tracer`. Every span also opens a
``jax.profiler.TraceAnnotation`` of its name, so any ``jax.profiler``
capture carries the program's spans on the profiler's own clock, beside
the device ops (about 0.6 us a span on a TPU v5e host when no profiler
runs). The executor instruments plan -> per-group trace staging
(``stage.*``) -> compile -> run -> fetch this way,
``repro.search`` wraps its generations, and ``benchmarks.bench_famsim``
its repeats — so ``benchmarks.run --telemetry`` (or any caller that
installs a tracer) gets one nested timeline of the whole run for free.

Spans emitted from worker threads (the executor's trace-staging overlap
pool) get their own ``tid`` lane, so nesting stays well-formed per
thread. Wall-clock measurement is this module's *job*; it is therefore
deliberately outside the analyzer's deterministic scope (like
``experiments/executor.py`` — see ``repro.analysis.scopes``), and
instrumented modules that ARE in scope only ever import these APIs.
"""
from __future__ import annotations

import json
import threading
import time
from contextlib import contextmanager, nullcontext
from functools import lru_cache
from pathlib import Path
from typing import Callable, Dict, Iterator, List, Optional, Tuple

__all__ = ["SpanTracer", "set_tracer", "current_tracer", "maybe_span"]


def _jsonable(args: Dict) -> Dict:
    out = {}
    for k, v in args.items():
        if isinstance(v, (str, int, float, bool)) or v is None:
            out[k] = v
        else:
            out[k] = str(v)
    return out


class SpanTracer:
    """Record spans/instants and emit Chrome trace-event JSON.

    Besides the trace events, every closed span is kept in :attr:`spans`
    as ``(name, start, end)`` on the host's absolute ``perf_counter``
    clock, and each of :attr:`listeners` is called as
    ``listener(name, "start" | "end", perf_counter time)`` when a span
    opens and closes, from the thread that runs the span."""

    def __init__(self, process_name: str = "repro"):
        self.process_name = process_name
        self.events: List[dict] = []
        self.spans: List[Tuple[str, float, float]] = []
        self.listeners: List[Callable[[str, str, float], None]] = []
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._tids: Dict[int, int] = {}

    # -- recording ---------------------------------------------------------

    def _us(self, t: float) -> float:
        return (t - self._t0) * 1e6

    def _now_us(self) -> float:
        return self._us(time.perf_counter())

    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            return self._tids.setdefault(ident, len(self._tids))

    @contextmanager
    def span(self, name: str, cat: str = "host", **args) -> Iterator[None]:
        """Record the enclosed block as one complete ("X") event."""
        t0 = time.perf_counter()
        for listen in self.listeners:
            listen(name, "start", t0)
        try:
            yield
        finally:
            t1 = time.perf_counter()
            ev = {"name": name, "cat": cat, "ph": "X",
                  "ts": round(self._us(t0), 1),
                  "dur": round(max(self._us(t1) - self._us(t0), 0.0), 1),
                  "pid": 0, "tid": self._tid()}
            if args:
                ev["args"] = _jsonable(args)
            with self._lock:
                self.events.append(ev)
                self.spans.append((name, t0, t1))
            for listen in self.listeners:
                listen(name, "end", t1)

    def instant(self, name: str, cat: str = "host", **args) -> None:
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": round(self._now_us(), 1), "pid": 0, "tid": self._tid()}
        if args:
            ev["args"] = _jsonable(args)
        with self._lock:
            self.events.append(ev)

    # -- summarizing / emitting -------------------------------------------

    def mark(self) -> int:
        """Bookmark into the event list (for windowed :meth:`summary`)."""
        with self._lock:
            return len(self.events)

    def summary(self, since: int = 0) -> Dict[str, dict]:
        """``{span name: {count, total_s}}`` over events recorded after
        ``since`` (a :meth:`mark`) — the compact form ``RunInfo.spans``
        and the search timings sidecar carry."""
        out: Dict[str, dict] = {}
        with self._lock:
            events = list(self.events[since:])
        for ev in events:
            if ev.get("ph") != "X":
                continue
            s = out.setdefault(ev["name"], {"count": 0, "total_s": 0.0})
            s["count"] += 1
            s["total_s"] += ev["dur"] / 1e6
        return {k: {"count": v["count"], "total_s": round(v["total_s"], 4)}
                for k, v in sorted(out.items())}

    def chrome_trace(self) -> dict:
        """The Chrome trace-event JSON *object format* payload."""
        meta = [{"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
                 "args": {"name": self.process_name}}]
        with self._lock:
            events = list(self.events)
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def save(self, path) -> Path:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.chrome_trace(), indent=1))
        return path


# -- process-global current tracer ------------------------------------------

_CURRENT: Optional[SpanTracer] = None


def set_tracer(tracer: Optional[SpanTracer]) -> Optional[SpanTracer]:
    """Install ``tracer`` as the process-global target of
    :func:`maybe_span`; returns the previous one (restore it when done)."""
    global _CURRENT
    prev = _CURRENT
    _CURRENT = tracer
    return prev


def current_tracer() -> Optional[SpanTracer]:
    return _CURRENT


@lru_cache(maxsize=None)
def _annotation():
    """``jax.profiler.TraceAnnotation``, or None where jax is missing."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:          # this module imports without jax
        return None
    return TraceAnnotation


@contextmanager
def maybe_span(name: str, cat: str = "host",
               **args) -> Iterator[Optional[SpanTracer]]:
    """A ``jax.profiler`` annotation of ``name`` around the block, and a
    span of the current tracer when one is installed (yields it, or
    None)."""
    annotate = _annotation()
    tracer = _CURRENT
    with annotate(name) if annotate is not None else nullcontext():
        if tracer is None:
            yield None
            return
        with tracer.span(name, cat=cat, **args):
            yield tracer
