"""Read a ``jax.profiler`` capture by the program's own names.

The program names its in-graph phases with ``jax.named_scope`` (the
names in :data:`SCOPES`, docs/observability.md) and opens a
``jax.profiler.TraceAnnotation`` for each of its ``repro.obs`` host spans
(the names in :data:`SPANS`). So a capture holds both on the profiler's
own clock:

* :func:`host_spans` reads the host spans from the trace's host planes,
  as ``(name, start_s, end_s)`` on the trace's clock:
  ``profile_reduce.reduce(pd, host_spans(pd), 0, ...)`` names each idle
  gap by the innermost span open in it (``stage.params``, not just
  ``trace_stage``);
* :func:`scopes` sums the device ops' self time by top-level scope, each
  op mapped to its scope through the ``op_name`` metadata of the
  optimized HLO text of the executable that ran
  (``compiled.as_text()``), and counts the scan iterations the slice
  holds.

Run as a script, it prints one capture's time by scope::

    python bench/profile_scopes.py --trace <logdir> --hlo <hlo.txt>
"""
from __future__ import annotations

import argparse
import re
import statistics
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

import profile_reduce

#: the program's named scopes, outermost first where they nest
#: (``phase_a`` holds ``cache_lookup`` and ``prefetcher``, ``phase_c``
#: holds ``cache_fill``)
SCOPES = ("trace_gen", "phase_a", "cache_lookup", "prefetcher", "sched",
          "phase_c", "cache_fill", "telemetry", "metrics")
#: the top-level scopes of one scan step
STEP_SCOPES = ("phase_a", "sched", "phase_c", "telemetry")
#: the program's ``repro.obs`` span names
SPANS = ("generation", "plan", "execute", "trace_stage", "stage.traces",
         "stage.params", "stage.stack", "compile", "run", "device_call",
         "fetch", "repeat")
UNSCOPED = "unscoped"

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?'
                    r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"',
                    re.MULTILINE)
_DEF = re.compile(r'^\s+(?:ROOT\s+)?%?([\w.\-]+) = ')
_COMP = re.compile(r'^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$')
_WHILE = re.compile(r' while\(.*?condition=%?([\w.\-]+), body=%?([\w.\-]+)')
_REF = re.compile(r'%([\w.\-]+)')


def host_spans(pd, names: Sequence[str] = SPANS
               ) -> List[Tuple[str, float, float]]:
    """``(name, start_s, end_s)`` of every host event named in ``names``,
    on the trace's clock, from every host plane and thread."""
    keep = set(names)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in keep:
                    s = float(ev.start_ns)
                    out.append((ev.name, s / 1e9,
                                (s + float(ev.duration_ns)) / 1e9))
    return sorted(out, key=lambda x: x[1])


def _parts(op_name: str) -> List[str]:
    """An ``op_name``'s components; a scope under ``jax.vmap`` reads
    ``vmap(name)`` there and is unwrapped."""
    out = []
    for part in op_name.split("/"):
        while part.startswith("vmap(") and part.endswith(")"):
            part = part[5:-1]
        out.append(part)
    return out


def scope_path(op_name: str) -> Tuple[str, ...]:
    """The program's scopes in an op's ``op_name``, outermost first."""
    return tuple(p for p in _parts(op_name) if p in SCOPES)


def _top(op_name: str) -> str:
    """An op's top-level scope, or ``unscoped``."""
    path = scope_path(op_name)
    return path[0] if path else UNSCOPED


def _step_op(op_name: str) -> bool:
    """In a scope of the scan step, and in no loop of its own: such an op
    runs once a step."""
    parts = _parts(op_name)
    top = next((i for i, p in enumerate(parts) if p in SCOPES), None)
    return top is not None and parts[top] in STEP_SCOPES and \
        "while" not in parts[top:]


def op_names(hlo_text: str) -> Dict[str, str]:
    """{HLO instruction name: its ``op_name`` metadata} of an optimized
    HLO module's text."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(hlo_text)}


def scan_ops(hlo_text: str, names: Dict[str, str]) -> Set[str]:
    """The instructions the scan runs: each ``while`` whose body (with
    the computations it calls) holds an op of the step's scopes, and every
    instruction of those computations, scoped or not (the compiler's
    copies carry no metadata)."""
    comps: Dict[str, List[Tuple[str, str]]] = {}
    cur = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            m = _COMP.match(line)
            cur = m.group(1) if m else None
            if cur is not None:
                comps[cur] = []
        elif cur is not None:
            m = _DEF.match(line)
            if m:
                comps[cur].append((m.group(1), line))

    def run_by(roots) -> Set[str]:
        seen, todo = set(), list(roots)
        while todo:
            c = todo.pop()
            if c in seen or c not in comps:
                continue
            seen.add(c)
            for _, line in comps[c]:
                todo.extend(_REF.findall(line))
        return {op for c in seen for op, _ in comps[c]}

    out: Set[str] = set()
    for instrs in comps.values():
        for op, line in instrs:
            m = _WHILE.search(line)
            if m is None:
                continue
            ops = run_by(m.groups())
            if any(_top(names.get(o, "")) in STEP_SCOPES for o in ops):
                out |= ops | {op}
    return out


def label(op: str, names: Dict[str, str]) -> str:
    """An op's name prefixed by its innermost scope path
    (``phase_a/cache_lookup/fusion.12``); bare when it is in none."""
    path = scope_path(names.get(op, ""))
    return "/".join(path + (op,))


def scopes(pd, hlo_text: str, lo: float, hi: float, plane_prefix: str,
           op_lines: Sequence[str]) -> Optional[dict]:
    """Device self time in [lo, hi] (ns, the trace's clock) by top-level
    scope, in seconds averaged over the devices.

    ``unscoped`` holds the ops in no scope, ``unscoped_body`` the part of
    them that the scan runs (:func:`scan_ops`: its loop, copies and
    bookkeeping). ``iterations`` is the number of scan steps in the
    slice: the median count of the events of the step's ops (those in a
    step scope and in no loop of their own). None when the executable
    carries none of the scopes."""
    ops = profile_reduce.device_ops(pd, plane_prefix, op_lines)
    names = op_names(hlo_text)
    if not ops or not any(scope_path(n) for n in names.values()):
        return None
    in_scan = scan_ops(hlo_text, names)
    by: Dict[str, float] = {}
    body_unscoped = 0.0
    counts: Dict[str, int] = {}
    for evs in ops.values():
        for op, d in profile_reduce.self_times(evs, lo, hi).items():
            top = _top(names.get(op, ""))
            by[top] = by.get(top, 0.0) + d
            if top == UNSCOPED and op in in_scan:
                body_unscoped += d
        for s, _, op in evs:
            if lo <= s < hi:
                counts[op] = counts.get(op, 0) + 1
    step_ops = [counts[op] for op, meta in names.items()
                if op in counts and _step_op(meta)]
    n = len(ops)
    out = {k: v / n / 1e9 for k, v in sorted(by.items())}
    out.setdefault(UNSCOPED, 0.0)
    out["unscoped_body"] = body_unscoped / n / 1e9
    out["iterations"] = (int(statistics.median(step_ops)) // n
                         if step_ops else 0)
    return out


def main(argv=None) -> int:
    import glob
    import json

    from jax.profiler import ProfileData
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace", required=True,
                    help="a jax.profiler log directory or .xplane.pb file")
    ap.add_argument("--hlo", required=True,
                    help="the optimized HLO text of the executable that ran")
    ap.add_argument("--plane-prefix", default="/device:TPU:")
    ap.add_argument("--op-line", default="XLA Ops")
    args = ap.parse_args(argv)
    path = args.trace
    if not path.endswith(".pb"):
        path = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)[0]
    pd = ProfileData.from_file(path)
    ops = profile_reduce.device_ops(pd, args.plane_prefix, [args.op_line])
    every = [t for evs in ops.values() for s, e, _ in evs for t in (s, e)]
    out = scopes(pd, Path(args.hlo).read_text(), min(every), max(every),
                 args.plane_prefix, [args.op_line])
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
