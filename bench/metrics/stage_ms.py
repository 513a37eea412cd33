"""Executor: host milliseconds per call staging the group's inputs
(``executor._prepare``, the body of the ``trace_stage`` span of
``repro.obs``: trace encodings, per-system ``FamParams``, stacking), read
from ``RunInfo.trace_gen_s``; the mean over the window's calls other than
call 2, whose staging the traced slice of the window covers (the
profiler's Python tracer slows the host there)."""

#: the call whose staging lies inside the traced slice (run.py traces the
#: boundary of calls 1 and 2)
TRACED_CALL = 2


def read(run):
    vals = [c["info"]["trace_gen_s"] for c in run["calls"]
            if c["index"] != TRACED_CALL and "trace_gen_s" in c["info"]]
    return 1e3 * sum(vals) / len(vals) if vals else None
