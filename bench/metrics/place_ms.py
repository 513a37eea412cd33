"""Executor, sharded staging: host milliseconds per call placing the
group's inputs on the mesh (span ``stage.place`` of ``repro.obs``: the
stacked ``FamParams``, the trace parameters, ``t_true`` and
``warm_start``, each split over the devices straight from the host), read
from ``RunInfo.place_s``; the mean over the window's calls other than call
2, whose staging the traced slice of the window covers. None where the
program does not report the placement."""

#: the call whose staging lies inside the traced slice (run.py traces the
#: boundary of calls 1 and 2)
TRACED_CALL = 2


def read(run):
    vals = [c["info"]["place_s"] for c in run["calls"]
            if c["index"] != TRACED_CALL and "place_s" in c["info"]]
    return 1e3 * sum(vals) / len(vals) if vals else None
