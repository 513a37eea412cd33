"""Executor: host milliseconds per call of a grid, outside the device call.
Per call, its wall time less the ``device_call`` span of ``repro.obs``;
the mean over the window's calls."""


def read(run):
    vals = [c["wall_s"] - c["device_s"] for c in run["calls"]
            if c.get("device_s") is not None]
    return 1e3 * sum(vals) / len(vals) if vals else None
