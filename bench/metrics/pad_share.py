"""Planner: share of the executed events that padding adds (systems
repeated up to the canonical width, steps past a system's true length).
From ``RunInfo.padded_events`` and ``RunInfo.events`` of the window's
calls."""


def read(run):
    pad = sum(c["info"]["padded_events"] for c in run["calls"])
    true = sum(c["info"]["events"] for c in run["calls"])
    if pad + true <= 0:
        return None
    return 100.0 * pad / (pad + true)
