"""Group program: microseconds per scan step of the group executable.
The ``device_call`` span (host clock around ``block_until_ready``) over
the group's executed trace length ``T_pad``; the mean over the window's
calls."""


def read(run):
    vals = [c["device_s"] / c["info"]["groups"][0]["T_pad"]
            for c in run["calls"]
            if c.get("device_s") is not None and c["info"]["groups"]]
    return 1e6 * sum(vals) / len(vals) if vals else None
