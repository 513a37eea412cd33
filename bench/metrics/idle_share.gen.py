"""Device: idle share of the traced slice of the window of search generations, 1 - (union of
device op intervals) / slice length, from the ``jax.profiler`` trace."""


def read(run):
    tr = run.get("trace")
    return None if tr is None else tr["idle_share"]
