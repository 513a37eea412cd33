"""Group program: temporary bytes per device of the cell's compiled group
executable (``memory_analysis().temp_size_in_bytes``), in MB."""


def read(run):
    exe = run.get("executable")
    if exe is None:
        return None
    try:
        ma = exe.memory_analysis()
    except Exception:  # the backend may not analyse this executable
        return None
    temp = getattr(ma, "temp_size_in_bytes", None)
    return None if temp is None else temp / 1e6
