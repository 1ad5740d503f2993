"""Peak bytes on the fullest device of the cell after the window, in GB (1e9
bytes): the larger of ``peak_bytes_in_use`` (live arrays) and ``bytes_in_use +
bytes_reserved`` (arrays plus the loaded step's temporaries); see
``benchmark/run.py:memory_peak``."""


def read(run):
    peak = run["memory_peak_bytes"]
    return peak / 1e9 if peak else None
