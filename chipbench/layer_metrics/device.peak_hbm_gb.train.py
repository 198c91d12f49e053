"""Peak device memory in use on the fullest chip after the window."""


def read(run):
    peak = run.counters.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
