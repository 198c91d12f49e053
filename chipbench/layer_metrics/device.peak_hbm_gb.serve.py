"""Peak device memory in use after the window, before the reference ran."""


def read(run):
    peak = run.counters.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
