"""Programs compiled (or fetched from the compile cache) between the
window's two fences; anything but 0 also makes the run not correct."""


def read(run):
    return run.counters.get("recompiles")
