"""Host time of one `train_step` call (median over the window's steps):
what the Python side of a step costs, which the device never sees while
dispatch runs ahead of it."""
from statistics import median


def read(run):
    samples = run.samples.get("dispatch_s")
    return 1e6 * median(samples) if samples else None
