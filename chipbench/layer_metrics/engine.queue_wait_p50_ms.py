"""Median time from when a measured request was due (or sent) to its
admission into a slot."""
from chipbench.harness.context import percentile


def read(run):
    waits = run.samples.get("queue_wait_s")
    return 1e3 * percentile(waits, 50) if waits else None
