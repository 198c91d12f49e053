"""Mean host time a step waited in `next(loader)`: above zero, the device
finished before its next batch was ready."""


def read(run):
    samples = run.samples.get("stall_s")
    return 1e3 * sum(samples) / len(samples) if samples else None
