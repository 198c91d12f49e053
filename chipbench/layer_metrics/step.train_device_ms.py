"""Device time of one call of the train-step program (median over the
traced calls), from the trace's 'XLA Modules' line. The program is found
by the name pattern in the cell's `programs.train_step`."""


def read(run):
    pattern = run.cell.shape.get("programs", {}).get("train_step")
    if run.trace is None or not pattern:
        return None
    return run.trace.median_call_ms(pattern)
