"""Device time of one call of the engine's decode program (median over the
traced calls), from the trace's 'XLA Modules' line; the program is found
by the name pattern in the cell's `programs.decode`."""


def read(run):
    pattern = run.cell.shape.get("programs", {}).get("decode")
    if run.trace is None or not pattern:
        return None
    return run.trace.median_call_ms(pattern)
