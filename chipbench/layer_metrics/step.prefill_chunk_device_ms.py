"""Device time of one call of the engine's prefill program (median over the
traced calls), from the trace's 'XLA Modules' line; the program is found
by the name pattern in the cell's `programs.prefill`."""


def read(run):
    pattern = run.cell.shape.get("programs", {}).get("prefill")
    if run.trace is None or not pattern:
        return None
    return run.trace.median_call_ms(pattern)
