"""Device time of CLIPPING AND THE OPTIMIZER'S UPDATE in one train step: the
operations billed to `optimizer` (`harness/trace_scopes.py`) inside whole
calls of the program `programs.train_step`, self time, over those calls."""
from chipbench.harness import trace_scopes


def read(run):
    return trace_scopes.part_ms(run, "train_step", "optimizer")
