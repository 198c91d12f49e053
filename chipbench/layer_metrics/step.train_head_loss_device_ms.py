"""Device time of the HEAD AND ITS LOSS in one train step, forward and
backward: the operations billed to `head` or `loss` (the backward's carry
their forward's scope inside `transpose(jvp(...))`;
`harness/trace_scopes.py`) inside whole calls of the program
`programs.train_step`, self time, over those calls."""
from chipbench.harness import trace_scopes


def read(run):
    return trace_scopes.part_ms(run, "train_step", "head", "loss")
