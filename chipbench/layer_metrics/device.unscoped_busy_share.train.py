"""Share of the train step's device time that NO part of the model claims:
self time of the operations inside whole calls of `programs.train_step`
whose `op_name` holds no known part (`harness/trace_scopes.py` logs the
largest), over those calls' busy time. The gauge of the by-part metrics."""
from chipbench.harness import trace_scopes


def read(run):
    return trace_scopes.unscoped_share(run, "train_step")
