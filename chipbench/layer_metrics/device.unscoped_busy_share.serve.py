"""Share of the engine programs' device time that NO part of the model
claims: self time of the operations inside whole calls of
`programs.prefill` and `programs.decode` whose `op_name` holds no known
part (`harness/trace_scopes.py` logs the largest), over those calls' busy
time. The gauge of the by-part metrics: what reads here is missing there."""
from chipbench.harness import trace_scopes


def read(run):
    return trace_scopes.unscoped_share(run, "prefill", "decode")
