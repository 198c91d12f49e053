"""Device time of the FEED-FORWARD half of one prefill chunk: the operations
billed to `mlp` or to a part under `moe` (router, sorts, the experts'
grouped products whatever kernel runs them, combine, shared expert;
`harness/trace_scopes.py`) inside whole calls of the program
`programs.prefill`, self time, over those calls."""
from chipbench.harness import trace_scopes


def read(run):
    return trace_scopes.part_ms(run, "prefill", "mlp", "moe")
