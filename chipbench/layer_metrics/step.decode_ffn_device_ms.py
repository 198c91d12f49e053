"""Device time of the FEED-FORWARD half of one decode step: the operations
billed to `mlp` or to a part under `moe` (`harness/trace_scopes.py`) inside
whole calls of the program `programs.decode`, self time, over those calls.
Minus `step.moe_decode_device_ms` / `step.routed_experts_decode_device_ms`
(the grouped products alone) it is the router, the sorts, the combine and
the shared expert."""
from chipbench.harness import trace_scopes


def read(run):
    return trace_scopes.part_ms(run, "decode", "mlp", "moe")
