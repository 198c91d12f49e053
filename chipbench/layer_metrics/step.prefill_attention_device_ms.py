"""Device time of ATTENTION in one prefill chunk: the operations billed to a
part under `attn` (projections, an indexer's scores, a selection, the
attention itself, the output projection; `harness/trace_scopes.py`) that
start inside a whole call of the program `programs.prefill`, self time,
over those calls. Keyed by what the operations are for, not by a kernel's
name: it survives the kernel beneath it being replaced."""
from chipbench.harness import trace_scopes


def read(run):
    return trace_scopes.part_ms(run, "prefill", "attn")
