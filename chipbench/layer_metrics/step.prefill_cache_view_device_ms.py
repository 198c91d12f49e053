"""Device time of a prefill chunk's CACHE traffic: the operations billed to a
part under `cache` (the slot's pages gathered into a view, the views sliced
a layer and stacked again, the chunk's rows written into a view and back to
their pages; `harness/trace_scopes.py`) inside whole calls of the program
`programs.prefill`, self time, over those calls."""
from chipbench.harness import trace_scopes


def read(run):
    return trace_scopes.part_ms(run, "prefill", "cache")
