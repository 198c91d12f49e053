"""Device time of ATTENTION and the CACHE in one decode step: the operations
billed to a part under `attn` or `cache` (projections, indexer, selection,
the paged kernels, the output projection, the new rows' write to their
pages; `harness/trace_scopes.py`) inside whole calls of the program
`programs.decode`, self time, over those calls."""
from chipbench.harness import trace_scopes


def read(run):
    return trace_scopes.part_ms(run, "decode", "attn", "cache")
