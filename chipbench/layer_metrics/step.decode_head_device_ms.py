"""Device time of the HEAD of one decode step: the operations billed to
`head` (final norm, the vocabulary-wide projection) or `sample` (tokens and
logprobs of every slot; `harness/trace_scopes.py`) inside whole calls of the
program `programs.decode`, self time, over those calls."""
from chipbench.harness import trace_scopes


def read(run):
    return trace_scopes.part_ms(run, "decode", "head", "sample")
