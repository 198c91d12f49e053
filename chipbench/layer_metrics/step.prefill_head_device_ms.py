"""Device time of the HEAD of one prefill chunk: the operations billed to
`head` (final norm, the vocabulary-wide projection) or `sample` (the row
read, the token and its logprob; `harness/trace_scopes.py`) inside whole
calls of the program `programs.prefill`, self time, over those calls. A
family that projects every row of a chunk and reads one shows here."""
from chipbench.harness import trace_scopes


def read(run):
    return trace_scopes.part_ms(run, "prefill", "head", "sample")
