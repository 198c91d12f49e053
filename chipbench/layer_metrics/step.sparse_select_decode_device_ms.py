"""Device time of CHOOSING the keys in one decode step, all layers: from
the START of a layer's `kernels.indexer_scores` kernel (every slot's live
index keys scored) to the START of its `kernels.sparse_paged_attention`
kernel, summed over the layers of a call of the program `programs.decode`,
over the number of those calls (`harness/trace_spans.py`). The span holds
the score kernel, the exact top-k (XLA's loops, `%while` in a trace) and
the glue that hands the selection to the attention kernel (the score
transposes, the page sort, the bias): what a dense model does not pay at
all. It buys the attention kernel its shorter read, and a change that makes
choosing cost more than it saves shows here first."""
from chipbench.harness import trace_spans


def read(run):
    shape = run.cell.shape
    kernels = shape.get("kernels", {})
    first = kernels.get("indexer_scores")
    last = kernels.get("sparse_paged_attention")
    program = shape.get("programs", {}).get("decode")
    if run.trace is None or not first or not last or not program:
        return None
    calls, spans = trace_spans.spans_inside(run.trace, first, last, program)
    if not calls:
        return None
    return sum(before for before, _ in spans) / 1e6 / calls
