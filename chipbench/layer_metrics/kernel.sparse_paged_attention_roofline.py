"""Share of its roofline that decode attention over selected keys reaches,
everything it runs counted: the least time the chip could take for the
traced decode calls (from each call's own live lengths: a slot's `length`
cached index keys of `indexer_head_dim` bf16 lanes read and scored by
`indexer_num_heads` index queries, then the K and V rows of
`min(length + 1, topk)` selected keys read and attended by every query
head; each piece's least time is the larger of its operations over the
bf16 peak and its bytes over the HBM peak, `harness/sparse_attention_costs.py`
and `harness/flops.py`), over the device time from the START of a layer's
`kernels.indexer_scores` kernel to the END of its
`kernels.sparse_paged_attention` kernel, summed over the layers of the calls
of the program `programs.decode` (`harness/trace_spans.py`). That span holds
the two kernels, the exact selection (XLA's loops) and the glue between them
(the score transposes, the page sort, the bias), none of which has a least
time of its own (a k-th value and a page order need no byte beyond the
scores), so all of it counts against the share. The lengths are those of
the decode steps the harness saw while the trace ran; their mean least time
stands for each traced call."""
from chipbench.harness import flops, sparse_attention_costs, trace_spans


def read(run):
    shape = run.cell.shape
    kernels = shape.get("kernels", {})
    first = kernels.get("indexer_scores")
    last = kernels.get("sparse_paged_attention")
    program = shape.get("programs", {}).get("decode")
    steps = run.samples.get("decode_lengths")
    cfg = run.cell.config
    if (run.trace is None or run.peaks is None or not first or not last
            or not program or not steps or "sa_config" not in cfg):
        return None
    calls, spans = trace_spans.spans_inside(run.trace, first, last, program)
    if not calls:
        return None
    spent = sum(whole for _, whole in spans) / 1e9
    sa = cfg["sa_config"]
    least = 0.0
    for lengths in steps:
        scores = flops.roofline_seconds(
            *sparse_attention_costs.indexer_score_cost(
                lengths, sa["indexer_num_heads"], sa["indexer_head_dim"]),
            run.peaks)[0]
        attend = flops.roofline_seconds(
            *sparse_attention_costs.sparse_attention_cost(
                lengths, sa["topk"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["head_dim"]),
            run.peaks)[0]
        least += (scores + attend) * cfg["num_hidden_layers"]
    return 100.0 * (least / len(steps)) * calls / spent
