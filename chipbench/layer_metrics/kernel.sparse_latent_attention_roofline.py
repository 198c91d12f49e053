"""Share of its roofline that decode attention over SELECTED LATENT rows
reaches on the full layers, everything it runs counted: the least time the
chip could take for the traced decode calls (from each call's own live
lengths: a slot's `length` cached index keys of `index_head_dim` bf16 lanes
read and scored by `index_n_heads` index queries, then `min(length + 1,
index_topk)` latent rows of `kv_lora_rank + qk_rope_head_dim` lanes read
ONCE and attended by every head in absorbed form; each piece's least time
is the larger of its operations over the bf16 peak and its bytes over the
HBM peak, `harness/sparse_latent_costs.py` and `harness/flops.py`), over
the device time from the START of a full layer's `kernels.indexer_scores`
kernel to the END of its `kernels.sparse_paged_attention` kernel, summed
over the full layers of the calls of the program `programs.decode`
(`harness/trace_spans.py`). That span holds the two kernels, the exact
selection (XLA's loops) and the glue between them, none of which has a
least time of its own, so all of it counts against the share. The lengths
are those of the decode steps the harness saw while the trace ran; their
mean least time stands for each traced call."""
from chipbench.harness import flops, trace_spans


def read(run):
    shape = run.cell.shape
    kernels = shape.get("kernels", {})
    first = kernels.get("indexer_scores")
    last = kernels.get("sparse_paged_attention")
    program = shape.get("programs", {}).get("decode")
    steps = run.samples.get("decode_lengths")
    cfg = run.cell.config
    if (run.trace is None or run.peaks is None or not first or not last
            or not program or not steps or "index_topk" not in cfg):
        return None
    from chipbench.harness import sparse_latent_costs as costs

    calls, spans = trace_spans.spans_inside(run.trace, first, last, program)
    if not calls:
        return None
    spent = sum(whole for _, whole in spans) / 1e9
    layers = sum(kind == "full_attention" for kind in cfg["layer_types"])
    least = 0.0
    for lengths in steps:
        scores = flops.roofline_seconds(
            *costs.index_score_cost(lengths, cfg["index_n_heads"],
                                    cfg["index_head_dim"]), run.peaks)[0]
        attend = flops.roofline_seconds(
            *costs.bounded_latent_attention_cost(
                lengths, cfg["index_topk"], cfg["num_attention_heads"],
                cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
                cfg["kv_lora_rank"]), run.peaks)[0]
        least += (scores + attend) * layers
    return 100.0 * (least / len(steps)) * calls / spent
