"""Share of its roofline that the paged-attention decode kernel reaches:
the least time the chip could take for the traced decode calls (from each
call's own live lengths: K and V rows of the live tokens, q in, out
written; max of operations over the bf16 peak and bytes over the HBM peak,
`harness/flops.py`), all layers, over the kernel's summed device time."""
from chipbench.harness import flops


def read(run):
    pattern = run.cell.shape.get("kernels", {}).get("paged_attention")
    calls = run.samples.get("decode_lengths")
    if run.trace is None or run.peaks is None or not pattern or not calls:
        return None
    seconds, _ = run.trace.op_seconds(pattern)
    if seconds <= 0:
        return None
    cfg = run.cell.config
    least = 0.0
    for lengths in calls:
        ops, byts = flops.paged_attention_cost(
            lengths, cfg["num_attention_heads"], cfg["num_key_value_heads"],
            run.counters["head_dim"])
        least += flops.roofline_seconds(ops, byts, run.peaks)[0]
    return 100.0 * least * cfg["num_hidden_layers"] / seconds
