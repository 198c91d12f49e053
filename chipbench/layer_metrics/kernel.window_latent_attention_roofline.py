"""Share of its roofline that the latent decode kernel over a RING reaches
on the sliding layers: the least time the chip could take for the traced
decode calls (from each call's own live lengths: `min(length + 1,
sliding_window_size)` latent rows of `swa_kv_lora_rank +
swa_qk_rope_head_dim` lanes a slot read ONCE and attended by every
`swa_` head in absorbed form; the larger of operations over the bf16 peak
and bytes over the HBM peak, `harness/sparse_latent_costs.py`), all sliding
layers, over the summed device time of the operations named by the cell's
`kernels.window_latent_attention` that start inside a call of the program
`programs.decode`. A sliding layer reads at most a window of rows a slot
however long the session, so the least time does not grow with the
sessions' lengths, and neither may the kernel's."""
from chipbench.harness import flops, trace_calls


def read(run):
    shape = run.cell.shape
    pattern = shape.get("kernels", {}).get("window_latent_attention")
    program = shape.get("programs", {}).get("decode")
    steps = run.samples.get("decode_lengths")
    cfg = run.cell.config
    if (run.trace is None or run.peaks is None or not pattern or not program
            or not steps or "swa_kv_lora_rank" not in cfg):
        return None
    from chipbench.harness import sparse_latent_costs as costs

    calls, inside = trace_calls.ops_inside(run.trace, pattern, program)
    if not calls or not inside:
        return None
    layers = sum(kind == "sliding_attention" for kind in cfg["layer_types"])
    least = 0.0
    for lengths in steps:
        least += layers * flops.roofline_seconds(
            *costs.bounded_latent_attention_cost(
                lengths, cfg["sliding_window_size"],
                cfg["swa_num_attention_heads"],
                cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"],
                cfg["swa_kv_lora_rank"]), run.peaks)[0]
    return 100.0 * (least / len(steps)) * calls / (sum(inside) / 1e9)
