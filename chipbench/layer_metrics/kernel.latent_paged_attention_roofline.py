"""Share of its roofline that the latent paged-attention decode kernel
reaches: the least time the chip could take for the traced decode calls
(from each call's own live lengths: the latent rows of the live tokens
read ONCE for all heads, absorbed queries in, latent outputs written;
max of operations over the bf16 peak and bytes over the HBM peak,
`harness/latent_moe_costs.py`), all layers, over the kernel's summed
device time. The kernel is found by the name in the cell's
`kernels.latent_paged_attention`."""
from chipbench.harness import flops, latent_moe_costs


def read(run):
    pattern = run.cell.shape.get("kernels", {}).get("latent_paged_attention")
    calls = run.samples.get("decode_lengths")
    cfg = run.cell.config
    if (run.trace is None or run.peaks is None or not pattern or not calls
            or "kv_lora_rank" not in cfg):
        return None
    seconds, _ = run.trace.op_seconds(pattern)
    if seconds <= 0:
        return None
    least = 0.0
    for lengths in calls:
        ops, byts = latent_moe_costs.latent_attention_cost(
            lengths, cfg["num_attention_heads"],
            cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
            cfg["kv_lora_rank"])
        least += flops.roofline_seconds(ops, byts, run.peaks)[0]
    return 100.0 * least * cfg["num_hidden_layers"] / seconds
