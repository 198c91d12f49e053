"""Share of their roofline that the softmax-routed experts' grouped
products reach in PREFILL chunks, reckoned as
`kernel.moe_expert_matmul_roofline` reckons it, with this configuration's
keys (`num_experts`, every layer an expert layer: `mlp_layer_types`): over
the chunk calls of the traced window (the operations named by the cell's
`kernels.routed_expert_matmul` that start inside a call of the program
`programs.prefill`), the least time the chip could take over those
operations' summed device time. The least time takes every chunk as FULL:
each layer's whole set of expert matrices read once a chunk and `2 x 3 x
hidden x expert width` operations for each of `chunk x top-k`
token-expert pairs (`harness/latent_moe_costs.py`). That counts too much
for a prompt's last chunk, which holds 1 to `chunk` real rows (its padding
rows all go to the same 8 experts): by uniform routing r real rows touch
`64 x (1 - (7/8)^r)` experts, 63 at r = 32 and all 64 from r = 50 on, so
the BYTES are over-counted in under a tenth of the last chunks and by
little; the OPERATIONS of the padding rows are done by the kernel like any
row's and counted like them. Over the cell's prompts (median 4096 tokens
at chunk 512: one chunk in 12.9 is a last chunk, half full on average)
the share reads under 1% (of the share) too high. The program counts the
distinct experts of each call on the device
(`Engine.device_counters()["prefill"]`), which the harness cannot hand a
reader yet (PERF.md section 7)."""
from chipbench.harness import flops, latent_moe_costs, trace_calls


def read(run):
    shape = run.cell.shape
    pattern = shape.get("kernels", {}).get("routed_expert_matmul")
    program = shape.get("programs", {}).get("prefill")
    cfg = run.cell.config
    if (run.trace is None or run.peaks is None or not pattern or not program
            or "num_experts" not in cfg or "mlp_layer_types" not in cfg):
        return None
    calls, inside = trace_calls.ops_inside(run.trace, pattern, program)
    if not calls or not inside:
        return None
    chunk = shape["engine"]["prefill_chunk"]
    ops, byts = latent_moe_costs.expert_products_cost(
        chunk * cfg["num_experts_per_tok"], cfg["num_experts"],
        cfg["hidden_size"], cfg["moe_intermediate_size"])
    layers = cfg["mlp_layer_types"].count("sparse")
    least = flops.roofline_seconds(ops, byts, run.peaks)[0]
    return 100.0 * least * layers * calls / (sum(inside) / 1e9)
