"""Share of their roofline that the routed experts' grouped products reach
in PREFILL chunks: over the chunk calls of the traced window (the
operations named by the cell's `kernels.moe_expert_matmul` that start
inside a call of the program `programs.prefill`), the least time the chip
could take over those operations' summed device time. The least time
takes every chunk as FULL: each expert layer's whole set of expert
matrices read once a chunk and `2 x 3 x hidden x expert width` operations
for each of `chunk x top-k` token-expert pairs
(`harness/latent_moe_costs.py`). That counts too much for a question's
last chunk, which holds 1 to `chunk` real rows (its padding rows all go to
the same 8 experts): by uniform routing r real rows touch `256 x (1 -
(31/32)^r)` experts, 203 at r = 50, all at r >= 200. Over the cell's
questions (128-1024 tokens at chunk 512: 1.6 chunks a request) the chunks
touch about 250 of 256 experts on average, so the bytes and with them
this share read about 2% (of the share) too high. The program counts the
distinct experts of each call on the device
(`Engine.device_counters()["prefill"]`), which the harness cannot hand a
reader yet (PERF.md section 7)."""
from chipbench.harness import flops, latent_moe_costs, trace_calls


def read(run):
    shape = run.cell.shape
    pattern = shape.get("kernels", {}).get("moe_expert_matmul")
    program = shape.get("programs", {}).get("prefill")
    cfg = run.cell.config
    if (run.trace is None or run.peaks is None or not pattern or not program
            or "n_routed_experts" not in cfg):
        return None
    calls, inside = trace_calls.ops_inside(run.trace, pattern, program)
    if not calls or not inside:
        return None
    chunk = shape["engine"]["prefill_chunk"]
    ops, byts = latent_moe_costs.expert_products_cost(
        chunk * cfg["num_experts_per_tok"], cfg["n_routed_experts"],
        cfg["hidden_size"], cfg["moe_intermediate_size"])
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    least = flops.roofline_seconds(ops, byts, run.peaks)[0]
    return 100.0 * least * layers * calls / (sum(inside) / 1e9)
