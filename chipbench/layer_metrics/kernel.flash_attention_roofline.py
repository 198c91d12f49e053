"""Share of its roofline that the flash-attention kernel reaches: the least
time the chip could take for the traced calls' attention (forward and
backward, causal, from the cell's shapes; max of operations over the bf16
peak and bytes over the HBM peak) over the kernel's summed device time."""
from chipbench.harness import flops


def read(run):
    pattern = run.cell.shape.get("kernels", {}).get("flash_attention")
    if run.trace is None or run.peaks is None or not pattern:
        return None
    seconds, count = run.trace.op_seconds(pattern)
    pattern_calls = run.cell.shape.get("programs", {}).get("train_step")
    steps = len(run.trace.module_calls(pattern_calls)) if pattern_calls else 0
    if seconds <= 0 or steps == 0:
        return None
    cfg, c = run.cell.config, run.counters
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    ops, byts = flops.flash_attention_cost(
        c["batch"] // c["chips"] or 1, c["seq_len"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"], hd)
    least, _ = flops.roofline_seconds(ops, byts, run.peaks)
    return 100.0 * least * cfg["num_hidden_layers"] * steps / seconds
