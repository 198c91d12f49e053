"""Share of its roofline that the retention decode kernel reaches: the
least time the chip could take for the traced decode calls (from each
call's own LIVE lanes: every live lane's state of `d (d + 1) / 2` rows a
KV head read and written once, q, k, v and the gate in, o out; max of
operations over the bf16 peak and bytes over the HBM peak,
`harness/retention_costs.py`; memory-bound), all layers, over the summed
device time of the kernel named by the cell's `kernels.retention_decode`
inside the program `programs.decode`. The bytes are the WORK's (8,256
rows), whatever rows the program's layout pads a state to. Nothing to
read where the program has no such kernel."""
from chipbench.harness import flops, retention_costs, trace_calls


def read(run):
    shape = run.cell.shape
    pattern = shape.get("kernels", {}).get("retention_decode")
    program = shape.get("programs", {}).get("decode")
    steps = run.samples.get("decode_lengths")
    cfg = run.cell.config
    if (run.trace is None or run.peaks is None or not pattern or not program
            or not steps or "retention_degree" not in cfg):
        return None
    calls, inside = trace_calls.ops_inside(run.trace, pattern, program)
    if not calls or not inside:
        return None
    least = sum(flops.roofline_seconds(*retention_costs.decode_step_cost(
        len(lengths), cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["retention_degree"]), run.peaks)[0] for lengths in steps)
    return (100.0 * (least / len(steps)) * cfg["num_hidden_layers"] * calls
            / (sum(inside) / 1e9))
