"""Share of their roofline that the two paged-attention decode kernels of
a model whose layers differ in kind reach together: the least time the
chip could take for the traced decode calls, every layer by its own kind
(from each call's own live lengths: a full layer reads `length + 1` keys a
slot, a sliding layer `min(length + 1, sliding_window)`; K and V rows of
`num_key_value_heads x head_dim` bf16 numbers, q in, out written; a
kernel call's least time is the larger of its operations over the bf16
peak and its bytes over the HBM peak, `harness/mixed_window_costs.py` and
`harness/flops.py`), over the summed device time of the operations named
by the cell's `kernels.mixed_paged_attention` (both kernels' names hold
it) that start inside a call of the program `programs.decode`. The
lengths are those of the decode steps the harness saw while the trace
ran; their mean least time stands for each traced call. `head_dim` is the
configuration's own key: it is no `hidden_size / heads` here."""
from chipbench.harness import flops, mixed_window_costs, trace_calls


def read(run):
    shape = run.cell.shape
    pattern = shape.get("kernels", {}).get("mixed_paged_attention")
    program = shape.get("programs", {}).get("decode")
    steps = run.samples.get("decode_lengths")
    cfg = run.cell.config
    if (run.trace is None or run.peaks is None or not pattern or not program
            or not steps or "layer_types" not in cfg):
        return None
    calls, inside = trace_calls.ops_inside(run.trace, pattern, program)
    if not calls or not inside:
        return None
    least = 0.0
    for lengths in steps:
        for window in mixed_window_costs.layer_windows(cfg):
            least += flops.roofline_seconds(
                *mixed_window_costs.decode_attention_cost(
                    lengths, cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"], window),
                run.peaks)[0]
    return 100.0 * (least / len(steps)) * calls / (sum(inside) / 1e9)
