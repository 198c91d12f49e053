"""Share of its roofline that the selective scan's decode kernel reaches:
the least time the chip could take for the traced decode calls (from each
call's own LIVE lanes: every live lane's state of `d_inner x d_state`
float32 numbers read and written once a Mamba layer, `dt`, `dt x`, `B`, `C`
in and `y` out; max of operations over the bf16 peak and bytes over the HBM
peak, `harness/ssm_costs.py`; memory-bound), all Mamba layers, over the
summed device time of the kernel named by the cell's `kernels.ssm_decode`
inside the program `programs.decode`. The bytes are the WORK's. The
convolution window (three rows a lane) is moved by a slice update outside
the kernel and is in neither the bytes nor the time. Nothing to read where
the program has no such kernel."""
from chipbench.harness import flops, ssm_costs, trace_calls


def read(run):
    shape = run.cell.shape
    pattern = shape.get("kernels", {}).get("ssm_decode")
    program = shape.get("programs", {}).get("decode")
    steps = run.samples.get("decode_lengths")
    cfg = run.cell.config
    if (run.trace is None or run.peaks is None or not pattern or not program
            or not steps or "mamba_d_state" not in cfg):
        return None
    calls, inside = trace_calls.ops_inside(run.trace, pattern, program)
    if not calls or not inside:
        return None
    least = sum(flops.roofline_seconds(*ssm_costs.decode_scan_cost(
        len(lengths), cfg), run.peaks)[0] for lengths in steps)
    return (100.0 * (least / len(steps)) * ssm_costs.mamba_layers(cfg) * calls
            / (sum(inside) / 1e9))
