"""Device time of the selective scan's chunk kernel in one prefill chunk,
all Mamba layers: the operations named by the cell's `kernels.ssm_chunk`
that start inside a call of the program `programs.prefill`, summed, over
the number of those calls. The recurrence is sequential in the chunk's
rows; what a chunk costs beyond its matmuls shows here. (No roofline share:
the kernel's least time is element-wise work, an exponential and some six
multiply-adds a state element a row, and `harness/device.py` has no
published peak for the vector unit.) Nothing to read where the program has
no such kernel."""
from chipbench.harness import trace_calls


def read(run):
    shape = run.cell.shape
    pattern = shape.get("kernels", {}).get("ssm_chunk")
    program = shape.get("programs", {}).get("prefill")
    if run.trace is None or not pattern or not program:
        return None
    calls, inside = trace_calls.ops_inside(run.trace, pattern, program)
    if not calls or not inside:
        return None
    return sum(inside) / 1e6 / calls
