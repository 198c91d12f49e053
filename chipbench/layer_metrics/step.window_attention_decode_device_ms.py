"""Device time of the SLIDING layers' paged-attention kernel in one decode
step, all sliding layers: the operations named by the cell's
`kernels.window_paged_attention` that start inside a call of the program
`programs.decode`, summed, over the number of those calls. A sliding
layer reads at most `sliding_window` keys a slot however long the session
(its cache is a ring of pages), so this number must NOT grow with the
sessions' lengths: a change that lets a sliding layer walk the whole
context shows here first."""
from chipbench.harness import trace_calls


def read(run):
    shape = run.cell.shape
    pattern = shape.get("kernels", {}).get("window_paged_attention")
    program = shape.get("programs", {}).get("decode")
    if run.trace is None or not pattern or not program:
        return None
    calls, inside = trace_calls.ops_inside(run.trace, pattern, program)
    if not calls or not inside:
        return None
    return sum(inside) / 1e6 / calls
