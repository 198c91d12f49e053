"""Device time of the expert layers' own kernels in one decode step, all
expert layers: the operations named by the cell's `kernels.expert_layer`
(the grouped products and the kernel that lays out their groups) that
start inside a call of the program `programs.decode`, summed, over the
number of those calls. A device trace names an operation by its HLO text,
which carries no `named_scope`: the router's product, the two sorts, the
combine and the shared expert are fusions with no name of their own and
are NOT in this number (1.5% of the layer in the one trace that showed
the layer whole: PERF.md section 7)."""
from chipbench.harness import trace_calls


def read(run):
    shape = run.cell.shape
    pattern = shape.get("kernels", {}).get("expert_layer")
    program = shape.get("programs", {}).get("decode")
    if run.trace is None or not pattern or not program:
        return None
    calls, inside = trace_calls.ops_inside(run.trace, pattern, program)
    if not calls or not inside:
        return None
    return sum(inside) / 1e6 / calls
