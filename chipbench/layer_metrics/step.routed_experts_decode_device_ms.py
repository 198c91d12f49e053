"""Device time of the softmax-routed expert layers' own kernels in one
decode step, all layers: the operations named by the cell's
`kernels.routed_expert_layer` (the grouped products and the kernel that
lays out their groups) that start inside a call of the program
`programs.decode`, summed, over the number of those calls
(`step.moe_decode_device_ms`'s definition, under this cell's key). The
router's product, the sorts and the combine are fusions with no name of
their own and are NOT in this number."""
from chipbench.harness import trace_calls


def read(run):
    shape = run.cell.shape
    pattern = shape.get("kernels", {}).get("routed_expert_layer")
    program = shape.get("programs", {}).get("decode")
    if run.trace is None or not pattern or not program:
        return None
    calls, inside = trace_calls.ops_inside(run.trace, pattern, program)
    if not calls or not inside:
        return None
    return sum(inside) / 1e6 / calls
