"""Device operations by the program call they ran in: what a per-layer
reader needs when a kernel or a layer means something else in `jit_decode`
than in `jit_prefill`."""

from __future__ import annotations


def ops_inside(trace, op_pattern: str, program_pattern: str):
    """(number of calls of the programs whose name contains
    `program_pattern`, durations in ns of the operations whose name contains
    `op_pattern` and that START inside one of those calls), first device;
    (0, []) where the trace holds no device."""
    if not trace.device_names:
        return 0, []
    dev = trace.events["devices"][trace.device_names[0]]
    calls = [(s, s + d) for n, s, d in dev["modules"]
             if program_pattern in n]
    return len(calls), [d for n, s, d in dev["ops"] if op_pattern in n
                        and any(a <= s < b for a, b in calls)]
