"""Device time between two named kernels of the same layer, by the program
call they ran in: what a reader needs when the work between the kernels is
XLA's own (a loop, a sort, fusions) and has no name of its own in a trace.
A chip runs a program's operations one after another, so the time from the
start of a layer's first kernel to the end (or the start) of its last one is
everything the layer did between them; an operation the scheduler happened to
place there is billed to the span, which makes a share of a roofline read
LOWER, never higher."""

from __future__ import annotations


def spans_inside(trace, first_pattern: str, last_pattern: str,
                 program_pattern: str):
    """(calls, spans): `calls` is how many calls of the programs whose name
    contains `program_pattern` were read whole, and `spans` holds one
    `(ns from the first kernel's start to the last kernel's START, ns from
    the first kernel's start to the last kernel's END)` for every pair of
    an operation whose name contains `first_pattern` and the next one whose
    name contains `last_pattern`, inside those calls, first device. A call
    that does not hold as many of the one as of the other, each first
    before its last (the trace's edge cut it), is left out; (0, []) where
    the trace holds no device or no such pair."""
    if not trace.device_names:
        return 0, []
    dev = trace.events["devices"][trace.device_names[0]]
    calls, spans = 0, []
    named = sorted((s, d, first_pattern in n) for n, s, d in dev["ops"]
                   if first_pattern in n or last_pattern in n)
    for name, start, dur in dev["modules"]:
        if program_pattern not in name:
            continue
        inside = [e for e in named if start <= e[0] < start + dur]
        firsts, lasts = inside[0::2], inside[1::2]
        if (not inside or len(firsts) != len(lasts)
                or not all(f[2] for f in firsts) or any(e[2] for e in lasts)):
            continue
        calls += 1
        spans += [(b[0] - a[0], b[0] + b[1] - a[0])
                  for a, b in zip(firsts, lasts)]
    return calls, spans
