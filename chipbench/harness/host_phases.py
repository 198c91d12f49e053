"""The serving engine's host pass, read from a traced window's events.

The engine wraps every stretch of its host pass in a span whose name
starts with `serving.` (docs/observability.md, "Engine phases"); live spans
are forwarded to the profiler, so `trace_reduce.extract` finds them on the
host plane of the same file, and on the same clock, as the device's
operations. From `TraceSummary.events` this module gives

- the host pass by phase: self time grouped so that the groups are
  disjoint and sum to the whole (`phase_times`);
- the number of engine steps (`engine_steps`);
- the device's idle time split by OVERLAP among the innermost `serving.*`
  span open during each part of each gap, the rest being outside the
  engine, in the caller's loop (`idle_by_phase`). `TraceSummary.idle_gaps`
  gives a whole gap to the one span open at its middle; a gap that starts
  under one phase and ends under another is split here.

The engine drives all of this on ONE thread, and the event list carries no
thread: spans are nested by time alone.

    python3 chipbench/harness/host_phases.py <events.json.gz>

prints the table (events as `trace_reduce.save_events` wrote them).
"""

from __future__ import annotations

import collections
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from chipbench.harness.context import percentile
from chipbench.harness.trace_reduce import _union, load_events, self_times

PREFIX = "serving."
# the one phase in which the host waits for the chip
WAIT = PREFIX + "host_read"
# one engine step = one dispatch of one of these programs
STEP_SPANS = (PREFIX + "decode", PREFIX + "prefill", PREFIX + "verify")
# choosing the program, building its arguments, dispatching it: the way from
# the scheduler's decision to the device (those of the admit program lie
# inside a container below and count there)
STAGE_SPANS = tuple(PREFIX + n for n in (
    "schedule", "stage_inputs", "decode", "prefill", "verify", "draft",
    "draft_prefill", "admit", "swap_in"))
# phases counted with everything nested in them (the allocator's spans, the
# admit program's dispatch), wherever they open
CONTAINERS = (PREFIX + "admit_pending", PREFIX + "commit")
OUTSIDE = "(outside the engine)"


def engine_spans(events: dict) -> list:
    """The host's `serving.*` spans, [name, start_ns, dur_ns], by start
    (an enclosing span before what it encloses)."""
    spans = [e for e in events["host"] if e[0].startswith(PREFIX)]
    spans.sort(key=lambda e: (e[1], -e[2]))
    return spans


def has_phases(spans: list) -> bool:
    """Whether the engine that made this trace records its phases at all
    (one before them has the dispatch spans only)."""
    return any(name == WAIT for name, _, _ in spans)


def engine_steps(spans: list) -> int:
    return sum(1 for name, _, _ in spans if name in STEP_SPANS)


def median_ms(spans: list, name: str):
    """Median duration of the spans of that name; None if there is none."""
    value = percentile([d for n, _, d in spans if n == name], 50)
    return None if value is None else value / 1e6


def span_median_ms(run, name: str):
    """`median_ms` over a traced run's engine spans; None untraced."""
    if run.trace is None:
        return None
    return median_ms(engine_spans(run.trace.events), name)


def innermost_segments(spans: list) -> list:
    """[start, end, name, group] stretches, in order and disjoint, each
    under the innermost span open during it: its name, and its group. A
    span's group is the outermost container it lies in (itself included),
    else its own name; the wait phase keeps its name wherever it opens."""
    segs = []
    stack = []  # [name, group, end]
    cursor = 0.0

    def advance(to):
        nonlocal cursor
        if stack and to > cursor:
            segs.append([cursor, to, stack[-1][0], stack[-1][1]])
        cursor = max(cursor, to)

    for name, start, dur in spans:
        while stack and stack[-1][2] <= start:
            advance(stack[-1][2])
            stack.pop()
        advance(start)
        group = name
        if name != WAIT and stack and stack[-1][1] in CONTAINERS:
            group = stack[-1][1]
        # a child never outlasts what encloses it (clock granularity)
        end = min(start + dur, stack[-1][2]) if stack else start + dur
        stack.append([name, group, end])
    while stack:
        advance(stack[-1][2])
        stack.pop()
    return segs


def phase_times(spans: list) -> dict:
    """group -> summed self time (ns): the stretches during which a span of
    that group is the innermost one. Groups are disjoint and sum to the
    time covered by any `serving.*` span."""
    out: dict = {}
    for start, end, _, group in innermost_segments(spans):
        out[group] = out.get(group, 0.0) + end - start
    return out


def read_phases(run):
    """(spans, groups, steps) of a traced run whose engine records its
    phases; None otherwise (no trace, an engine before the phases, no
    step in the window), so that a reader returns nothing and never
    raises."""
    if run.trace is None:
        return None
    spans = engine_spans(run.trace.events)
    steps = engine_steps(spans)
    if not steps or not has_phases(spans):
        return None
    return spans, phase_times(spans), steps


def host_pass_ns(groups: dict) -> float:
    """Everything the host does inside the engine except waiting for the
    chip."""
    return sum(t for g, t in groups.items() if g != WAIT)


def ms_per_step(run, names=None):
    """Milliseconds an engine step of the groups `names` (all of the host
    pass when None); None where `read_phases` finds nothing."""
    got = read_phases(run)
    if got is None:
        return None
    _, groups, steps = got
    total = (host_pass_ns(groups) if names is None
             else sum(groups.get(n, 0.0) for n in names))
    return total / steps / 1e6


def device_gaps(events: dict) -> list:
    """[start, end] stretches between operations of the first device."""
    names = sorted(events["devices"])
    if not names:
        return []
    busy = _union([(s, s + d) for _, s, d in
                   events["devices"][names[0]]["ops"]])
    return [[e0, s1] for (_, e0), (s1, _) in zip(busy, busy[1:]) if s1 > e0]


def idle_by_phase(events: dict, spans: list | None = None) -> dict:
    """name -> idle ns of the first device overlapped by that innermost
    span; `OUTSIDE` takes what no `serving.*` span overlaps. Sums to the
    gaps' total."""
    spans = engine_spans(events) if spans is None else spans
    segs = innermost_segments(spans)
    out: dict = {}
    i = 0
    for g0, g1 in device_gaps(events):
        while i < len(segs) and segs[i][1] <= g0:
            i += 1
        covered, j = 0.0, i
        while j < len(segs) and segs[j][0] < g1:
            s0, s1, name, _ = segs[j]
            part = min(s1, g1) - max(s0, g0)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            j += 1
        out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (g1 - g0) - covered
    return out


def report(events: dict) -> str:
    spans = engine_spans(events)
    steps = engine_steps(spans)
    by_name = self_times(spans)
    groups = phase_times(spans)
    idle = idle_by_phase(events, spans)
    counts = collections.Counter(name for name, _, _ in spans)
    per = max(steps, 1)
    rows = [f"{steps} engine steps; host pass "
            f"{host_pass_ns(groups) / per / 1e6:.3f} ms a step; idle "
            f"{sum(idle.values()) / 1e6:.1f} ms in the gaps between "
            f"operations",
            f"{'span':28s} {'count':>7s} {'self ms':>10s} {'ms/step':>9s} "
            f"{'median ms':>10s} {'idle ms':>9s}"]
    for name in sorted(set(by_name) | set(idle),
                       key=lambda n: -by_name.get(n, 0.0)):
        med = median_ms(spans, name)
        rows.append(
            f"{name:28s} {counts[name]:7d} "
            f"{by_name.get(name, 0.0) / 1e6:10.2f} "
            f"{by_name.get(name, 0.0) / per / 1e6:9.3f} "
            f"{med if med is not None else 0.0:10.3f} "
            f"{idle.get(name, 0.0) / 1e6:9.2f}")
    rows.append("by group (containers with what is nested in them): "
                + ", ".join(f"{g} {t / per / 1e6:.3f}" for g, t in
                            sorted(groups.items(), key=lambda kv: -kv[1])))
    return "\n".join(rows)


if __name__ == "__main__":
    print(report(load_events(sys.argv[1])))
