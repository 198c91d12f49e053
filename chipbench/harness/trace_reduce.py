"""From a profiler trace to numbers: device busy and idle, time by program,
time by operation (self time, so a loop does not count its body twice),
the longest idle gaps named by what the host was doing.

Two steps, so that the second can be checked on a small recorded trace:
`extract(xplane path)` turns the profiler's `.xplane.pb` into a plain event
list (JSON-able), and `TraceSummary(events, ...)` reduces that list.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import shutil
import time

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host spans worth naming an idle gap by: the harness's own, and the
# program's telemetry spans forwarded to the profiler
HOST_SPAN_PREFIXES = ("chipbench.", "serving.", "accelerate_tpu.", "data.")


def short_name(hlo: str) -> str:
    """An operation's trace name is its whole HLO text (hundreds of
    characters). Keep what identifies it: `%name op[custom-call target]
    kind result-type`, layouts dropped."""
    m = re.match(r"^(%\S+) = (.*)$", hlo, re.S)
    if not m:
        return hlo[:120]
    lhs, rhs = m.groups()
    if rhs.startswith("("):
        depth, end = 0, len(rhs)
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                end = i + 1
                break
    else:
        end = rhs.find(" ") if " " in rhs else len(rhs)
    rtype = re.sub(r"\{[^}]*\}", "", rhs[:end])
    op = rhs[end:].lstrip().split("(", 1)[0].strip()
    target = re.search(r'custom_call_target="([^"]+)"', rhs)
    kind = re.search(r"kind=(\w+)", rhs)
    return (f"{lhs} {op}" + (f"[{target.group(1)}]" if target else "")
            + (f" {kind.group(1)}" if kind else "") + f" {rtype[:80]}")


def extract(path: str) -> dict:
    """The device's op and module events and the host's named spans of one
    `.xplane.pb`, times in nanoseconds from the trace's own origin.
    Operation names are shortened by `short_name`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    name = short_name(ev.name) if key == "ops" else ev.name
                    dev[key].append([name, float(ev.start_ns),
                                     float(ev.duration_ns)])
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIXES):
                        out["host"].append([ev.name, float(ev.start_ns),
                                            float(ev.duration_ns)])
    out["host"].sort(key=lambda e: e[1])
    return out


def describe(path: str, limit: int = 12) -> str:
    """What is in a trace, for a reader who has not seen one from this
    machine yet: planes, lines, event counts, a few events with stats."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        rows.append(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            rows.append(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:limit]:
                stats = {k: v for k, v in list(ev.stats)[:8]}
                rows.append(f"    {ev.name!r} start {ev.start_ns:.0f} dur "
                            f"{ev.duration_ns:.0f} {stats}")
    return "\n".join(rows)


def _union(intervals):
    """Merged [start, end] intervals, sorted."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def self_times(events):
    """name -> summed SELF time (ns): an event's duration minus what the
    events nested inside it cover. Events are [name, start, duration]."""
    out: dict = {}
    stack = []  # [name, end, child_ns, dur]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, child, dur = stack.pop()
            out[name] = out.get(name, 0.0) + max(dur - child, 0.0)
            if stack:
                stack[-1][2] += dur

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        stack.append([name, start + dur, 0.0, dur])
    close(float("inf"))
    return out


class TraceSummary:
    def __init__(self, events: dict, window_s: float, chips: int = 1):
        self.events = events
        self.window_s = float(window_s)
        names = sorted(events["devices"])[:chips]
        self.device_names = names
        self.chips = max(len(names), 1)
        self._busy = {n: _union([(s, s + d) for _, s, d in
                                 events["devices"][n]["ops"]])
                      for n in names}
        self.busy_s = sum(sum(e - s for s, e in iv)
                          for iv in self._busy.values()) / self.chips / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_calls(self, pattern: str) -> list:
        """Durations (s) of the device programs whose name contains
        `pattern`, on the first device."""
        if not self.device_names:
            return []
        mods = self.events["devices"][self.device_names[0]]["modules"]
        return [d / 1e9 for n, _, d in mods if pattern in n]

    def median_call_ms(self, pattern: str):
        """Median device milliseconds of one call of that program; None if
        the trace holds none."""
        calls = sorted(self.module_calls(pattern))
        return 1e3 * calls[len(calls) // 2] if calls else None

    def op_seconds(self, pattern: str) -> tuple[float, int]:
        """(summed device seconds, count) of operations whose name contains
        `pattern`, averaged over the devices used."""
        total, count = 0.0, 0
        for n in self.device_names:
            for name, _, d in self.events["devices"][n]["ops"]:
                if pattern in name:
                    total += d
                    count += 1
        return total / self.chips / 1e9, count // self.chips

    def top_ops(self, k: int = 10) -> list:
        if not self.device_names:
            return []
        st = self_times(self.events["devices"][self.device_names[0]]["ops"])
        top = sorted(st.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t / 1e9] for n, t in top]

    def idle_gaps(self, k: int = 10) -> list:
        """Idle seconds of the first device by the host span that was open
        in the middle of each gap (innermost), largest first."""
        if not self.device_names:
            return []
        busy = self._busy[self.device_names[0]]
        host = self.events["host"]
        by_name: dict = {}
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            gap = s1 - e0
            if gap <= 0:
                continue
            mid = e0 + gap / 2
            name, best = "(no span)", None
            for hn, hs, hd in host:
                if hs > mid:
                    break
                if hs + hd >= mid and (best is None or hd < best):
                    name, best = hn, hd
            by_name[name] = by_name.get(name, 0.0) + gap
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t / 1e9] for n, t in top]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


class Capture:
    """`jax.profiler` around the LAST `trace_seconds` of the window of a
    `--trace 1` run; a no-op otherwise. Stopping the profiler holds the
    calling thread for seconds, so the trace ends with the window and the
    stop falls after it, where it delays nothing that is measured. The
    trace goes into the cell's scratch directory inside the checkout and
    is read back by `reduce`."""

    def __init__(self, work_dir: str, enabled: bool, trace_seconds: float):
        self.enabled = enabled
        self.trace_seconds = trace_seconds
        self.dir = os.path.join(work_dir, "trace")
        self.running = False
        self.window_s = 0.0
        self.stopped_at: float | None = None  # when `stop` was called
        self._t0 = 0.0

    def poll(self, now: float, t_close: float) -> None:
        """Start once the window has `trace_seconds` left."""
        if (not self.enabled or self.running or self.stopped_at is not None
                or now < t_close - self.trace_seconds):
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self.running = True
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        if not self.running:
            return
        import jax

        self.stopped_at = time.perf_counter()
        self.window_s = self.stopped_at - self._t0
        jax.profiler.stop_trace()
        self.running = False

    def xplane(self) -> str | None:
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        return found[0] if found else None

    def reduce(self, chips: int):
        path = self.xplane() if self.enabled else None
        if path is None:
            return None
        return TraceSummary(extract(path), self.window_s, chips)


def save_events(events: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load_events(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


if __name__ == "__main__":  # python3 chipbench/harness/trace_reduce.py <xplane.pb> [events.json.gz]
    import sys

    print(describe(sys.argv[1]))
    if len(sys.argv) > 2:
        save_events(extract(sys.argv[1]), sys.argv[2])
