"""Device time by the PART of the model that issued it.

The program wraps its parts in `jax.named_scope` under one vocabulary
(`accelerate_tpu/models/common.py` `PARTS`; copied below, this file imports
nothing of the program): a scope is HLO metadata, an operation's `op_name`,
e.g. `jit(decode)/jit(main)/while/body/attn.project/dot_general`. This
module reads a traced run's `.xplane.pb` once more (`trace_reduce.extract`
keeps `%name op kind type` of an operation and drops the rest) and bills
every operation of the first device's 'XLA Ops' line

- its SELF time, by `trace_reduce.self_times`' rule (a loop does not count
  its body twice);
- to the call of a program ('XLA Modules' line) it STARTS in, whole calls
  only (a call the trace's edge cut is left out);
- to the INNERMOST component of its `op_name` that is a known part, looking
  through what a transformation wraps a name in (`transpose(jvp(loss))`,
  `checkpoint`, `while/body`: a backward operation carries its forward's
  scope), else to `(unscoped)`.

A fusion has ONE `op_name` (its root's): where XLA fuses across a boundary
the whole fusion goes to one part. `(unscoped)` is the gauge of what the
vocabulary misses. The metadata is the COMPILED program's: the persistent
compile cache's key leaves metadata out, so an executable read from an
entry that a build without these scopes wrote carries THAT build's
`op_name`s and reads all `(unscoped)` (seen on the chip, PR 38: a
parent's run had filled the cache). A reader keyed by part survives the kernel beneath it
being replaced, which a reader keyed by a kernel's name does not (PR 36).

    python3 chipbench/harness/trace_scopes.py <xplane.pb> <program pattern> [...]

prints the table of one capture, as `host_phases.py` does for the host.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
import sys
import time

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from chipbench.harness import trace_reduce
from chipbench.harness.context import log

# accelerate_tpu/models/common.py `PARTS`, letter for letter
# (tests/test_model_parts.py holds the two lists together)
PARTS = (
    "embed",
    "attn.project", "attn.indexer", "attn.select", "attn.attend",
    "attn.output",
    "cache.view", "cache.write",
    "mlp",
    "moe.route", "moe.sort", "moe.experts", "moe.combine", "moe.shared",
    "head", "sample", "loss", "optimizer",
)
UNSCOPED = "(unscoped)"
# `transpose(jvp(attn.project))`, `checkpoint(mlp)`: one wrapper around a
# name; `jit(head)` is a FUNCTION called head, not the part
_WRAPPED = re.compile(r"^([A-Za-z_][\w.]*)\((.*)\)$")
_NOT_WRAPPERS = ("jit", "pjit", "xla_call", "shard_map", "pmap")
# what the compiler names itself: XLA expands `jax.lax.ragged_dot` into
# custom calls whose `op_name` is `ragged-dot-none`, the name stack gone
# (read on the chip, PR 38; a Pallas kernel keeps the stack it was called
# under). Used only where an `op_name` holds no part.
COMPILER_NAMED = (("ragged-dot", "moe.experts"),)


@functools.lru_cache(maxsize=None)
def part_of(op_name: str | None) -> str:
    """The innermost known part among the `/`-separated components of an
    `op_name`; `UNSCOPED` when it has none. (Cached: a capture holds
    hundreds of thousands of operations under a few thousand names.)"""
    for component in reversed((op_name or "").split("/")):
        while component:
            if component in PARTS:
                return component
            m = _WRAPPED.match(component)
            if m is None or m.group(1) in _NOT_WRAPPERS:
                break
            component = m.group(2)
    for prefix, part in COMPILER_NAMED:
        if (op_name or "").startswith(prefix):
            return part
    return UNSCOPED


def parts_matching(*prefixes: str) -> tuple:
    """The parts a reader sums: every part that equals a prefix or lies
    under it (`attn` -> `attn.project`, ..., `mlp` -> `mlp`)."""
    return tuple(p for p in PARTS
                 if any(p == x or p.startswith(x + ".") for x in prefixes))


# ---------------------------------------------------------------------------
# the .xplane.pb, read for what `jax.profiler.ProfileData` does not show
# ---------------------------------------------------------------------------
#
# Where an operation's `op_name` lives (read on the chip, PR 38): NOT in the
# event's name (the HLO text, printed without `metadata={...}`) and NOT in
# the event's own stats (`device_offset_ps`, `device_duration_ps`, `Time
# Scale Multiplier`: all `ProfileData` iterates), but in the stats of the
# event's METADATA (`XEventMetadata.stats`), under `tf_op`, as `<op_name>:`.
# The file is a protocol buffer (tsl/profiler/protobuf/xplane.proto); the few
# fields needed are decoded here from the wire format, with the standard
# library alone.

# field numbers of xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_META_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_META_NAME, _META_STATS = 2, 5
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_MAP_KEY, _MAP_VALUE = 1, 2
OP_NAME_STAT = "tf_op"


def _varint(buf, i: int):
    """(the varint at buf[i:], the index after it)."""
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, start: int, end: int):
    """(field number, value) of every field of the message in
    buf[start:end]: an int for a varint, (start, end) for a
    length-delimited field, None for a fixed-width one."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = (i, i + size), i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    """(key, span of the value) of one entry of a map<int64, message>."""
    key, value = 0, (span[1], span[1])
    for field, v in _fields(buf, *span):
        if field == _MAP_KEY:
            key = v
        elif field == _MAP_VALUE:
            value = v
    return key, value


def read_device(path: str) -> dict | None:
    """The first device's operations and program calls of one `.xplane.pb`:
    {"ops": [[op_name or None, start_ns, dur_ns, HLO text], ...],
    "modules": [[name, start_ns, dur_ns], ...]}, times from the line's own
    origin as `trace_reduce.extract` has them; None where the file holds no
    device plane (a trace made on the CPU)."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    for field, span in _fields(buf, 0, len(buf)):
        if field != _SPACE_PLANES:
            continue
        for inner, v in _fields(buf, *span):
            if inner == _PLANE_NAME:
                planes.append((_text(buf, v), span))
                break
    device = sorted(p for p in planes
                    if p[0].startswith(trace_reduce.DEVICE_PREFIX))
    if not device:
        return None
    lines, event_meta, stat_names = [], {}, {}
    for field, v in _fields(buf, *device[0][1]):
        if field == _PLANE_LINES:
            lines.append(v)
        elif field == _PLANE_EVENT_META:
            key, value = _map_entry(buf, v)
            event_meta[key] = value
        elif field == _PLANE_STAT_META:
            key, value = _map_entry(buf, v)
            stat_names[key] = next(
                (_text(buf, s) for f, s in _fields(buf, *value) if f == 2),
                "")
    wanted = {k for k, name in stat_names.items() if name == OP_NAME_STAT}

    def describe(span):
        """(an event metadata's name, its `tf_op` without the colon)."""
        name, op_name = "", None
        for field, v in _fields(buf, *span):
            if field == _META_NAME:
                name = _text(buf, v)
            elif field == _META_STATS:
                stat = dict(_fields(buf, *v))
                if stat.get(_STAT_META_ID) in wanted:
                    # a string, or a reference to a stat metadata's name
                    op_name = (_text(buf, stat[_STAT_STR])
                               if _STAT_STR in stat
                               else stat_names.get(stat.get(_STAT_REF), ""))
        if op_name is not None:
            op_name = op_name.rsplit(":", 1)[0]
        return name, op_name

    named = {}
    out = {"ops": [], "modules": []}
    for span in lines:
        key, origin_ns, events = None, 0, []
        for field, v in _fields(buf, *span):
            if field == _LINE_NAME:
                key = {trace_reduce.OPS_LINE: "ops",
                       trace_reduce.MODULES_LINE: "modules"}.get(
                           _text(buf, v))
            elif field == _LINE_TIMESTAMP_NS:
                origin_ns = v
            elif field == _LINE_EVENTS:
                events.append(v)
        if key is None:
            continue
        for ev in events:
            meta_id = offset_ps = duration_ps = 0
            for field, v in _fields(buf, *ev):
                if field == _EVENT_META_ID:
                    meta_id = v
                elif field == _EVENT_OFFSET_PS:
                    offset_ps = v
                elif field == _EVENT_DURATION_PS:
                    duration_ps = v
            if meta_id not in named:
                named[meta_id] = describe(event_meta[meta_id])
            name, op_name = named[meta_id]
            start_ns = origin_ns + offset_ps / 1e3
            if key == "ops":
                out["ops"].append([op_name, start_ns, duration_ps / 1e3,
                                   name])
            else:
                out["modules"].append([name, start_ns, duration_ps / 1e3])
    return out


# ---------------------------------------------------------------------------
# billing
# ---------------------------------------------------------------------------


def whole_calls(device: dict, pattern: str) -> list:
    """[start, end] of the calls of the programs whose name contains
    `pattern` that the trace holds WHOLE, by start: a call that begins
    before the first recorded operation or ends after the last one was cut
    by the trace's edge, and a part of its operations is missing."""
    ops = device["ops"]
    if not ops:
        return []
    first = min(s for _, s, _, _ in ops)
    last = max(s + d for _, s, d, _ in ops)
    calls = sorted([s, s + d] for n, s, d in device["modules"]
                   if pattern in n)
    # (a call's last operation ends a little before the call does)
    return [c for c in calls
            if c[0] >= first and c[1] - last <= 0.01 * (c[1] - c[0])]


def bill(device: dict, programs: dict) -> dict:
    """key -> {"calls", "busy_ns", "parts": {part: ns}, "unscoped": [[HLO
    name, op_name, ns], ...]} for every `key: name pattern` of `programs`:
    mean nanoseconds a whole call, by part (every part, 0.0 where no
    operation carried it; `UNSCOPED` last), `busy_ns` their sum, and the
    largest unscoped instructions. Self time: an operation's duration
    minus what the operations nested in it (a loop's body) cover."""
    ops = device["ops"]
    own = trace_reduce.self_times(
        [[i, s, d] for i, (_, s, d, _) in enumerate(ops)])
    out = {}
    for key, pattern in programs.items():
        calls = whole_calls(device, pattern)
        starts = [c[0] for c in calls]
        parts = dict.fromkeys(PARTS + (UNSCOPED,), 0.0)
        unscoped: dict = {}
        for i, (op_name, start, _, hlo) in enumerate(ops):
            at = bisect.bisect_right(starts, start) - 1
            if at < 0 or start >= calls[at][1]:
                continue
            part = part_of(op_name)
            parts[part] += own[i]
            if part == UNSCOPED:
                who = (trace_reduce.short_name(hlo), op_name)
                unscoped[who] = unscoped.get(who, 0.0) + own[i]
        n = max(len(calls), 1)
        top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:12]
        out[key] = {
            "calls": len(calls),
            "parts": {p: t / n for p, t in parts.items()},
            "busy_ns": sum(parts.values()) / n,
            "unscoped": [[hlo, name, t / n] for (hlo, name), t in top]}
    return out


def report(bills: dict) -> str:
    rows = []
    for key, b in bills.items():
        busy = max(b["busy_ns"], 1e-9)
        rows.append(f"{key}: {b['calls']} whole calls, busy "
                    f"{b['busy_ns'] / 1e6:.3f} ms a call")
        rows += [f"  {part:14s} {t / 1e6:9.4f} ms {100 * t / busy:6.2f}%"
                 for part, t in b["parts"].items() if t > 0]
        rows += [f"    {UNSCOPED} {t / 1e6:8.4f} ms  {hlo}  [{name}]"
                 for hlo, name, t in b["unscoped"]]
    return "\n".join(rows)


# ---------------------------------------------------------------------------
# what the readers call
# ---------------------------------------------------------------------------

def bills_of(run) -> dict | None:
    """`bill` of a traced run's capture under the cell's `programs`
    patterns, read ONCE a run (kept on the run's trace summary, which
    every reader is handed) and logged once; None where today's trace
    readers return None (no trace, no device plane: the CPU)."""
    if run.trace is None or not run.trace.device_names:
        return None
    if not hasattr(run.trace, "bills_by_part"):
        found = glob.glob(os.path.join(
            run.cell.work_dir(), "trace", "plugins", "profile", "*",
            "*.xplane.pb"))
        t0 = time.perf_counter()
        device = read_device(found[0]) if found else None
        run.trace.bills_by_part = (None if device is None else bill(
            device, run.cell.shape.get("programs", {})))
        if device is not None:
            log(f"device time by part ({len(device['ops'])} operations "
                f"read and billed in {time.perf_counter() - t0:.1f} s):\n"
                + report(run.trace.bills_by_part))
    return run.trace.bills_by_part


def part_ms(run, program: str, *prefixes: str):
    """Mean device milliseconds a whole call of the cell's program
    `programs.<program>` in the parts under `prefixes`; 0.0 (logged) where
    no operation carried them; None where the trace holds no whole call."""
    bills = bills_of(run)
    if not bills or not bills.get(program, {}).get("calls"):
        return None
    parts = bills[program]["parts"]
    value = sum(parts[p] for p in parts_matching(*prefixes)) / 1e6
    if value == 0.0:
        log(f"no operation of {program} carried a part under {prefixes} "
            "(a program read from a compile cache entry of an older build "
            "carries that build's metadata)")
    return value


def unscoped_share(run, *programs: str):
    """`UNSCOPED` self time of the whole calls of those programs over their
    busy time, in percent; None where the trace holds none of them."""
    bills = bills_of(run)
    if not bills:
        return None
    read = [bills[p] for p in programs if bills.get(p, {}).get("calls")]
    busy = sum(b["busy_ns"] * b["calls"] for b in read)
    if not busy:
        return None
    return 100.0 * sum(b["parts"][UNSCOPED] * b["calls"] for b in read) / busy


if __name__ == "__main__":
    device = read_device(sys.argv[1])
    print("no device plane" if device is None else report(bill(
        device, {pattern: pattern for pattern in sys.argv[2:]})))
