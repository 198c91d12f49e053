"""Per-program device-cost attribution: roofline telemetry + goodput.

The repo could *time* things (StepTimer, serving histograms) but could
not say what the hardware was DOING with that time: the serving engine
timed host dispatch only, and MFU math existed for training steps alone.
This module owns the missing layer (ISSUE 11):

- a **static cost table**: FLOPs / bytes-accessed captured ONCE per
  compiled program from `cost_analysis()` on the jax Lowered/Compiled
  stage (tracing cost only — never an extra XLA compile), with an
  analytic per-family fallback for backends that report nothing. Entries
  export as registry gauges (`program_flops{program=...}` etc.) so the
  Prometheus endpoint, JSONL snapshots, and incident bundles all see
  them.
- **sampled device-time measurement**: every Kth call per program pays a
  `block_until_ready` fence pair around the dispatch and records the
  true wall duration of that one program into a
  `program_device_time_seconds{program=...}` streaming histogram. All
  other calls pay one integer increment. The programs themselves are
  untouched — sampling is host-side, so compile counts stay flat.
- **roofline derivation**: cost table x measured device time -> MFU,
  HBM-bandwidth utilization, arithmetic intensity, and the MXU-idle
  fraction (1 - MFU, the number ROADMAP item 1's speculative-decoding
  case is built on), per program, as gauges and in `roofline()` dicts.

Peaks come from the public TPU spec tables; non-TPU backends get NOMINAL
placeholder peaks so smoke runs still produce non-null, run-over-run
comparable numbers (`peaks_nominal=True` marks them — absolute
utilization off-TPU is a smoke reading, not a hardware claim).

No jax imports at module level — `accelerate_tpu.telemetry` must import
without touching a backend; `device_peaks()`/`fence()` import lazily.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import threading
from typing import Any, Callable

from .registry import MetricsRegistry, StreamingHistogram

__all__ = [
    "ProgramCost",
    "CostTable",
    "device_peaks",
    "extract_cost_analysis",
    "fence",
    "resolve_sample_every",
    "COST_SAMPLE_EVERY_ENV",
    "NOMINAL_PEAK_FLOPS",
    "NOMINAL_PEAK_HBM_BYTES",
    "TPU_PEAK_HBM_BYTES",
]

COST_SAMPLE_EVERY_ENV = "ACCELERATE_TPU_COST_SAMPLE_EVERY"

# Nominal peaks for NON-TPU backends only (the CPU rehearsals): roofline
# arithmetic stays exercised there, and `peaks_nominal` marks the output
# as placeholders, not hardware claims. An unknown TPU kind is an error
# (`device_peaks`), never these.
NOMINAL_PEAK_FLOPS = 1e12
NOMINAL_PEAK_HBM_BYTES = 100e9

# TPU generations -> peak HBM bandwidth bytes/s per chip (public specs;
# the FLOPs half of the roofline lives in utils.constants.TPU_PEAK_FLOPS).
TPU_PEAK_HBM_BYTES = {
    "v4": 1.2e12,
    "v5e": 0.82e12,
    "v5 lite": 0.82e12,
    "v5p": 2.77e12,
    "v6e": 1.64e12,
}


def resolve_sample_every(explicit: int | None = None,
                         default: int = 16) -> int:
    """Sampling cadence: explicit kwarg wins, else the env var, else the
    default. 0 disables device-time sampling (the cost table still
    captures static costs)."""
    if explicit is not None:
        return int(explicit)
    raw = os.environ.get(COST_SAMPLE_EVERY_ENV, "").strip()
    if not raw:
        return default
    return int(raw)


def device_peaks(device=None) -> tuple[float, float, bool]:
    """(peak_flops, peak_hbm_bytes_per_s, nominal) for this chip.
    TPU generations resolve from the public spec tables, and a TPU whose
    `device_kind` is in neither table is an ERROR (a roofline against an
    assumed peak is a made-up number). Only a non-TPU backend (the CPU
    rehearsals) gets the NOMINAL placeholders, with nominal=True."""
    import jax

    from ..utils.constants import tpu_peak_flops

    device = device or jax.devices()[0]
    if device.platform != "tpu":
        return NOMINAL_PEAK_FLOPS, NOMINAL_PEAK_HBM_BYTES, True
    kind = getattr(device, "device_kind", "")
    flops = tpu_peak_flops(kind)
    for key, hbm in TPU_PEAK_HBM_BYTES.items():
        if key in kind.lower():
            return flops, hbm, False
    raise ValueError(
        f"no peak HBM bandwidth known for device kind {kind!r}; add it to "
        "TPU_PEAK_HBM_BYTES with its source")


def fence(tree: Any) -> None:
    """Block until every array in `tree` is ready (the sampling fence).
    Best-effort: a tree with no blockable leaves is a no-op, and a
    backend error must never take the serving loop down for a telemetry
    sample."""
    try:
        import jax

        jax.block_until_ready(tree)
    except Exception:
        pass


def extract_cost_analysis(obj: Any) -> tuple[float, float] | None:
    """(flops, bytes_accessed) from a jax Lowered/Compiled stage (or the
    dict / list-of-dicts its `cost_analysis()` returns directly). None
    when the backend reports nothing usable — callers fall back to the
    analytic estimate."""
    ca = obj
    if hasattr(obj, "cost_analysis"):
        try:
            ca = obj.cost_analysis()
        except Exception:
            return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    try:
        flops = float(ca.get("flops") or 0.0)
        nbytes = float(ca.get("bytes accessed") or 0.0)
    except (TypeError, ValueError):
        return None
    if flops <= 0.0 and nbytes <= 0.0:
        return None
    return flops, nbytes


@dataclasses.dataclass
class ProgramCost:
    """Static per-program cost: FLOPs and bytes accessed per call.
    `source` records where the numbers came from ("cost_analysis" = the
    backend reported them, "analytic" = the per-family fallback)."""

    name: str
    flops: float
    bytes_accessed: float
    source: str = "cost_analysis"

    @property
    def arith_intensity(self) -> float:
        """FLOPs per byte accessed — which roofline regime the program
        lives in (decode is memory-bound: intensity far below the
        machine balance point)."""
        if self.bytes_accessed <= 0:
            return math.nan
        return self.flops / self.bytes_accessed


class CostTable:
    """Static program costs + sampled device-time sketches + rooflines.

    One table per engine (sharing the engine's registry) or per process
    (the Accelerator's). All series are registry-backed and labeled
    `{program="<name>"}`, so the Prometheus endpoint, JSONL snapshots,
    and `telemetry.aggregate`'s cross-host merge see them with zero
    extra wiring:

    - gauges `program_flops` / `program_bytes_accessed` /
      `program_arith_intensity` (static, set at registration),
    - histogram `program_device_time_seconds` (sampled),
    - gauges `program_mfu` / `program_hbm_bw_util` /
      `program_mxu_idle_fraction` (derived, refreshed per sample).

    Sampling cadence: per program, call 1 is never sampled (it is the
    trace+compile call) — samples land on call 2 and every
    `sample_every`-th call after, so short smokes still get readings.
    """

    def __init__(self, registry: MetricsRegistry | None = None,
                 sample_every: int = 16,
                 peaks: tuple[float, float, bool] | None = None,
                 num_chips: int | Callable[[], int] | None = None,
                 clock: Callable[[], float] | None = None):
        self.registry = registry or MetricsRegistry()
        self.sample_every = max(0, int(sample_every))
        self._peaks = peaks
        # the utilization denominator is peak x num_chips, matching the
        # FLOPs side: registrations must come from PRE-partition stages
        # (Lowered / analytic — GLOBAL FLOPs), so a meshed program's MFU
        # divides global FLOPs by the whole mesh's peak, not one chip's.
        # A callable defers resolution (e.g. jax.device_count) past the
        # jax-free import of this module; None = 1 chip.
        self._num_chips = num_chips
        self._lock = threading.Lock()
        self._entries: dict[str, ProgramCost] = {}
        self._calls: dict[str, int] = {}
        if clock is None:
            import time

            clock = time.perf_counter
        self.clock = clock

    # -- static costs --------------------------------------------------------

    @property
    def entries(self) -> dict[str, ProgramCost]:
        return dict(self._entries)

    def has(self, name: str) -> bool:
        return name in self._entries

    @property
    def peaks(self) -> tuple[float, float, bool]:
        if self._peaks is None:
            self._peaks = device_peaks()
        return self._peaks

    @property
    def num_chips(self) -> int:
        if callable(self._num_chips):
            self._num_chips = max(1, int(self._num_chips()))
        return self._num_chips or 1

    def register(self, name: str, cost_source: Any = None, *,
                 flops: float | None = None,
                 bytes_accessed: float | None = None,
                 fallback: Callable[[], tuple[float, float]] | None = None,
                 replace: bool = False) -> ProgramCost | None:
        """Record one compiled program's static cost. Resolution order:
        explicit flops/bytes kwargs, then `cost_source` (a Lowered /
        Compiled stage — its `cost_analysis()` is consulted), then the
        zero-arg `fallback` returning an analytic (flops, bytes)
        estimate. Callers key on their own compile caches (the AOT /
        strict-audit key discipline) so a program is captured once, not
        per dispatch; re-registering an existing name is a no-op unless
        `replace=True` (a train step warmed for a new batch shape).
        Returns the entry, or None when nothing could be resolved."""
        if not replace and name in self._entries:
            return self._entries[name]
        source = "explicit"
        resolved: tuple[float, float] | None = None
        if flops is not None or bytes_accessed is not None:
            resolved = (float(flops or 0.0), float(bytes_accessed or 0.0))
        if resolved is None and cost_source is not None:
            resolved = extract_cost_analysis(cost_source)
            source = "cost_analysis"
        if resolved is None and fallback is not None:
            try:
                fb = fallback()
            except Exception:
                fb = None
            if fb is not None:
                resolved = (float(fb[0]), float(fb[1]))
                source = "analytic"
        if resolved is None:
            return None
        entry = ProgramCost(name, resolved[0], resolved[1], source)
        with self._lock:
            self._entries[name] = entry
        self._publish_entry(entry)
        return entry

    def _publish_entry(self, entry: ProgramCost) -> None:
        r = self.registry
        r.gauge("program_flops", program=entry.name).set(entry.flops)
        r.gauge("program_bytes_accessed",
                program=entry.name).set(entry.bytes_accessed)
        ai = entry.arith_intensity
        if ai == ai:
            r.gauge("program_arith_intensity", program=entry.name).set(ai)

    def republish(self) -> None:
        """Re-set the static gauges after a registry reset (a metrics
        reset zeroes series in place; the cost of a compiled program did
        not change because the operator dropped a warmup window)."""
        for entry in list(self._entries.values()):
            self._publish_entry(entry)

    # -- sampled device time -------------------------------------------------

    def sample_due(self, name: str) -> bool:
        """Count one call of `name`; True when this call should be
        fence-timed. Call 1 (trace+compile) is never sampled; call 2 and
        every `sample_every`-th call after are."""
        if self.sample_every <= 0:
            return False
        with self._lock:
            n = self._calls.get(name, 0) + 1
            self._calls[name] = n
        if n < 2:
            return False
        return (n - 2) % self.sample_every == 0

    def calls(self, name: str) -> int:
        return self._calls.get(name, 0)

    @contextlib.contextmanager
    def maybe_sample(self, name: str, fence_in: Any = None):
        """Fence-pair timing for one dispatch when a sample is due::

            with table.maybe_sample("decode", fence_in=cache) as sample:
                out = program(*args)
                sample(out)   # no-op when this call isn't sampled

        Entering drains `fence_in` (prior in-flight work must not leak
        into this program's window); calling the yielded function blocks
        on the outputs and records the duration. The measured window
        includes the host dispatch of the one call — at sampled cadence
        that bias is the dispatch cost StepTimer already meters."""
        if not self.sample_due(name):
            yield lambda out: None
            return
        if fence_in is not None:
            fence(fence_in)
        t0 = self.clock()
        done = {"recorded": False}

        def sample(out: Any) -> None:
            if done["recorded"]:
                return
            done["recorded"] = True
            fence(out)
            self.record_device_time(name, self.clock() - t0)

        yield sample

    def device_time(self, name: str) -> StreamingHistogram:
        return self.registry.histogram("program_device_time_seconds",
                                       program=name)

    def mean_device_time(self, name: str) -> float | None:
        hist = self.device_time(name)
        if not hist.count:
            return None
        return hist.mean

    def record_device_time(self, name: str, seconds: float) -> None:
        """One measured device duration; refreshes the derived roofline
        gauges from this sample (the `roofline()` dict uses the running
        mean instead)."""
        seconds = float(seconds)
        self.device_time(name).record(seconds)
        entry = self._entries.get(name)
        if entry is None or seconds <= 0:
            return
        peak_f, peak_b, _nominal = self.peaks
        chips = self.num_chips
        r = self.registry
        mfu = entry.flops / seconds / (peak_f * chips)
        r.gauge("program_mfu", program=name).set(mfu)
        r.gauge("program_mxu_idle_fraction",
                program=name).set(min(1.0, max(0.0, 1.0 - mfu)))
        r.gauge("program_hbm_bw_util", program=name).set(
            entry.bytes_accessed / seconds / (peak_b * chips))

    # -- rooflines -----------------------------------------------------------

    def roofline(self, name: str) -> dict[str, float] | None:
        """The program's roofline sheet: static costs, measured device
        time (mean/p50/p99 over the samples), and the derived MFU /
        HBM-bandwidth utilization / MXU-idle fraction against the chip
        peaks. None when nothing is known about `name`."""
        entry = self._entries.get(name)
        hist = self.device_time(name)
        if entry is None and not hist.count:
            return None
        out: dict[str, float] = {}
        if entry is not None:
            out["flops"] = entry.flops
            out["bytes_accessed"] = entry.bytes_accessed
            ai = entry.arith_intensity
            if ai == ai:
                out["arith_intensity"] = ai
            out["cost_source"] = entry.source  # type: ignore[assignment]
        if hist.count:
            mean = hist.mean
            out["device_time_mean_s"] = mean
            out["device_time_p50_s"] = hist.quantile(0.5)
            out["device_time_p99_s"] = hist.quantile(0.99)
            out["device_time_samples"] = float(hist.count)
            if entry is not None and mean > 0:
                peak_f, peak_b, nominal = self.peaks
                chips = self.num_chips
                mfu = entry.flops / mean / (peak_f * chips)
                out["mfu"] = mfu
                out["mxu_idle_fraction"] = min(1.0, max(0.0, 1.0 - mfu))
                out["hbm_bw_util"] = (
                    entry.bytes_accessed / mean / (peak_b * chips))
                out["peaks_nominal"] = float(nominal)
        return out

    def summary(self) -> dict[str, dict[str, float]]:
        """name -> roofline() for every known program."""
        names = set(self._entries) | set(self._calls)
        out = {}
        for name in sorted(names):
            sheet = self.roofline(name)
            if sheet:
                out[name] = sheet
        return out

    def snapshot(self) -> dict:
        """JSON-safe dump for incident bundles: the static table, per-
        program call/sample counts, and the derived rooflines — what the
        device was doing with its time, frozen at the incident."""
        peaks: dict[str, Any] = {}
        if self._peaks is not None:  # never force a backend probe here
            peaks = {"peak_flops": self._peaks[0],
                     "peak_hbm_bytes_per_s": self._peaks[1],
                     "nominal": self._peaks[2]}
            if isinstance(self._num_chips, int):
                peaks["num_chips"] = self._num_chips
        return {
            "sample_every": self.sample_every,
            "peaks": peaks,
            "programs": {
                name: dict(dataclasses.asdict(entry),
                           calls=self._calls.get(name, 0))
                for name, entry in sorted(self._entries.items())
            },
            "rooflines": self.summary(),
        }
