"""The device a run stands on: which chip, its published peaks, where the
compile cache lives, how many programs compiled, how full the memory got.

A run that finds no TPU, fewer chips than the cell asks for, or a device
kind that is not in `PEAKS` fails: nothing here falls back to another
backend or to an assumed peak.
"""

from __future__ import annotations

import os

# Published peaks of ONE chip, keyed by `jax.devices()[0].device_kind`.
# Source: Google Cloud documentation, "TPU v5e" system architecture page
# (197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2e at 819 GB/s).
_V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}  # two names JAX has given it


class NoChip(RuntimeError):
    pass


def describe(chips: int, require_chip: bool = True) -> dict:
    """`device` of the result line, as JAX reports it; raises `NoChip`
    unless `chips` TPU devices are there (tests pass require_chip=False
    and get the CPU described, never under a metric's name)."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}
    if require_chip:
        if d0.platform != "tpu":
            raise NoChip(f"no TPU: jax.devices()[0].platform is "
                         f"{d0.platform!r}")
        if len(devices) < chips:
            raise NoChip(f"the cell asks for {chips} chips, JAX shows "
                         f"{len(devices)}")
        if d0.device_kind not in PEAKS:
            raise NoChip(f"device kind {d0.device_kind!r} is not in the "
                         f"peaks table {sorted(PEAKS)}")
    info["count"] = chips if len(devices) >= chips else len(devices)
    return info


def peaks(kind: str) -> dict | None:
    return PEAKS.get(kind)


def configure_compile_cache(root: str) -> str:
    """JAX's persistent cache at a FIXED path: where
    `JAX_COMPILATION_CACHE_DIR` says, else `<checkout>/.jax_cache`. Every
    program is kept, however quickly it compiled, so that a cell's second
    run in a checkout finds all of them. Set before the program's own
    `configure_compilation_cache` runs, which then keeps this directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts XLA backend compilations and persistent-cache traffic from
    jax's own monitoring events."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_requests = 0
        self.cache_hits = 0
        self.compile_seconds = 0.0

        def on_duration(event: str, seconds: float, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_seconds += seconds

        def on_event(event: str, **_):
            if event == "/jax/compilation_cache/compile_requests_use_cache":
                self.cache_requests += 1
            elif event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips used (0 where the
    backend reports none, as on the CPU)."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def machine_counters() -> dict:
    """Seconds the machine itself took from this run so far, where Linux
    tells: CPU time stolen from the guest (all cores, /proc/stat) and the
    pressure-stall totals (/proc/pressure: some task waited for CPU,
    memory or I/O). Read when the window opens and closes; a run whose
    process stood still for seconds shows here whether the machine was
    the cause. Missing files give missing keys."""
    out: dict = {}
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        out["cpu_steal_s"] = int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        pass
    for kind in ("cpu", "memory", "io"):
        try:
            with open(f"/proc/pressure/{kind}") as f:
                some = f.readline()
            out[f"{kind}_pressure_s"] = int(some.rsplit("total=", 1)[1]) / 1e6
        except (OSError, IndexError, ValueError):
            pass
    return out
