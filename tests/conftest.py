"""Test harness: a virtual 8-device CPU mesh stands in for a TPU slice.

This replaces the reference's `debug_launcher` gloo world
(ref launchers.py:225-257, SURVEY.md §4): distributed semantics run in one
process over 8 XLA host devices, so sharding/collective logic is exercised
without hardware.
"""

import functools
import os

# Must be set before jax initializes its backends.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402
import pytest  # noqa: E402

# JAX_PLATFORMS=cpu (set above, before the import) is all this installation
# needs: the tests always run on the virtual 8-device CPU mesh.
assert len(jax.devices()) == 8, f"expected 8 CPU devices, got {jax.devices()}"

# The persistent compilation cache is OFF for the suite, whatever the
# environment says (JAX_COMPILATION_CACHE_DIR included): few of the suite's
# compiles cross jax's >=1s write threshold, so it buys little, and nobody
# has yet shown on this installation (jax/jaxlib 0.9.0) that a suite run
# executing freshly deserialized CPU entries mid-session is safe. Modules
# that want a scoped cache pass one explicitly (their fixtures) and hand
# the process back with it off. (Export ACCELERATE_TPU_COMPILATION_CACHE=
# <dir> to opt children back in.)
from accelerate_tpu.utils.constants import ENV_COMPILATION_CACHE  # noqa: E402
from accelerate_tpu.utils.environment import configure_compilation_cache  # noqa: E402

os.environ.setdefault(ENV_COMPILATION_CACHE, "off")
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)  # children: the repo var decides
configure_compilation_cache(os.environ[ENV_COMPILATION_CACHE])

# Serving-state sanitizer (ISSUE 13): every engine the suite builds
# validates its cross-structure invariants (page conservation, refcount
# closure, table discipline, scheduler books) after each step — the
# whole serving/speculative/pod surface runs sanitized in tier-1.
# Host-side only; compile counts are pinned flat with this on.
os.environ.setdefault("ACCELERATE_TPU_SANITIZE", "1")

# Runtime lock-order sanitizer (ISSUE 19): transport / host-tier /
# metrics-registry locks become TrackedLocks recording per-thread
# acquisition order into a process-wide graph — a would-deadlock
# ordering raises LockOrderViolation instead of wedging the suite.
# Same split as the sanitizer above: the ATP3xx static pass proves what
# it can name, lockwatch catches the orderings only runtime sees.
os.environ.setdefault("ACCELERATE_TPU_LOCKWATCH", "1")


def pytest_collection_modifyitems(config, items):
    """Gate @pytest.mark.slow behind RUN_SLOW=1 (ref testing.py slow
    decorator semantics)."""
    if os.environ.get("RUN_SLOW", "0").lower() in ("1", "true", "yes"):
        return
    skip_slow = pytest.mark.skip(reason="slow test; set RUN_SLOW=1 to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)


@pytest.fixture(autouse=True)
def reset_state():
    """Clear the shared-state singletons between tests
    (ref test_utils/testing.py:394-439 AccelerateTestCase)."""
    from accelerate_tpu.state import PartialState

    PartialState._reset_state()
    yield
    PartialState._reset_state()
    # the tracing switch that NO test's own teardown puts back: a dozen
    # tests turn the forwarding of span names to the profiler off
    # (`configure_tracing(..., annotate=False)`), and a traced benchmark
    # cell that the same worker's process runs later (tests/chipbench)
    # then finds no span on the profiler's host plane. Which file precedes
    # which in a worker follows the whole suite's file list, so the
    # failure came and went with unrelated PRs.
    from accelerate_tpu.telemetry.trace import (
        configure_tracing,
        tracing_enabled,
    )

    configure_tracing(enabled=tracing_enabled(), annotate=True)


@pytest.fixture
def devices():
    return jax.devices()


# ---------------------------------------------------------------------------
# forced-host-device subprocess harness (pod-scale serving tests)
# ---------------------------------------------------------------------------

_FORCED_DEVICE_PROBE_CODE = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
sys.exit(0 if jax.device_count() == int(sys.argv[1]) else 7)
"""


@functools.lru_cache()
def _forced_device_unsupported(n: int) -> str | None:
    """None when this jaxlib can stand up an N-forced-host-device CPU
    backend in a fresh process, else a skip reason. Probed ONCE per
    session per N with a minimal import (same spirit as
    test_utils.multiprocess_backend_supported): some jaxlib builds
    ignore the flag or wedge at backend init on exotic CPUs, and a pod
    test must skip with a reason rather than fail collection or hang."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _FORCED_DEVICE_PROBE_CODE, str(n)],
            env=env, capture_output=True, text=True, timeout=120,
            start_new_session=True)
    except subprocess.TimeoutExpired:
        return f"jaxlib wedged initializing a {n}-forced-device CPU backend"
    if proc.returncode == 7:
        return f"jaxlib ignores xla_force_host_platform_device_count={n}"
    if proc.returncode != 0:
        tail = (proc.stderr or proc.stdout).strip().splitlines()
        return (f"{n}-forced-device probe failed (rc={proc.returncode}): "
                f"{tail[-1][:200] if tail else 'no output'}")
    return None


@pytest.fixture
def forced_device_run():
    """Run a python script in a subprocess pinned to EXACTLY `n_devices`
    forced host CPU devices (`XLA_FLAGS=--xla_force_host_platform_
    device_count=N` + JAX_PLATFORMS=cpu). Skips with a reason when this jaxlib can't force that
    device count; kills the whole process group on timeout so a wedged
    backend never hangs the suite. Returns the child's stdout."""
    from accelerate_tpu.test_utils import execute_subprocess

    def run(script_path: str, n_devices: int, args=(), timeout: int = 600):
        reason = _forced_device_unsupported(n_devices)
        if reason is not None:
            pytest.skip(reason)
        import sys

        env = {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS":
                f"--xla_force_host_platform_device_count={n_devices}",
        }
        return execute_subprocess(
            [sys.executable, script_path, *map(str, args)], env=env,
            timeout=timeout)

    return run
