"""Environment parsing helpers.

TPU-native analogue of ref src/accelerate/utils/environment.py (274 LoC):
bool/int env parsing, env patching, and launch-context discovery. GPU probing
and NUMA affinity are replaced by TPU topology introspection via JAX.
"""

from __future__ import annotations

import contextlib
import os
from typing import Any, Iterator

_TRUE = {"1", "true", "yes", "on", "y", "t"}
_FALSE = {"0", "false", "no", "off", "n", "f", ""}


def str_to_bool(value: str) -> bool:
    """Parse a boolean env value (ref utils/environment.py:31-44)."""
    v = value.strip().lower()
    if v in _TRUE:
        return True
    if v in _FALSE:
        return False
    raise ValueError(f"invalid truth value {value!r}")


def parse_flag_from_env(key: str, default: bool = False) -> bool:
    value = os.environ.get(key)
    if value is None:
        return default
    return str_to_bool(value)


def parse_int_from_env(key: str, default: int | None = None) -> int | None:
    value = os.environ.get(key)
    if value is None:
        return default
    return int(value)


def get_int_from_env(keys, default: int | None = None) -> int | None:
    """First int found among ``keys`` (ref utils/environment.py:200-219 MPI
    variable discovery: PMI_RANK / OMPI_COMM_WORLD_RANK / ...)."""
    for key in keys:
        value = os.environ.get(key)
        if value is not None:
            return int(value)
    return default


def set_virtual_host_devices(n: int, env: dict | None = None) -> None:
    """Set (substituting any existing count) the XLA flag that fakes ``n``
    host CPU devices — the no-hardware stand-in for a TPU slice
    (SURVEY.md §4: replaces the reference's gloo debug_launcher worlds).

    Must run before the process's JAX backend initializes. When ``env`` is
    a partial overlay dict (launcher child-env assembly), the substitution
    starts from the PARENT's XLA_FLAGS — otherwise the overlay would later
    replace the inherited variable wholesale and silently drop every other
    XLA flag the parent had set (e.g. --xla_dump_to).
    """
    import re

    env = os.environ if env is None else env
    flags = env.get("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    want = f"--xla_force_host_platform_device_count={n}"
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", want, flags)
    else:
        flags = f"{flags} {want}".strip()
    env["XLA_FLAGS"] = flags


def force_cpu_platform() -> bool:
    """Force JAX onto the host CPU platform from INSIDE a process that has
    already imported jax (`Accelerator(cpu=True)`, the dry runs): the
    JAX_PLATFORMS variable is read when jax is imported, so after that the
    choice has to go through `jax.config`. A process that can set
    JAX_PLATFORMS=cpu before importing jax needs nothing else. Returns
    False if a backend is already initialized — at that point the platform
    can no longer change in this process."""
    import jax

    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        jax.config.update("jax_platforms", "cpu")
        return True
    except RuntimeError:
        return False


_compilation_cache_dir_applied: str | None = None


def default_compilation_cache_dir() -> str:
    """`<checkout>/.jax_cache`: ONE fixed directory next to the package
    (git-ignored). The path is part of every cache key, so it is never
    built from a temporary name, a process id or the time — two
    processes of one checkout always agree on it."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def _apply_cache_thresholds() -> None:
    """Forward the threshold env overrides to the matching jax knobs."""
    import jax

    from .constants import (
        ENV_COMPILATION_CACHE_MIN_COMPILE_SECS,
        ENV_COMPILATION_CACHE_MIN_ENTRY_BYTES,
    )

    min_secs = os.environ.get(ENV_COMPILATION_CACHE_MIN_COMPILE_SECS)
    if min_secs is not None:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", float(min_secs))
    min_bytes = os.environ.get(ENV_COMPILATION_CACHE_MIN_ENTRY_BYTES)
    if min_bytes is not None:
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes", int(min_bytes))


def configure_compilation_cache(
    cache_dir: str | None = None, force: bool = False
) -> str | None:
    """Wire jax's persistent compilation cache so relaunches deserialize
    executables instead of recompiling (minutes of XLA work at real model
    sizes; the dominant cost of a restart on TPU pods).

    Resolution: explicit ``cache_dir`` arg (scoped caches of test
    fixtures; ``off`` disables) > ``JAX_COMPILATION_CACHE_DIR`` (jax reads
    it itself at import: the cache is kept THERE and this function points
    jax at no other path) > ``ACCELERATE_TPU_COMPILATION_CACHE`` env (a
    dir, or ``0``/``off``/``false``/``none`` to disable) > a
    ``jax_compilation_cache_dir`` the user already configured (left
    untouched) > `default_compilation_cache_dir()`, the fixed
    ``<checkout>/.jax_cache``. Threshold overrides
    ``ACCELERATE_TPU_COMPILATION_CACHE_MIN_COMPILE_SECS`` / ``_MIN_ENTRY_BYTES``
    forward to the matching jax knobs (jax's defaults otherwise: entries
    cheaper than ~1 s of compile are not persisted).

    Safe to call any time — including after compiles have already happened:
    jax memoizes "is the cache in use" at first compile, so when the dir
    changes the cache state is reset to re-evaluate. Returns the active dir,
    or None when disabled. Idempotent per resolved dir unless ``force``.
    """
    global _compilation_cache_dir_applied
    from .constants import ENV_COMPILATION_CACHE

    _OFF = {"0", "off", "false", "no", "none", "disabled"}
    if cache_dir is None:
        placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
        if placed:
            # placed from outside: jax already holds this dir (it reads
            # the variable at import) and nothing here may move it
            import jax

            _apply_cache_thresholds()
            return jax.config.jax_compilation_cache_dir or placed
        cache_dir = os.environ.get(ENV_COMPILATION_CACHE)
    if cache_dir is not None:
        cache_dir = cache_dir.strip()
        if cache_dir.lower() in _OFF:
            # actively un-wire a previously-enabled cache: callers that
            # force-enable a scoped cache (test fixtures) must be able to
            # hand the process back with caching genuinely off, not just
            # decline to enable it again
            import jax

            if jax.config.jax_compilation_cache_dir:
                jax.config.update("jax_compilation_cache_dir", None)
                from jax.experimental.compilation_cache import compilation_cache

                compilation_cache.reset_cache()
            _compilation_cache_dir_applied = None
            return None
        if not cache_dir:
            # `ACCELERATE_TPU_COMPILATION_CACHE= python ...` means "unset",
            # not "use the cwd" (abspath("") is the launch directory)
            cache_dir = None
    import jax

    if cache_dir is None:
        existing = jax.config.jax_compilation_cache_dir
        if existing:
            # user already configured jax directly: keep their dir, but the
            # threshold env overrides still apply
            _apply_cache_thresholds()
            return existing
        cache_dir = default_compilation_cache_dir()
    cache_dir = os.path.abspath(os.path.expanduser(cache_dir))
    if cache_dir == _compilation_cache_dir_applied and not force:
        _apply_cache_thresholds()
        return cache_dir
    try:
        os.makedirs(cache_dir, exist_ok=True)
    except OSError:
        return None  # unwritable cache location (read-only HOME): skip
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    _apply_cache_thresholds()
    # jax checks cache usability once, at the first compile, and memoizes the
    # answer — a process that already compiled something (test suites, REPL
    # exploration before Accelerator()) would otherwise silently keep "no
    # cache" forever. reset_cache() drops that memo; the next compile
    # re-initializes against the dir configured above.
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()
    _compilation_cache_dir_applied = cache_dir
    return cache_dir


@contextlib.contextmanager
def patch_environment(**kwargs: Any) -> Iterator[None]:
    """Temporarily set env vars; restores previous values on exit
    (ref utils/other.py:246)."""
    saved: dict[str, str | None] = {}
    for key, value in kwargs.items():
        key = key.upper()
        saved[key] = os.environ.get(key)
        if value is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = str(value)
    try:
        yield
    finally:
        for key, old in saved.items():
            if old is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = old


def parse_mesh_shape(spec: str) -> dict[str, int]:
    """Parse ``"data=8,model=4"`` / ``"8x4"``-style mesh specs into an ordered
    ``{axis: size}`` dict. ``-1`` means "infer from device count"."""
    spec = spec.strip()
    if not spec:
        return {}
    axes: dict[str, int] = {}
    if "=" in spec:
        for part in spec.split(","):
            name, _, size = part.partition("=")
            axes[name.strip()] = int(size)
    else:
        from .constants import MESH_AXES

        sizes = [int(s) for s in spec.replace("x", ",").split(",")]
        for name, size in zip(MESH_AXES, sizes):
            axes[name] = size
    return axes


def format_mesh_shape(axes: dict[str, int]) -> str:
    return ",".join(f"{k}={v}" for k, v in axes.items())
