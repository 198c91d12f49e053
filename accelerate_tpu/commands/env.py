"""`accelerate-tpu env` — environment report (ref src/accelerate/commands/env.py, 109 LoC)."""

from __future__ import annotations

import argparse
import os
import platform
import subprocess
import sys

# Device probing honors a hard timeout: backend init can hang (a chip held
# by another process), and an environment report must never hang the
# terminal.
_PROBE_TIMEOUT = int(os.environ.get("ACCELERATE_TPU_ENV_PROBE_TIMEOUT", "60"))


def register_subcommand(subparsers) -> None:
    parser = subparsers.add_parser("env", help="Print environment information")
    parser.set_defaults(func=env_command)


def _probe_devices() -> tuple[str, str, str]:
    """(devices, backend, process_count) via a subprocess so a hung backend
    can be killed; respects ACCELERATE_TPU_USE_CPU."""
    code = (
        "import os\n"
        "if os.environ.get('ACCELERATE_TPU_USE_CPU', '').lower() in "
        "('1', 'true', 'yes'):\n"
        "    from accelerate_tpu.utils.environment import force_cpu_platform\n"
        "    force_cpu_platform()\n"
        "import jax\n"
        "print(', '.join(str(d) for d in jax.devices()))\n"
        "print(jax.default_backend())\n"
        "print(jax.process_count())\n"
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=_PROBE_TIMEOUT,
        )
    except subprocess.TimeoutExpired:
        return (f"<unreachable: backend init hung >{_PROBE_TIMEOUT}s>",
                "<unreachable>", "?")
    if out.returncode != 0:
        tail = (out.stderr or "").strip().splitlines()
        return (f"<init failed: {tail[-1][:120] if tail else 'no output'}>",
                "<failed>", "?")
    lines = out.stdout.strip().splitlines()
    return (lines[0] if lines else "?",
            lines[1] if len(lines) > 1 else "?",
            lines[2] if len(lines) > 2 else "?")


def env_command(args: argparse.Namespace) -> int:
    import jax

    import accelerate_tpu
    from accelerate_tpu.utils.imports import package_version

    devices, backend, nproc = _probe_devices()
    info = {
        "`accelerate_tpu` version": accelerate_tpu.__version__,
        "Platform": platform.platform(),
        "Python version": platform.python_version(),
        "JAX version": jax.__version__,
        "jaxlib version": package_version("jaxlib"),
        "flax version": package_version("flax"),
        "optax version": package_version("optax"),
        "orbax-checkpoint version": package_version("orbax-checkpoint"),
        "Devices": devices,
        "Default backend": backend,
        "Process count": nproc,
    }
    print("\nCopy-and-paste the text below in your GitHub issue\n")
    for key, value in info.items():
        print(f"- {key}: {value}")
    return 0
