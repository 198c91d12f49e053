"""The one place that decides whether a Pallas kernel is compiled or interpreted.

A Pallas TPU kernel only *runs as a kernel* on a TPU. Everywhere else
(the CPU tests) it can run through the Pallas interpreter: exact, slow,
and no evidence that the chip's compiler accepts it. That choice used to
be made silently next to every `pallas_call`; it is made here, recorded,
and can be forbidden:

- `resolve_interpret(name, interpret)` is what every kernel wrapper
  calls. An explicit `interpret=True/False` from the caller wins;
  `None` means "compiled on a TPU, interpreted elsewhere". Either way
  the decision is recorded under `name`.
- `require_compiled()` makes any interpreted kernel an error from then
  on — `chip_smoke.py` and the bench turn it on, so a run that claims
  the chip can never have executed a kernel in the interpreter.
- `kernel_report()` returns {kernel name: "compiled" | "interpret"} for
  every kernel resolved (traced) so far in this process.
"""

from __future__ import annotations

import jax

_require_compiled = False
_report: dict[str, str] = {}


def require_compiled(on: bool = True) -> None:
    """From now on, resolving any kernel to interpret mode raises."""
    global _require_compiled
    _require_compiled = bool(on)


def kernel_report() -> dict[str, str]:
    return dict(_report)


def resolve_interpret(name: str, interpret: bool | None = None) -> bool:
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    interpret = bool(interpret)
    if interpret and _require_compiled:
        raise RuntimeError(
            f"pallas kernel {name!r} would run in interpret mode on backend "
            f"{jax.default_backend()!r}, and compiled kernels are required "
            "(ops.kernel_mode.require_compiled)")
    _report[name] = "interpret" if interpret else "compiled"
    return interpret
