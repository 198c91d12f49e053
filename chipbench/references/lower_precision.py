"""The controls' lower precision, kept outside the plain references.

A control is a configuration's plain reference put in the program's place
and computed one step below the precision the configuration states (fp8
for bfloat16). The reference itself knows nothing of it: `rounded_copy`
loads a SECOND copy of the reference's file whose `jnp` rounds both
operands of every `jnp.matmul` and `jnp.einsum` and is `jax.numpy` in
everything else. The copy the harness compares against stays untouched.
"""

from __future__ import annotations

import importlib.util

import jax
import jax.numpy as jnp


def fp8_operand(x):
    """A matmul operand to float8 e4m3 with one scale a tensor, and back.
    The backward pass sees the identity (a straight-through estimate), as
    scaled fp8 training does: without it the cast would flush small
    cotangents to zero."""
    x32 = jax.lax.stop_gradient(x.astype(jnp.float32))
    scale = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-30) / 448.0
    rounded = ((x32 / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
               * scale).astype(x.dtype)
    return x + jax.lax.stop_gradient(rounded - x)


class _RoundedNumpy:
    """`jax.numpy` with the operands of its two matrix products rounded."""

    def __init__(self, round_operand):
        self._round = round_operand

    def __getattr__(self, name):
        return getattr(jnp, name)

    def matmul(self, a, b):
        return jnp.matmul(self._round(a), self._round(b))

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self._round(a), self._round(b))


def rounded_copy(path: str, round_operand=fp8_operand):
    """The reference at `path`, loaded again under another name, with
    every matrix product's operands rounded by `round_operand`."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_control_" + path.rsplit("/", 1)[-1].removesuffix(".py"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.jnp = _RoundedNumpy(round_operand)
    return module
