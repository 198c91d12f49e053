"""ops/kernel_mode.py: the one place that decides compiled vs interpreted
Pallas kernels, records the decision, and can forbid the interpreter — plus
`flash_attention_on_mesh`, the shard_map wrapper that makes the flash
kernel legal under a multi-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from accelerate_tpu.ops import kernel_mode


@pytest.fixture(autouse=True)
def _reset_mode(monkeypatch):
    # process-wide switch: start every test from "off", restore after
    monkeypatch.setattr(kernel_mode, "_require_compiled", False)
    yield


def test_none_means_interpret_off_tpu_and_the_decision_is_recorded():
    assert kernel_mode.resolve_interpret("k_auto") is True  # CPU test backend
    assert kernel_mode.resolve_interpret("k_forced", False) is False
    report = kernel_mode.kernel_report()
    assert report["k_auto"] == "interpret" and report["k_forced"] == "compiled"


def test_require_compiled_turns_an_interpreted_kernel_into_an_error():
    kernel_mode.require_compiled()
    with pytest.raises(RuntimeError, match="interpret mode"):
        kernel_mode.resolve_interpret("flash_attention")
    with pytest.raises(RuntimeError, match="interpret mode"):
        kernel_mode.resolve_interpret("flash_attention", True)
    assert kernel_mode.resolve_interpret("flash_attention", False) is False


@pytest.mark.parametrize("kernel", ["flash", "paged"])
def test_kernel_wrappers_go_through_the_one_place(kernel):
    """Both kernels of the main path fail under require_compiled() on the
    CPU instead of quietly running in the interpreter."""
    kernel_mode.require_compiled()
    if kernel == "flash":
        from accelerate_tpu.ops.flash_attention import flash_attention

        x = jnp.ones((1, 32, 2, 16), jnp.float32)
        with pytest.raises(RuntimeError, match="flash_attention"):
            flash_attention(x, x, x, causal=True)
    else:
        from accelerate_tpu.ops.paged_attention import (
            PagedDecodeMeta,
            PagedKV,
            paged_decode_attention,
        )

        pool = PagedKV(jnp.zeros((1, 3, 2, 8, 16), jnp.float32),
                       layer=jnp.int32(0))
        meta = PagedDecodeMeta(jnp.zeros((1, 2), jnp.int32),
                               jnp.zeros((1,), jnp.int32), rows=16)
        q = jnp.ones((1, 1, 4, 16), jnp.float32)
        kn = jnp.ones((1, 1, 2, 16), jnp.float32)
        with pytest.raises(RuntimeError, match="paged_decode_attention"):
            paged_decode_attention(q, kn, kn, pool, pool, meta)


def test_no_kernel_wrapper_decides_interpret_mode_by_itself():
    """The per-call-site `jax.devices()[0].platform != "tpu"` fallbacks
    are gone from the three kernel wrappers."""
    import inspect

    from accelerate_tpu.ops import flash_attention as _  # noqa: F401
    import importlib

    for name in ("accelerate_tpu.ops.flash_attention",
                 "accelerate_tpu.ops.paged_attention",
                 "accelerate_tpu.parallel.ring_attention"):
        src = inspect.getsource(importlib.import_module(name))
        assert 'platform != "tpu"' not in src, name
        assert "kernel_mode.resolve_interpret(" in src, name


@pytest.mark.parametrize("axes,shape", [
    (("fsdp",), (4,)), (("data", "model"), (2, 2)), (("fsdp", "model"), (4, 2)),
], ids=["fsdp4", "data2-model2", "fsdp4-model2"])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "keymask"])
def test_flash_on_mesh_matches_einsum_attention(axes, shape, masked):
    """Per-shard kernel under shard_map == plain attention, forward and
    gradient, with batch split over the data-like axes and heads over
    `model` (interpret mode on the virtual CPU mesh)."""
    from accelerate_tpu.models.common import dot_product_attention
    from accelerate_tpu.ops.flash_attention import flash_attention_on_mesh

    n = int(np.prod(shape))
    mesh = Mesh(np.array(jax.devices()[:n]).reshape(shape), axes)
    rng = np.random.default_rng(0)
    b, s, h, d = 8, 32, 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
               for _ in range(3))
    mask = None
    if masked:
        mask = jnp.asarray(rng.integers(0, 2, (b, s)), jnp.int32).at[:, 0].set(1)
    lead = tuple(a for a in axes if a != "model")
    spec = P(lead if len(lead) > 1 else lead[0], None,
             "model" if "model" in axes else None, None)
    q, k, v = (jax.device_put(x, NamedSharding(mesh, spec)) for x in (q, k, v))

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    on_mesh = jax.jit(jax.value_and_grad(loss(
        lambda q, k, v: flash_attention_on_mesh(q, k, v, mesh, causal=True,
                                                mask=mask)), argnums=(0, 1, 2)))
    plain = jax.jit(jax.value_and_grad(loss(
        lambda q, k, v: dot_product_attention(q, k, v, mask=mask,
                                              causal=True)), argnums=(0, 1, 2)))
    (l1, g1), (l2, g2) = on_mesh(q, k, v), plain(q, k, v)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4)
    assert g1[0].sharding.spec == spec  # nothing resharded around the kernel


def test_flash_on_mesh_without_a_mesh_or_inside_shard_map_is_the_bare_kernel():
    from accelerate_tpu.models.common import dot_product_attention
    from accelerate_tpu.ops.flash_attention import flash_attention_on_mesh

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 32, 2, 16)), jnp.float32)
    want = dot_product_attention(x, x, x, causal=True)
    np.testing.assert_allclose(
        np.asarray(flash_attention_on_mesh(x, x, x, None, causal=True)),
        np.asarray(want), atol=2e-5)
    # already inside a shard_map over the same mesh (the pipeline stages):
    # no nested shard_map, the per-shard arrays go straight to the kernel
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    spec = P("data", None, None, None)
    inner = jax.shard_map(
        lambda q: flash_attention_on_mesh(q, q, q, mesh, causal=True),
        mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False)
    np.testing.assert_allclose(np.asarray(jax.jit(inner)(x)),
                               np.asarray(want), atol=2e-5)
