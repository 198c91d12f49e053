"""The kernels of the main path, compiled for the chip without the chip.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (`on-chip-measurement` guide, section 2,
rehearsal 3). Interpret mode cannot see what it refuses: block shapes off
the (8, 128) tiling, kernels GSPMD cannot partition. These few compiles
guard every later PR at no chip time:

- paged decode attention, bf16 and int8 pools, at real widths;
- flash attention forward + backward at the bench shape (8 x 2048 x 12 x
  128, causal) and at the loss-sliced length 2047;
- flash attention under a 4-device mesh (`flash_attention_on_mesh`).

The topology is described ONLY inside this file's module-scoped fixture:
one process at a time may load the TPU's library, the xdist workers all
import every test file, and only the worker that is handed this file may
load it. So: nothing here touches the topology at import, in a `skipif`,
in `parametrize` or in `conftest.py`; the compiles run in the test's own
process; and all of them live in this one file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(monkeypatch):
    """What every compile in this file needs around it: kernels resolved
    to COMPILED mode (the process's default backend is the CPU, so the
    program's own choice would be the interpreter — steered here, in the
    test, not through an option of the program), and the persistent
    compilation cache off (a described-device entry can be written but
    never read back without a chip; the next run would warn)."""
    from jax.experimental.compilation_cache import compilation_cache

    from accelerate_tpu.ops import kernel_mode

    monkeypatch.setattr(kernel_mode, "resolve_interpret",
                        lambda name, interpret=None: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _assert_kernel_inside(compiled, at_least=1):
    assert compiled.as_text().count("tpu_custom_call") >= at_least


@pytest.mark.parametrize("hkv,group,d,quantized", [
    (2, 6, 128, False),   # Qwen2-1.5B: 12 heads over 2 KV heads
    (2, 6, 128, True),
    (8, 4, 128, True),    # Llama-3-8B: 32 heads over 8 KV heads
    (12, 1, 64, False),   # GPT-2: MHA, 64-wide heads
], ids=["qwen2-bf16", "qwen2-int8", "llama3-int8", "gpt2-bf16"])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, chip_compile, hkv,
                                              group, d, quantized):
    from accelerate_tpu.ops.paged_attention import (
        PagedDecodeMeta,
        PagedKV,
        paged_decode_attention,
    )

    slots, pages_per_slot, num_pages, page = 16, 64, 1024, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((num_pages + 1, hkv, page, d),
               jnp.int8 if quantized else jnp.bfloat16)
    scales = sds((num_pages + 1, hkv, page), jnp.bfloat16) if quantized \
        else None
    pk = PagedKV(pool, scales, jnp.bfloat16)
    meta = PagedDecodeMeta(sds((slots, pages_per_slot), jnp.int32),
                           sds((slots,), jnp.int32),
                           rows=pages_per_slot * page)
    q = sds((slots, 1, hkv * group, d), jnp.bfloat16)
    kn = sds((slots, 1, hkv, d), jnp.bfloat16)
    for window in (None, 256):
        compiled = jax.jit(
            lambda q, kn, vn, pk, pv, meta, window=window:
            paged_decode_attention(q, kn, vn, pk, pv, meta, window=window)[0]
        ).lower(q, kn, kn, pk, pk, meta).compile()
        _assert_kernel_inside(compiled)


@pytest.mark.parametrize("seq", [2048, 2047], ids=["bench-2048", "loss-2047"])
def test_flash_forward_backward_compiles_for_v5e(one_chip, chip_compile, seq):
    from accelerate_tpu.ops.flash_attention import flash_attention

    x = jax.ShapeDtypeStruct((8, seq, 12, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    _assert_kernel_inside(compiled, at_least=3)  # fwd, dQ, dK/dV
    # each kernel carries its name into the compiled program, which is how
    # a device trace shows it (`%flash_attention_fwd.N custom-call[...]`)
    text = compiled.as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv"):
        assert name in text, name


def test_flash_under_four_device_mesh_compiles_for_v5e(topo, chip_compile):
    """A Mosaic kernel cannot be partitioned by GSPMD; under a mesh the
    model calls `flash_attention_on_mesh`, which must compile on both
    4-device layouts chip_smoke.py --multichip runs."""
    from accelerate_tpu.ops.flash_attention import flash_attention_on_mesh

    devices = np.array(topo.devices)
    for shape, names, spec in (
            ((4,), ("fsdp",), P("fsdp", None, None, None)),
            ((2, 2), ("data", "model"), P("data", None, "model", None))):
        mesh = Mesh(devices.reshape(shape), names)
        x = jax.ShapeDtypeStruct((8, 2048, 12, 128), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, spec))

        def loss(q, k, v, mesh=mesh):
            return flash_attention_on_mesh(q, k, v, mesh, causal=True).astype(
                jnp.float32).sum()

        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= 3
        # batch and heads are split where they already live: nothing is
        # gathered around the kernel
        assert " all-gather(" not in text
