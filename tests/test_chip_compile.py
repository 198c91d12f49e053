"""The kernels of the main path, compiled for the chip without the chip.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (`on-chip-measurement` guide, section 2,
rehearsal 3). Interpret mode cannot see what it refuses: block shapes off
the (8, 128) tiling, kernels GSPMD cannot partition. These few compiles
guard every later PR at no chip time:

- paged decode attention, bf16 and int8 pools, at real widths, on the
  whole stacked pool and a layer index;
- flash attention forward + backward at the bench shape (8 x 2048 x 12 x
  128, causal) and at the loss-sliced length 2047;
- flash attention under a 4-device mesh (`flash_attention_on_mesh`);
- the serving engine's own `decode` and `prefill` programs at the chat
  cell's shape, bf16 and int8 pools: the KV pool is updated in place,
  and `decode` on a bf16 pool slices no layer out of it and holds the
  same temporaries at 4,096 and 16,384 pages;
- the same two programs for the latent-attention expert model at the
  shape of `serve-joyai-flash-docqa-long`: the latent kernel and the
  grouped expert products compile (XLA's `ragged-dot` over a chunk's
  rows, the rows kernel over a decode step's), the one-array pool is
  updated in place, no expert matrix is copied;
- the rows kernel alone at both expert cells' decode shapes;
- the rows kernel of a prefill chunk's exact selection at the sparse
  cells' chunk shape;
- the train step's head and loss as one op (`fused_head_loss`) at the
  train cell's shape: three vocabulary-wide products, the logits once,
  one block of float32 logits, the head's gradient summed in float32.

The topology is described ONLY inside this file's module-scoped fixture:
one process at a time may load the TPU's library, the xdist workers all
import every test file, and only the worker that is handed this file may
load it. So: nothing here touches the topology at import, in a `skipif`,
in `parametrize` or in `conftest.py`; the compiles run in the test's own
process; and all of them live in this one file.
"""

import functools
import os
import re

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
    (8, 4, 128, False),   # Llama-3-8B on a bf16 pool
    (32, 1, 128, False),  # Llama-2-7B / OPT-6.7B: MHA, a page is 128 KB
    (16, 1, 256, False),  # GPT-J-6B: MHA, 256-wide heads
    (1, 20, 128, False),  # Jamba2-3B: MQA, a page is 4 KB
    (4, 8, 128, False),   # Mellum2: 32 heads over 4 KV heads, a page 16 KB
], ids=["qwen2-bf16", "qwen2-int8", "llama3-int8", "gpt2-bf16",
        "llama3-bf16", "llama2-mha-bf16", "gptj-bf16", "jamba-bf16",
        "mellum-bf16"])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, chip_compile, hkv,
                                              group, d, quantized):
    from accelerate_tpu.ops.paged_attention import (
        PagedDecodeMeta,
        PagedKV,
        _pages_per_group,
        paged_decode_attention,
    )

    layers, slots, pages_per_slot, num_pages, page = 4, 16, 64, 1024, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((layers, num_pages + 1, hkv, page, d),
               jnp.int8 if quantized else jnp.bfloat16)
    scales = sds((layers, num_pages + 1, hkv, page), jnp.bfloat16) \
        if quantized else None
    pk = PagedKV(pool, scales, jnp.bfloat16, sds((), jnp.int32))
    meta = PagedDecodeMeta(sds((slots, pages_per_slot), jnp.int32),
                           sds((slots,), jnp.int32),
                           rows=pages_per_slot * page)
    q = sds((slots, 1, hkv * group, d), jnp.bfloat16)
    kn = sds((slots, 1, hkv, d), jnp.bfloat16)
    for window in (None, 256):
        attend = jax.jit(
            lambda q, kn, vn, pk, pv, meta, window=window:
            paged_decode_attention(q, kn, vn, pk, pv, meta, window=window)[0])
        compiled = attend.lower(q, kn, kn, pk, pk, meta).compile()
        _assert_kernel_inside(compiled)
        # the live-pages kernel takes the pool where it lies (the older
        # one, for int8 pools and 64-wide heads, is given a layer's slice)
        if not quantized and d % 128 == 0:
            assert not _ops_of_shape(compiled.as_text(), "bf16",
                                     pool.shape[1:])
            # ... and what the chip's compiler took holds the run copy: 8
            # pages of the pool's layer in one descriptor, for K and for V,
            # where a group is first started and where the next one is
            # (a group is 32 pages, 16 of them where a page is 128 KB)
            runs = re.findall(r"dma_start\(p0\) \w+\[\w+,(\w+):\1\+(\d+),",
                              str(jax.make_jaxpr(attend)(q, kn, kn, pk, pk,
                                                         meta)))
            group = _pages_per_group(pages_per_slot, pool.shape[2:],
                                     pool.dtype)
            assert [int(n) for _, n in runs] == [8] * (4 * group // 8)


def _ops_of_shape(text, dtype, shape):
    """Count, by kind, the operations of a compiled module whose result is
    a `dtype[shape]` array (parameters and the free re-namings of one
    buffer left out)."""
    pattern = re.compile(r"= " + dtype + re.escape(
        "[" + ",".join(map(str, shape)) + "]") + r"\S* ([\w-]+)\(")
    kinds = {}
    for kind in pattern.findall(text):
        if kind not in ("parameter", "get-tuple-element", "bitcast"):
            kinds[kind] = kinds.get(kind, 0) + 1
    return kinds


def _assert_host_output_is_its_own(program, args, text, shape):
    """What the host reads of a step is the program's THIRD output, the
    sampled tokens and their logprobs, of `shape`. The engine dispatches the
    next program, which donates the pool, the token register and (a
    prefill) the chunks' counters, before it reads them: no aliased output
    is one of the two."""
    out = jax.eval_shape(program, *args)
    assert [(x.shape, x.dtype) for x in out[2]] == [
        (shape, jnp.int32), (shape, jnp.float32)]
    aliased = {int(i) for i in re.findall(
        r"\{(\d+)\}: \(\d+, \{\}", re.search(
            r"input_output_alias=\{[^\n]*?\}, entry", text).group(0))}
    host = len(jax.tree.leaves(out[:2]))
    assert aliased and not aliased & {host, host + 1}, aliased
    assert host + 2 + len(jax.tree.leaves(out[3:])) == len(
        jax.tree.leaves(out))


def _chat_cell_programs(one_chip, kv_dtype, num_pages=4096):
    """The engine's own `decode` and `prefill` at Qwen2-1.5B widths and the
    chat cell's shape (32 slots x 2048, page 16, chunk 256), each with
    the abstract arguments to lower it with, and the abstract cache.

    The engine is built with the smallest pool it accepts and abstract
    weights (nothing of 3 GB is made here); the programs take the pool
    as an argument, so they are lowered with `num_pages` pages."""
    from accelerate_tpu.models import llama
    from accelerate_tpu.serving import Engine, EngineConfig, PagedKVCache

    slots, max_len, page, chunk = 32, 2048, 16, 256
    cfg = llama.LlamaConfig(
        vocab_size=151936, hidden_size=1536, intermediate_size=8960,
        num_hidden_layers=28, num_attention_heads=12, num_key_value_heads=2,
        max_position_embeddings=32768, rope_theta=1e6, rms_norm_eps=1e-6,
        tie_word_embeddings=True, attention_bias=True)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    engine = Engine(llama, cfg, params, EngineConfig(
        num_slots=slots, max_len=max_len, prefill_chunk=chunk,
        page_size=page, num_pages=(max_len + chunk) // page,
        paged_attention=True, kv_dtype=kv_dtype))
    small = engine.cache
    cache = on_chip(jax.eval_shape(lambda: PagedKVCache.create(
        cfg.num_hidden_layers, slots, max_len, cfg.num_key_value_heads,
        cfg.head_dim, page_size=page, pad_slack=small.pad_slack,
        num_pages=num_pages, kv_dtype=kv_dtype)))
    assert cache.pages_per_slot == small.pages_per_slot

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = (params, cache, arg((slots,), jnp.int32),
             arg(engine._slot_keys.shape, engine._slot_keys.dtype),
             arg((slots,), jnp.float32))
    programs = {
        "decode": (engine._decode_p, state + (
            arg((slots,), jnp.bool_),
            arg((slots, cache.pages_per_slot), jnp.int32))),
        "prefill": (engine._prefill_p, state + (
            arg((), jnp.int32), arg((cache.pages_per_slot,), jnp.int32),
            arg((chunk,), jnp.int32), arg((), jnp.int32))),
    }
    return programs, cache, chunk * cfg.vocab_size * 4


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
def test_engine_programs_update_the_kv_pool_in_place_on_v5e(
        one_chip, chip_compile, kv_dtype):
    """The engine's own `decode` and `prefill`, Qwen2-1.5B widths and the
    chat cell's shape (32 slots x 2048, page 16, chunk 256, 4,096 pages),
    compiled for the chip: besides the aliased update itself no operation
    produces an array of a pool half's shape, and no temporary grows with
    the pool.

    This test fails on the row scatter `_scatter_rows` had up to PR 24.
    A row index falls on the second-minor axis of the chip's (8, 128)
    tile, so the compiler re-laid a whole pool half out around each
    scatter: read at the parent, `decode` and `prefill` each held 4
    `copy bf16[28,4097,2,16,128]` and 942.0 / 948.7 MB of temporaries
    (941.6 MB in the reduced program of ISSUE 25; int8: 4 copies of the
    codes, 539.5 / 543.3 MB), 11 ms of EVERY call on the chip. Page
    indices lie outside the tile: the scatter of whole pages is the
    in-place update, and the temporaries are what the programs hold
    beside the pool (1.0 / 157.0 MB; int8 69.0 / 173.3 MB; with the
    host's own output of PR 30, 935,424 / 157,001,728 bytes against
    903,168 / 157,033,984 without it).

    Since PR 27 `decode` on a bf16 pool also produces nothing of ONE
    LAYER's shape: the kernel takes the whole stacked pool and a layer
    index. At the parent the layer scan sliced `bf16[4097,2,16,128]` out
    of each half for the kernel's call, twice a layer (2.55 ms a decode
    step on the chip at 4,096 pages, ~21 ms at 16,384). An int8 pool
    keeps the older kernel and its four slices (codes and scales)."""
    programs, cache, logits_bytes = _chat_cell_programs(one_chip, kv_dtype)
    half = cache.k.shape                       # (28, 4097, 2, 16, 128)
    half_bytes = cache.k.size * cache.k.dtype.itemsize
    # what a program may hold beside the pool, from its shapes: a chunk's
    # float32 logits in prefill (155.6 MB, under the copies at the parent
    # too) and, on an int8 pool, the two SCALE arrays: [L, pages, H, 16]
    # lies on the chip with the pages on the lanes, so their update still
    # re-lays them out with L there (28 of 128 lanes used), 33.6 MB each
    beside = {"decode": 0, "prefill": logits_bytes}
    scales = 2 * (half[1] * half[2] * half[3] * 128 * 2) if kv_dtype else 0
    for name, (program, args) in programs.items():
        compiled = program.lower(*args).compile()
        text = compiled.as_text()
        memory = compiled.memory_analysis()
        # (a) of a pool half's shape: K's and V's page scatter, each in its
        # fusion, and nothing else; both halves alias their arguments
        assert _ops_of_shape(text, "s8" if kv_dtype else "bf16", half) == {
            "scatter": 2, "fusion": 2}, name
        assert memory.alias_size_in_bytes >= 2 * half_bytes, name
        # (b) no temporary of the pool's order
        assert (memory.temp_size_in_bytes - beside[name] - scales
                < half_bytes // 10), (name, memory.temp_size_in_bytes)
        # (c) decode still walks the page table in the kernel (a chunk
        # attends its slot's gathered view: no kernel in prefill), and on
        # a bf16 pool nothing slices a layer out of the pool for it
        if name == "decode":
            _assert_kernel_inside(compiled)
            layer_slices = _ops_of_shape(
                text, "s8" if kv_dtype else "bf16", half[1:])
            assert bool(layer_slices) == bool(kv_dtype), layer_slices
        # (d) with the host's own output beside them (read one step late)
        _assert_host_output_is_its_own(
            program, args, text, (32,) if name == "decode" else ())


def test_decode_does_not_know_the_pool_size_on_v5e(one_chip, chip_compile):
    """`decode` of the chat cell's shape lowered with a pool of 4,096
    pages and of 16,384: the same temporaries to the byte, and the same
    operations but for the pool's shape in their types. At the parent the
    per-layer slices around the kernel's call grew with the pool (two
    `bf16[pages + 1, 2, 16, 128]` a layer: 16.8 MB at 4,096 pages, 67.1 MB
    at 16,384)."""
    seen = {}
    for num_pages in (4096, 16384):
        programs, cache, _ = _chat_cell_programs(one_chip, None, num_pages)
        program, args = programs["decode"]
        compiled = program.lower(*args).compile()
        text = compiled.as_text()
        assert not _ops_of_shape(text, "bf16", cache.k.shape[1:])
        seen[num_pages] = (
            compiled.memory_analysis().temp_size_in_bytes,
            len(re.findall(r"^\s+(?:ROOT )?%[\w.-]+ = ", text, re.M)))
    assert seen[4096] == seen[16384], seen
    assert seen[4096][0] < 8 << 20, seen


@pytest.mark.parametrize("rows,experts,k,n,lane_tile", [
    (384, 64, 2304, 896, None), (384, 64, 896, 2304, None),
    (128, 256, 2048, 768, None), (128, 256, 768, 2048, None),
    (1024, 32, 5120, 1536, 768), (1024, 32, 1536, 5120, 2560),
], ids=["mellum-gate", "mellum-down", "joyai-gate", "joyai-down",
        "share-chunk-gate", "share-chunk-down"])
def test_rows_kernel_compiles_for_v5e(one_chip, chip_compile, rows, experts,
                                      k, n, lane_tile):
    """The grouped product for few rows an expert at the decode shapes of
    both expert cells, and for a pass of a share's held rows at the dots3
    cell's chunk (15.7 MB matrices in blocks of lanes): it compiles as ONE
    kernel under its own name (what a device trace shows of it, and what
    `%ragged-dot` still finds), reads the stacked `[E, K, N]` matrices as
    they are (nothing of their shape is produced) and holds no temporary
    beyond its scalars."""
    from accelerate_tpu.ops.grouped_experts import (
        ROWS_KERNEL_NAME,
        _lane_tile,
        grouped_rows_matmul,
    )

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if lane_tile is not None:
        assert _lane_tile(k, n, jnp.bfloat16) == lane_tile
    compiled = jax.jit(
        functools.partial(grouped_rows_matmul, lane_tile=lane_tile)).lower(
        sds((rows, k), jnp.bfloat16), sds((experts, k, n), jnp.bfloat16),
        sds((experts,), jnp.int32)).compile()
    text = compiled.as_text()
    assert len(re.findall("%" + ROWS_KERNEL_NAME + r"[.\d]* = ", text)) == 1
    assert "%ragged-dot-none" not in text
    assert _ops_of_shape(text, "bf16", (experts, k, n)) == {}
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def _grouped_products(text):
    """(XLA's `ragged-dot` kernels, calls of the rows kernel) in a
    compiled program's text: an expert layer's three products are all of
    one kind, `ragged-dot-none` over a chunk's rows and the rows kernel
    (`ops/grouped_experts.py`, few rows an expert) in a decode step."""
    from accelerate_tpu.ops.grouped_experts import ROWS_KERNEL_NAME

    return (len(re.findall(r"%ragged-dot-none[.\d]* = ", text)),
            len(re.findall("%" + ROWS_KERNEL_NAME + r"[.\d]* = ", text)))


def test_latent_engine_programs_compile_for_v5e(one_chip, chip_compile):
    """`decode` and `prefill` of `serve-joyai-flash-docqa-long` (JoyAI-
    LLM-Flash widths, 1 dense + 4 expert layers, 16 slots x 17920, page
    16, chunk 512, 17,920 pages of 640-lane latent rows), abstract
    weights and pool, compiled for the chip:

    (a) the latent paged-attention kernel is in `decode`, once a layer,
        under its own name, and takes the WHOLE stacked pool (nothing of
        a layer's slice shape is produced around it); `prefill` holds the
        chunk kernel (`latent_chunk_attention`), once a layer;
    (b) the expert products, 3 an expert layer, are `ragged-dot` kernels
        in `prefill` and the rows kernel (`ragged-dot-rows`, which reads
        the stacked matrices as they are held) in `decode`, where no
        `ragged-dot-none` is left; nothing of an expert matrix's shape
        (`[256, 2048, 768]`, 805 MB) is copied or re-laid out, and no
        layer sits under control flow (no `conditional`);
    (c) the pool is ONE array, aliased to its argument; of its shape only
        the page scatter is produced;
    (d) temporaries: `decode` under 64 MB (15 MB read), `prefill` under
        640 MB (435 MB read: the slot's gathered view three times over,
        118 MB each, and a chunk's blocks), neither of the pool's order;
        a chunk's logits are ONE row (517 KB), not `[512, 129280]`
        float32 (265 MB)."""
    from accelerate_tpu.models import deepseek
    from accelerate_tpu.serving import Engine, EngineConfig, PagedKVCache

    slots, max_len, page, chunk, num_pages = 16, 17920, 16, 512, 17920
    cfg = deepseek.DeepseekConfig(num_hidden_layers=5,
                                  max_position_embeddings=32768)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: deepseek.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert 11.1e9 < weights < 11.13e9
    engine = Engine(deepseek, cfg, params, EngineConfig(
        num_slots=slots, max_len=max_len, prefill_chunk=chunk,
        page_size=page, num_pages=(max_len + chunk) // page,
        paged_attention=True))
    small = engine.cache
    spec = deepseek.cache_spec(cfg)
    cache = on_chip(jax.eval_shape(lambda: PagedKVCache.create(
        spec.num_layers, slots, max_len, spec.heads, spec.width,
        page_size=page, pad_slack=small.pad_slack, num_pages=num_pages,
        latent=True, stats=small.stats)))
    assert cache.v is None and cache.k.shape == (5, 17921, 1, 16, 640)
    pool_bytes = cache.k.size * 2
    assert pool_bytes == 1_835_110_400

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = (params, cache, arg((slots,), jnp.int32),
             arg(engine._slot_keys.shape, engine._slot_keys.dtype),
             arg((slots,), jnp.float32))
    programs = {
        "decode": (engine._decode_p, state + (
            arg((slots,), jnp.bool_),
            arg((slots, cache.pages_per_slot), jnp.int32)), 64e6),
        "prefill": (engine._prefill_p, state + (
            arg((), jnp.int32), arg((cache.pages_per_slot,), jnp.int32),
            arg((chunk,), jnp.int32), arg((), jnp.int32)), 640e6),
    }
    for name, (program, args, temp_limit) in programs.items():
        compiled = program.lower(*args).compile()
        text = compiled.as_text()
        memory = compiled.memory_analysis()
        kernels = len(re.findall(
            r"%latent_paged_decode_attention[.\d]* = ", text))
        assert kernels == (5 if name == "decode" else 0), name
        chunks = len(re.findall(r"%latent_chunk_attention[.\d]* = ", text))
        assert chunks == (0 if name == "decode" else 5), name
        assert _grouped_products(text) == (
            (0, 12) if name == "decode" else (12, 0)), name
        assert " conditional(" not in text, name
        assert _ops_of_shape(text, "bf16", (256, 2048, 768)) == {}, name
        assert _ops_of_shape(text, "bf16", (256, 768, 2048)) == {}, name
        assert _ops_of_shape(text, "bf16", (17921, 16, 640)) == {}, name
        assert _ops_of_shape(text, "bf16", (5, 17921, 16, 640)) == {
            "scatter": 1, "fusion": 1}, name
        assert memory.alias_size_in_bytes >= pool_bytes, name
        assert memory.temp_size_in_bytes < temp_limit, (
            name, memory.temp_size_in_bytes)
        assert _ops_of_shape(text, "f32", (chunk, cfg.vocab_size)) == {}
        _assert_host_output_is_its_own(
            program, args, text, (slots,) if name == "decode" else ())


def test_mixed_window_engine_programs_compile_for_v5e(one_chip, chip_compile):
    """`decode` and `prefill` of `serve-mellum2-code-mixed-closed`
    (Mellum2-12B-A2.5B widths, 8 layers s s s f s s s f, 48 slots x 32768,
    page 16, chunk 512; 24,576 pages for the two full layers and a ring of
    97 pages a slot for the six sliding ones), abstract weights and pools,
    compiled for the chip:

    (a) `decode` holds BOTH kernel names: `paged_decode_attention` twice
        (the full layers) and `paged_decode_attention_window` six times
        (the sliding layers, whose walk the window bounds); each takes its
        group's whole stacked pool (nothing of a layer's slice shape is
        produced around it); `prefill` holds neither (a chunk attends the
        slot's gathered views);
    (b) the expert products, 3 a layer, are `ragged-dot` kernels in
        `prefill` and the rows kernel (`ragged-dot-rows`) in `decode`,
        where no `ragged-dot-none` is left, and nothing of an expert
        matrix's shape is copied in either;
    (c) both groups' K and V pools are aliased to their arguments, and of
        a pool half's shape only the page scatter is produced;
    (d) a chunk's logits are one row, not `[512, 98304]` float32 (201 MB),
        and no temporary is of the full pool's order."""
    from accelerate_tpu.models import mellum
    from accelerate_tpu.ops import paged_attention
    from accelerate_tpu.serving import Engine, EngineConfig
    from accelerate_tpu.serving.cache import GroupedPagedCache

    slots, max_len, page, chunk, num_pages = 48, 32768, 16, 512, 24576
    cfg = mellum.MellumConfig(
        num_hidden_layers=8, max_position_embeddings=32768,
        rope_parameters={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}})
    assert cfg.layer_types == ("sliding_attention",) * 3 + (
        "full_attention",) + ("sliding_attention",) * 3 + ("full_attention",)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: mellum.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == 2 * 3_794_968_832
    engine = Engine(mellum, cfg, params, EngineConfig(
        num_slots=slots, max_len=max_len, prefill_chunk=chunk,
        page_size=page, num_pages=(max_len + chunk) // page,
        paged_attention=True, prefix_cache=False))
    small = engine.cache
    cache = on_chip(jax.eval_shape(lambda: GroupedPagedCache.create(
        mellum.cache_spec(cfg), slots, max_len, page_size=page,
        pad_slack=small.pad_slack, num_pages=num_pages, stats=small.stats)))
    full, ring = cache.groups
    assert full.k.shape == (2, 24577, 4, 16, 128)
    assert ring.k.shape == (6, 48 * 97 + 1, 4, 16, 128)
    assert (full.pages_per_slot, ring.pages_per_slot) == (2080, 97)
    pool_bytes = 2 * (full.k.size + ring.k.size) * 2
    assert 2.5e9 < pool_bytes < 2.55e9

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = (params, cache, arg((slots,), jnp.int32),
             arg(engine._slot_keys.shape, engine._slot_keys.dtype),
             arg((slots,), jnp.float32))
    programs = {
        "decode": (engine._decode_p, state + (
            arg((slots,), jnp.bool_),
            (arg((slots, 2080), jnp.int32), arg((slots, 97), jnp.int32)))),
        "prefill": (engine._prefill_p, state + (
            arg((), jnp.int32),
            (arg((2080,), jnp.int32), arg((97,), jnp.int32)),
            arg((chunk,), jnp.int32), arg((), jnp.int32))),
    }
    for name, (program, args) in programs.items():
        compiled = program.lower(*args).compile()
        text = compiled.as_text()
        memory = compiled.memory_analysis()
        names = (paged_attention.KERNEL_NAME,
                 paged_attention.WINDOW_KERNEL_NAME)
        # a kernel call is an instruction under the kernel's name, which
        # is what a device trace shows of it
        calls = [len(re.findall("%" + n + r"(?:\.\d+)? = ", text))
                 for n in names]
        assert calls == ([2, 6] if name == "decode" else [0, 0]), (
            name, calls)
        assert _grouped_products(text) == (
            (0, 24) if name == "decode" else (24, 0)), name
        assert " conditional(" not in text, name
        assert _ops_of_shape(text, "bf16", (64, 2304, 896)) == {}, name
        assert _ops_of_shape(text, "bf16", (64, 896, 2304)) == {}, name
        for group in (full, ring):
            assert _ops_of_shape(text, "bf16", group.k.shape[1:]) == {}, name
            assert _ops_of_shape(text, "bf16", group.k.shape) == {
                "scatter": 2, "fusion": 2}, (name, group.k.shape)
        assert memory.alias_size_in_bytes >= pool_bytes, name
        assert memory.temp_size_in_bytes < full.k.size * 2 // 2, (
            name, memory.temp_size_in_bytes)
        assert _ops_of_shape(text, "f32", (chunk, cfg.vocab_size)) == {}
        _assert_host_output_is_its_own(
            program, args, text, (slots,) if name == "decode" else ())


def test_sparse_select_engine_programs_compile_for_v5e(one_chip, chip_compile):
    """`decode` and `prefill` of `serve-keye-vl2-docqa-32k-closed`
    (Keye-VL-2.0-30B-A3B widths, 6 layers, 16 slots x 43008, page 16, chunk
    512, 21,504 pages of K, V and a 64-lane index key a token), abstract
    weights and pool, compiled for the chip:

    (a) `decode` holds the indexer-score kernel and the sparse attention
        kernel once a layer, each under its own name, and the selection's
        loops (`while`: the value's bits, and the ties' cut under a
        `conditional`); each kernel takes its whole stacked pool (nothing
        of a layer's slice shape is produced around it); `prefill` holds
        neither kernel (a chunk scores and attends the slot's gathered
        views) and the selection as the rows kernel, once a layer, with
        no 32-bit image of a layer's scores beside it;
    (b) the expert products, 3 a layer, are `ragged-dot` kernels in
        `prefill` and the rows kernel in `decode`; nothing of an expert
        matrix's shape is copied in either;
    (c) K, V and the index pool are aliased to their arguments; the index
        pool lies as [6, 21505, 8, 128] bf16, whole (8, 128)(2, 1) tiles:
        its argument takes its logical bytes, not twice them;
    (d) a chunk's logits are one row; temporaries: `decode` under 300 MB
        (90 MB read), `prefill` under 1.2 GB (a layer's gathered K and
        V views, 45 MB each, of the layers the scheduler holds at once,
        and a layer's [512, 43520] scores and the mask; 1.57 GB until
        PR 39, with all six layers' views stacked in
        and stacked out), beside 8.75 GB of weights and 4.49 GB of pool;
    (e) `prefill` holds no array of the stacked views' shape
        [6, 1, 43520, ...]: a layer's view is gathered alone."""
    from accelerate_tpu.models import keye
    from accelerate_tpu.ops import sparse_paged_attention as sparse
    from accelerate_tpu.serving import Engine, EngineConfig, PagedKVCache

    slots, max_len, page, chunk, num_pages = 16, 43008, 16, 512, 21504
    cfg = keye.KeyeConfig(num_hidden_layers=6, max_position_embeddings=49152)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: keye.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == 2 * 4_374_622_464
    engine = Engine(keye, cfg, params, EngineConfig(
        num_slots=slots, max_len=max_len, prefill_chunk=chunk,
        page_size=page, num_pages=(max_len + chunk) // page,
        paged_attention=True))
    small = engine.cache
    spec = keye.cache_spec(cfg)
    cache = on_chip(jax.eval_shape(lambda: PagedKVCache.create(
        spec.num_layers, slots, max_len, spec.heads, spec.width,
        page_size=page, pad_slack=small.pad_slack, num_pages=num_pages,
        stats=small.stats, side_width=spec.side_width)))
    assert cache.k.shape == (6, 21505, 4, 16, 128)
    assert cache.side.shape == (6, 21505, 8, 128)
    assert cache.pages_per_slot == 2720
    assert cache.page_nbytes == 16 * 13_056
    pool_bytes = 2 * cache.k.size * 2 + cache.side.size * 2

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = (params, cache, arg((slots,), jnp.int32),
             arg(engine._slot_keys.shape, engine._slot_keys.dtype),
             arg((slots,), jnp.float32))
    programs = {
        "decode": (engine._decode_p, state + (
            arg((slots,), jnp.bool_), arg((slots, 2720), jnp.int32)), 300e6),
        "prefill": (engine._prefill_p, state + (
            arg((), jnp.int32), arg((2720,), jnp.int32),
            arg((chunk,), jnp.int32), arg((), jnp.int32),
            on_chip(engine._chunk_stats)), 1.2e9),
    }
    for name, (program, args, temp_limit) in programs.items():
        compiled = program.lower(*args).compile()
        text = compiled.as_text()
        memory = compiled.memory_analysis()
        calls = [len(re.findall("%" + n + r"(?:\.\d+)? = ", text))
                 for n in (sparse.SCORES_KERNEL_NAME,
                           sparse.ATTENTION_KERNEL_NAME,
                           sparse.ROWS_SELECT_NAME)]
        assert calls == ([6, 6, 0] if name == "decode" else [0, 0, 6]), (
            name, calls)
        # the selection in `decode`: a loop over the value's bits a
        # layer, and the ties' cut (a second loop) under a conditional,
        # and no other loop (a trace names the selection `%while`
        # there); in `prefill` it is the rows kernel, and the loops are
        # the blocks of the scores and of the attention
        assert len(re.findall(r" while\(", text)) == 12, name
        assert len(re.findall(r" conditional\(", text)) == (
            6 if name == "decode" else 0), name
        assert not re.search(r"[su]32\[1,512,43520\].* (?:while|copy)\(",
                             text), name
        assert _grouped_products(text) == (
            (0, 18) if name == "decode" else (18, 0)), name
        assert _ops_of_shape(text, "bf16", (128, 2048, 768)) == {}, name
        assert _ops_of_shape(text, "bf16", (128, 768, 2048)) == {}, name
        assert _ops_of_shape(text, "bf16", cache.k.shape[1:]) == {}, name
        assert _ops_of_shape(text, "bf16", cache.side.shape[1:]) == {}, name
        assert _ops_of_shape(text, "bf16", cache.k.shape) == {
            "scatter": 2, "fusion": 2}, name
        assert memory.alias_size_in_bytes >= pool_bytes, name
        # the index pool as it lies on the chip: whole tiles, no padding
        assert re.search(r"bf16\[6,21505,8,128\]\{3,2,1,0:T\(8,128\)\(2,1\)\}",
                         text), name
        assert memory.temp_size_in_bytes < temp_limit, (
            name, memory.temp_size_in_bytes)
        assert _ops_of_shape(text, "f32", (chunk, cfg.vocab_size)) == {}
        assert not re.search(r"\[6,1,43520,", text), name
        _assert_host_output_is_its_own(
            program, args, text, (slots,) if name == "decode" else ())
        print(name, "temp", memory.temp_size_in_bytes, "args",
              memory.argument_size_in_bytes)


def test_latent_groups_engine_programs_compile_for_v5e(one_chip, chip_compile):
    """`decode` and `prefill` of `serve-dots3-note-16k-in-512-out-closed`
    (dots3-note-prev widths, 5 layers, 32 of 256 experts and 19,008
    vocabulary rows held, 16 slots x 43008, page 16, chunk 512, 43,008
    pages of 640-lane latent rows with a 128-lane index key a token, and a
    ring of 66 pages of 1,152-lane rows a slot), abstract weights and
    pools, compiled for the chip:

    (a) `decode` holds, a FULL layer, the indexer-score kernel and the
        sparse latent attention kernel, and a SLIDING layer the ring mode
        of the latent kernel, each under its own name, and the selection's
        loops; `prefill` holds none of those, the selection as the rows
        kernel (a full layer, two), and ONE chunk kernel a layer
        (`latent_chunk_attention`, five), with no block's float32 scores
        `[.., 512, 1024]` and no 32-bit image of a layer's index scores
        beside them;
    (b) each kernel takes its whole stacked pool: `decode` holds no copy
        of either pool, nor of a layer's slice of one;
    (c) both latent pools and the index pool are aliased to their
        arguments; arguments + temporaries stay under 15.5 GB;
    (d) a chunk's logits are one row, and `prefill` holds no array of the
        stacked views' shape: a layer's view is gathered alone;
    (e) the held experts' products: `ragged-dot-none` in `decode`, the
        rows kernel over the held rows in `prefill`."""
    import json

    from accelerate_tpu.models import dots3
    from accelerate_tpu.ops import latent_chunk_attention as chunk_kernel
    from accelerate_tpu.ops import latent_paged_attention as latent
    from accelerate_tpu.ops import sparse_paged_attention as sparse
    from accelerate_tpu.serving import Engine, EngineConfig
    from accelerate_tpu.serving.cache import GroupedPagedCache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "dots3-note-prev-d5-ep8.json")) as f:
        file = json.load(f)
    cfg = dots3.Dots3Config(**{k: file[k] for k in file["program"]["copy"]},
                            **file["program"]["extra"])
    slots, max_len, page, chunk, num_pages = 16, 43008, 16, 512, 43008

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: dots3.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    # (the router's float32 bias: 4 x 256 x 2 bytes over bf16)
    assert weights == 2 * file["parameters"] + 4 * 256 * 2
    engine = Engine(dots3, cfg, params, EngineConfig(
        num_slots=slots, max_len=max_len, prefill_chunk=chunk,
        page_size=page, num_pages=(max_len + chunk) // page,
        prefix_cache=False, paged_attention=True))
    small = engine.cache
    cache = on_chip(jax.eval_shape(lambda: GroupedPagedCache.create(
        dots3.cache_spec(cfg), slots, max_len, page_size=page,
        pad_slack=small.pad_slack, num_pages=num_pages, stats=small.stats)))
    full, ring = cache.groups
    assert full.k.shape == (2, 43009, 1, 16, 640) and full.v is None
    assert full.side.shape == (2, 43009, 16, 128)
    assert ring.k.shape == (3, 16 * 66 + 1, 1, 16, 1152) and ring.v is None
    assert (full.pages_per_slot, ring.pages_per_slot) == (2720, 66)
    assert cache.page_nbytes == 16 * 2 * (1280 + 256)
    pool_bytes = 2 * (full.k.size + full.side.size + ring.k.size)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = (params, cache, arg((slots,), jnp.int32),
             arg(engine._slot_keys.shape, engine._slot_keys.dtype),
             arg((slots,), jnp.float32))
    programs = {
        "decode": (engine._decode_p, state + (
            arg((slots,), jnp.bool_),
            (arg((slots, 2720), jnp.int32), arg((slots, 66), jnp.int32)))),
        "prefill": (engine._prefill_p, state + (
            arg((), jnp.int32),
            (arg((2720,), jnp.int32), arg((66,), jnp.int32)),
            arg((chunk,), jnp.int32), arg((), jnp.int32),
            on_chip(engine._chunk_stats))),
    }
    for name, (program, args) in programs.items():
        compiled = program.lower(*args).compile()
        text = compiled.as_text()
        memory = compiled.memory_analysis()
        calls = [len(re.findall("%" + n + r"(?:\.\d+)? = ", text))
                 for n in (sparse.SCORES_KERNEL_NAME,
                           sparse.LATENT_ATTENTION_KERNEL_NAME,
                           latent.WINDOW_KERNEL_NAME,
                           chunk_kernel.KERNEL_NAME,
                           sparse.ROWS_SELECT_NAME)]
        assert calls == ([2, 2, 3, 0, 0] if name == "decode"
                         else [0, 0, 0, 5, 2]), (name, calls)
        assert not re.search(r"\[(?:1,)?128,(?:1,)?512,1024\]", text), name
        # (e) the four expert layers' products: a decode step's few rows
        # over 15.7 MB matrices are XLA's; a chunk's HELD rows go through
        # the rows kernel in blocks of lanes, and XLA's kernel is gone
        assert _grouped_products(text) == (
            (12, 0) if name == "decode" else (0, 12)), name
        # the selection's cut of ties: a conditional of XLA's in a decode
        # step, inside the rows kernel in a chunk
        assert len(re.findall(r" conditional\(", text)) == (
            2 if name == "decode" else 0), name
        assert not re.search(r"[su]32\[1,512,43520\].* (?:while|copy)\(",
                             text), name
        # no copy of a pool, nor of a layer's slice of one
        for pool in (full.k, full.side, ring.k):
            for shape in (pool.shape, pool.shape[1:]):
                found = _ops_of_shape(text, "bf16", shape)
                assert set(found) <= {"scatter", "fusion"} and (
                    len(shape) == pool.ndim or not found), (name, shape, found)
        assert memory.alias_size_in_bytes >= pool_bytes, name
        total = (memory.argument_size_in_bytes + memory.temp_size_in_bytes)
        assert total < 15.5e9, (name, total)
        assert _ops_of_shape(text, "f32", (chunk, cfg.vocab_size)) == {}
        assert not re.search(r"\[[23],1,43520,", text), name
        assert not re.search(r"\[3,1,1056,", text), name
        _assert_host_output_is_its_own(
            program, args, text, (slots,) if name == "decode" else ())
        print(name, "temp", memory.temp_size_in_bytes, "args",
              memory.argument_size_in_bytes)


@pytest.mark.parametrize("S,heads,nope,rank,width,rows,selected,window", [
    (512, 128, 128, 512, 640, 43520, True, None),   # dots3, a full layer
    (512, 64, 192, 1024, 1152, 1056, False, 513),   # dots3, a sliding ring
    (512, 32, 128, 512, 640, 18432, False, None),   # joyai
    (1, 128, 128, 512, 640, 43520, True, None),     # a dense decode's token
], ids=["dots3-full", "dots3-sliding", "joyai", "one-token"])
def test_latent_chunk_kernel_compiles_for_v5e(one_chip, chip_compile, S,
                                              heads, nope, rank, width, rows,
                                              selected, window):
    """`latent_chunk_attention` alone at the cells' shapes (bf16, a chunk
    of 512 queries; the last case ONE query, padded to a sublane tile):
    the chip's compiler takes the tiles `_tiles` computes, the program
    holds the kernel once, no loop, and no float32 array over a tile of
    rows (its scores) around it."""
    from accelerate_tpu.ops import latent_chunk_attention as lca

    bf = jnp.bfloat16

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [arg((1, S, heads, nope), bf), arg((1, S, heads, 64), bf),
            arg((1, S), jnp.int32), arg((1, rows, width), bf),
            arg((1, rows), jnp.int32), arg((rank, heads, nope + 128), bf),
            arg((), jnp.int32), arg((), jnp.int32)]
    if selected:
        args.append(arg((1, S, rows), jnp.bool_))

    def attend(q_nope, q_pe, q_pos, view, key_pos, w_kvb, first, end,
               select=None):
        return lca.latent_chunk_attention(
            q_nope, q_pe, q_pos, view, key_pos, w_kvb, select=select,
            window=window, live=(first, end))

    compiled = jax.jit(attend).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall("%" + lca.KERNEL_NAME + r"(?:\.\d+)? = ",
                          text)) == 1
    assert not re.search(r"f32\[(?:\d+,){2,}1\d\d\d\]", text)
    assert " while(" not in text


@pytest.mark.parametrize("blocked", [True, False],
                         ids=["blocks-as-scored", "row-major"])
def test_rows_select_kernel_compiles_for_v5e(one_chip, chip_compile, blocked):
    """`exact_topk_mask_rows` alone at the sparse cells' chunk shape (512
    queries over a view of 43,520 rows, 2,048 keys a query, a traced live
    bound), the scores in the 43 blocks of 1,024 columns the indexer's
    loop leaves them in, and as one row-major array: the chip's compiler
    takes the tile the wrapper computes (32 rows x 43,520 keys resident),
    the program holds the kernel once, no loop of XLA's, no 32-bit image
    of the scores beside them, and the blocks go into the kernel as they
    are: no copy of them."""
    from accelerate_tpu.ops import sparse_paged_attention as sparse

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    scores = arg((43, 1, 512, 1024) if blocked else (1, 512, 43520),
                 jnp.float32)
    compiled = jax.jit(
        lambda scores, live: sparse.exact_topk_mask_rows(
            scores, 2048, live, columns=43520 if blocked else None)
    ).lower(scores, arg((), jnp.int32)).compile()
    text = compiled.as_text()
    assert len(re.findall("%" + sparse.ROWS_SELECT_NAME + r"(?:\.\d+)? = ",
                          text)) == 1
    assert " while(" not in text
    assert not re.search(r"[su]32\[1,512,43520\]", text)
    assert re.search(r"s8\[1,512,43520\]", text)
    assert not re.search(r"f32\[\S* (?:copy|fusion)\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("degree,state_dtype", [
    (2, "float32"), (1, "float32"), (2, "bfloat16")],
    ids=["served", "degree-1", "bf16-state"])
def test_retention_kernels_compile_for_v5e(one_chip, chip_compile, degree,
                                           state_dtype):
    """The decode kernel and the chunk kernel of power retention alone, at
    the cell's widths (40 query heads over 8 KV heads of 128, 16 lanes, a
    chunk of 512 rows, a pool of 8 layers x 17 entries), as served and as
    the controls and the what-if run them: each takes the WHOLE pool and
    hands it back aliased; nothing of the pool's size is a temporary."""
    from accelerate_tpu.ops import power_retention as pr

    L, E, B, C, H, G, d = 8, 17, 16, 512, 40, 8, 128
    D, dt = pr.state_rows(d, degree), jnp.dtype(state_dtype)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s = arg((L, E, G, D, d), dt)
    z = arg((L, E, G, pr.normaliser_rows(d, degree), d), dt)
    pool_bytes = (s.size + z.size) * dt.itemsize

    def decode(q, k, v, gamma, s, z, entries, rows):
        o, pool = pr.retention_decode_step(
            q, k, v, gamma, pr.StatePool(s, z, True), 3,
            pr.StateMeta(entries, rows), degree=degree)
        return o, pool.s, pool.z

    def chunk(q, k, v, gamma, s, z, entries):
        o, pool = pr.retention_chunk(
            q, k, v, gamma, pr.StatePool(s, z, True), 3, entries,
            degree=degree)
        return o, pool.s, pool.z

    bf = jnp.bfloat16
    for fn, args, name in (
            (decode, (arg((B, H, d), bf), arg((B, G, d), bf),
                      arg((B, G, d), bf), arg((B, G), jnp.float32), s, z,
                      arg((B,), jnp.int32), arg((B,), jnp.int32)),
             pr.DECODE_KERNEL),
            (chunk, (arg((1, C, H, d), bf), arg((1, C, G, d), bf),
                     arg((1, C, G, d), bf), arg((1, C, G), jnp.float32), s,
                     z, arg((1,), jnp.int32)), pr.CHUNK_KERNEL)):
        compiled = jax.jit(fn, donate_argnums=(4, 5)).lower(*args).compile()
        text = compiled.as_text()
        assert len(re.findall("%" + name + r"(?:\.\d+)? = ", text)) == 1, name
        assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
        # no copy, slice or re-laid image of the pool around the kernel
        for shape in (s.shape, s.shape[1:], s.shape[2:]):
            assert _ops_of_shape(text, "f32" if dt == jnp.float32 else "bf16",
                                 shape) == {}, (name, shape)


def test_retention_engine_programs_compile_for_v5e(one_chip, chip_compile):
    """`decode` and `prefill` of `serve-brumby-8k-in-1k-out-closed`
    (Brumby-14B-Base widths, 8 layers, 16 slots x 26624, chunk 512, a state
    pool of 16 entries and the spare), abstract weights and pool, compiled
    for the chip:

    (a) `decode` holds the retention decode kernel once a layer and
        `prefill` the chunk kernel once a layer, each under its own name;
    (b) the pool (4.67 GB) is aliased to its arguments in both programs and
        NOTHING of a pool array's shape, of a layer's slice of it or of an
        entry's is produced around the kernels: no copy of the state;
    (c) a chunk's logits are one row; temporaries: `decode` under 200 MB,
        `prefill` under 700 MB (the chunk's [40, 512, 512] scores and
        weights, a layer's MLP activations), beside 8.40 GB of weights."""
    from accelerate_tpu.models import brumby
    from accelerate_tpu.ops import power_retention as pr
    from accelerate_tpu.serving import Engine, EngineConfig
    from accelerate_tpu.serving.cache import StateCache

    slots, max_len, chunk, entries = 16, 26624, 512, 16
    cfg = brumby.BrumbyConfig(num_hidden_layers=8)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: brumby.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == 2 * 4_198_652_992
    engine = Engine(brumby, cfg, params, EngineConfig(
        num_slots=slots, max_len=max_len, prefill_chunk=chunk, num_pages=1,
        prefix_cache=False, paged_attention=True))
    small = engine.cache
    cache = on_chip(jax.eval_shape(lambda: StateCache.create(
        brumby.cache_spec(cfg), slots, max_len, pad_slack=small.pad_slack,
        num_entries=entries, stats=small.stats)))
    assert cache.s.shape == (8, 17, 8, 8320, 128)
    assert cache.z.shape == (8, 17, 8, 72, 128)
    assert (cache.num_pages, cache.trash_page, cache.pages_per_slot) == (
        16, 16, 1)
    assert cache.page_nbytes == 274_989_056
    pool_bytes = 17 * cache.page_nbytes

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = (params, cache, arg((slots,), jnp.int32),
             arg(engine._slot_keys.shape, engine._slot_keys.dtype),
             arg((slots,), jnp.float32))
    programs = {
        "decode": (engine._decode_p, state + (
            arg((slots,), jnp.bool_), arg((slots, 1), jnp.int32)),
            pr.DECODE_KERNEL, 200e6),
        "prefill": (engine._prefill_p, state + (
            arg((), jnp.int32), arg((1,), jnp.int32),
            arg((chunk,), jnp.int32), arg((), jnp.int32)),
            pr.CHUNK_KERNEL, 700e6),
    }
    for name, (program, args, kernel, temp_limit) in programs.items():
        compiled = program.lower(*args).compile()
        text = compiled.as_text()
        memory = compiled.memory_analysis()
        assert len(re.findall("%" + kernel + r"(?:\.\d+)? = ", text)) == 8, name
        assert memory.alias_size_in_bytes >= pool_bytes, name
        for shape in (cache.s.shape, cache.s.shape[1:], cache.s.shape[2:],
                      cache.z.shape[1:]):
            assert _ops_of_shape(text, "f32", shape) == {}, (name, shape)
        # (a chunk's normaliser, 37 KB a head, is XLA's: a slice read and
        # an in-place slice update a layer)
        assert set(_ops_of_shape(text, "f32", cache.z.shape)) <= {
            "dynamic-update-slice"}, name
        assert memory.temp_size_in_bytes < temp_limit, (
            name, memory.temp_size_in_bytes)
        assert _ops_of_shape(text, "f32", (chunk, cfg.vocab_size)) == {}
        _assert_host_output_is_its_own(
            program, args, text, (slots,) if name == "decode" else ())
        print(name, "temp", memory.temp_size_in_bytes, "args",
              memory.argument_size_in_bytes)


def test_qwen_decode_holds_the_one_kernel_name_on_v5e(one_chip,
                                                      chip_compile):
    """The Qwen cells' `decode` still calls `paged_decode_attention` alone:
    the window kernel's name belongs to a ring of pages, which only a
    cache with one group a layer kind has."""
    programs, _, _ = _chat_cell_programs(one_chip, None)
    program, args = programs["decode"]
    text = program.lower(*args).compile().as_text()
    assert len(re.findall(r"%paged_decode_attention(?:\.\d+)? = ", text)) == 1
    assert "paged_decode_attention_window" not in text


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


def test_fused_head_loss_compiles_for_v5e_with_one_projection(one_chip,
                                                              chip_compile):
    """The train cell's head and loss (2 x 2048 rows over Qwen2's 151,936 x
    1,536 tied head, bf16) as the chip's compiler sees them: three
    vocabulary-wide products in all (the logits ONCE, the hidden rows'
    gradient, the head's gradient), one block of float32 logits and never
    the whole [B, S, V], the head's gradient summed in float32."""
    from accelerate_tpu.models import llama
    from accelerate_tpu.models.common import fused_head_loss

    B, S, H, V = 2, 2048, 1536, 151936
    plan = llama.loss_plan(B, S, V)
    assert plan["path"] == "fused"
    chunk = plan["rows_per_block"] // B

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss_and_grads(hidden, head, labels, weights):
        return jax.value_and_grad(
            lambda h, w: fused_head_loss(h, w, labels, weights, True, chunk)
            / jnp.maximum(jnp.sum(weights), 1), argnums=(0, 1))(hidden, head)

    compiled = jax.jit(loss_and_grads).lower(
        sds((B, S, H), jnp.bfloat16), sds((V, H), jnp.bfloat16),
        sds((B, S), jnp.int32), sds((B, S), jnp.float32)).compile()
    text = compiled.as_text()
    # every product of the program, by the einsum it came from
    products = re.findall(
        r' convolution\([^\n]*op_name="[^"\n]*?/([a-z,]+->[a-z]+)/dot_general',
        text)
    assert sorted(products) == ["bsh,vh->bsv", "bsv,bsh->vh", "bsv,vh->bsh"]
    assert f"f32[{B},{chunk},{V}]" in text
    assert f"[{B},{S},{V}]" not in text
    assert f"f32[{V},{H}]" in text  # the accumulator
    # one block of logits + the accumulator + what the compiler keeps
    # beside them: far from the whole logits' 2.5 GB + a saved copy
    assert compiled.memory_analysis().temp_size_in_bytes < 3.5e9


def test_selective_scan_kernels_compile_for_v5e(one_chip, chip_compile):
    """The decode kernel and the chunk kernel of the selective scan alone,
    at the cell's widths (5,120 channels x 16 states, 256 lanes, a chunk of
    512 rows, a pool of 26 layers x 257 entries): each takes the WHOLE
    state pool and hands it back aliased; nothing of the pool's size is a
    temporary, and no layer's or entry's slice of it is made."""
    from accelerate_tpu.models.contract import StateMeta, StatePool
    from accelerate_tpu.ops import selective_scan as ss

    L, E, B, T, n, d = 26, 257, 256, 512, 16, 5120
    f32 = jnp.float32

    def arg(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s, z = arg((L, E, 1, n, d)), arg((L, 3, E, d))
    pool_bytes = s.size * 4

    def decode(dt, x, Bm, Cm, A, s, z, rows):
        y, pool = ss.ssm_decode_step(dt, x, Bm, Cm, A, StatePool(s, z, True),
                                     3, StateMeta(None, rows))
        return y, pool.s

    def chunk(dt, x, Bm, Cm, A, s, z, entries, rows):
        y, pool = ss.ssm_chunk_scan(dt, x, Bm, Cm, A, StatePool(s, z, True),
                                    3, StateMeta(entries, rows))
        return y, pool.s

    for fn, args, name in (
            (decode, (arg((B, d)), arg((B, d)), arg((B, n)), arg((B, n)),
                      arg((n, d)), s, z, arg((B,), jnp.int32)),
             ss.DECODE_KERNEL),
            (chunk, (arg((1, T, d)), arg((1, T, d)), arg((1, T, n)),
                     arg((1, T, n)), arg((n, d)), s, z,
                     arg((1,), jnp.int32), arg((1,), jnp.int32)),
             ss.CHUNK_KERNEL)):
        compiled = jax.jit(fn, donate_argnums=(5,)).lower(*args).compile()
        text = compiled.as_text()
        assert len(re.findall("%" + name + r"(?:\.\d+)? = ", text)) == 1, name
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes >= pool_bytes, name
        assert memory.temp_size_in_bytes < 64e6, (
            name, memory.temp_size_in_bytes)
        for shape in (s.shape, s.shape[1:], s.shape[2:]):
            assert _ops_of_shape(text, "f32", shape) == {}, (name, shape)


def test_state_beside_pages_engine_programs_compile_for_v5e(
        one_chip, chip_compile, monkeypatch):
    """`decode` and `prefill` of `serve-jamba2-3b-reason-256-closed`
    (AI21-Jamba2-3B whole, 28 layers, 256 slots x 4096, page 16, chunk 512,
    65,536 pages of two attention layers' K/V beside 257 state entries of
    26 Mamba layers), abstract weights and pools, compiled for the chip:

    (a) `decode` holds the scan's decode kernel once a Mamba layer and the
        live-pages attention kernel once an attention layer, `prefill` the
        chunk kernel once a Mamba layer, each under its own name;
    (b) every pool is aliased to its argument in both programs and NO copy
        of one is made: nothing of the state pool's shape, of a layer's or
        an entry's slice of it is produced around the kernels; the windows
        (entries in sublanes) are a slice read and an in-place slice
        update a layer, never a re-laid image of the pool (entry-major they
        were: 25 whole copies a decode step);
    (c) arguments and temporaries fit a chip with room: under 15.5 GB."""
    from accelerate_tpu.models import jamba
    from accelerate_tpu.ops import paged_attention
    from accelerate_tpu.ops import selective_scan as ss
    from accelerate_tpu.serving import Engine, EngineConfig
    from accelerate_tpu.serving import engine as engine_module

    slots, max_len, chunk, page, pages = 256, 4096, 512, 16, 65536
    cfg = jamba.JambaConfig()

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: jamba.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    assert sum(x.size for x in jax.tree.leaves(params)) == 3_029_337_472
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert 6.05e9 < weights < 6.07e9
    # the pools are never allocated here: the engine is built over their
    # shapes (3.7 GB of zeros on this machine's CPU otherwise)
    create = engine_module.create_cache
    monkeypatch.setattr(
        engine_module, "create_cache",
        lambda *a, **k: on_chip(jax.eval_shape(lambda: create(*a, **k))))
    engine = Engine(jamba, cfg, params, EngineConfig(
        num_slots=slots, max_len=max_len, prefill_chunk=chunk,
        page_size=page, num_pages=pages, paged_attention=True,
        prefix_cache=False, max_queue=512))
    cache = engine.cache
    full, state = cache.groups[0], cache.state
    assert full.k.shape == (2, 65537, 1, 16, 128)
    assert state.s.shape == (26, 257, 1, 16, 5120)
    assert state.z.shape == (26, 3, 257, 5120)
    assert cache.pages_per_slot == 288 and cache.page_nbytes == 16 * 1024
    pool_bytes = 2 * full.k.size * 2 + (state.s.size + state.z.size) * 4
    assert 3.67e9 < pool_bytes < 3.68e9

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    regs = (params, cache, arg((slots,), jnp.int32),
            arg(engine._slot_keys.shape, engine._slot_keys.dtype),
            arg((slots,), jnp.float32))
    programs = {
        "decode": (engine._decode_p, regs + (
            arg((slots,), jnp.bool_), (arg((slots, 288), jnp.int32),))),
        "prefill": (engine._prefill_p, regs + (
            arg((), jnp.int32), (arg((288,), jnp.int32),),
            arg((chunk,), jnp.int32), arg((), jnp.int32))),
    }
    for name, (program, args) in programs.items():
        compiled = program.lower(*args).compile()
        text = compiled.as_text()
        memory = compiled.memory_analysis()
        calls = [len(re.findall("%" + n + r"(?:\.\d+)? = ", text))
                 for n in (ss.DECODE_KERNEL, ss.CHUNK_KERNEL,
                           paged_attention.KERNEL_NAME)]
        assert calls == ([26, 0, 2] if name == "decode" else [0, 26, 0]), (
            name, calls)
        assert memory.alias_size_in_bytes >= pool_bytes, name
        for shape in (state.s.shape, state.s.shape[1:], state.s.shape[2:]):
            assert _ops_of_shape(text, "f32", shape) == {}, (name, shape)
        assert set(_ops_of_shape(text, "f32", state.z.shape)) <= {
            "dynamic-update-slice", "fusion"}, name
        for shape in (full.k.shape, full.k.shape[1:]):
            assert set(_ops_of_shape(text, "bf16", shape)) <= {
                "scatter", "fusion"}, (name, shape)
        assert " copy(" not in "".join(
            line for line in text.splitlines()
            if "f32[26,3,257,5120]" in line.split(" copy(")[0][-60:]), name
        total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
        assert 9.5e9 < total < 15.5e9, (name, total)
        assert memory.temp_size_in_bytes < 400e6, (
            name, memory.temp_size_in_bytes)
        assert _ops_of_shape(text, "f32", (chunk, cfg.vocab_size)) == {}
        _assert_host_output_is_its_own(
            program, args, text, (slots,) if name == "decode" else ())
        print(name, "temp", memory.temp_size_in_bytes, "args",
              memory.argument_size_in_bytes)
