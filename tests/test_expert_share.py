"""An expert layer that is TOLD which experts it holds
(`ops/grouped_experts.py` `grouped_swiglu_experts(..., experts_held=(first,
count))`; `models/deepseek.py` `moe_layer` reads `config.experts_held`): the
router chooses among all experts and normalises over all the chosen, an
assignment to an absent expert is dropped before the grouped products, and
the result is the held experts' PART. The shares of one layer add up to the
uncut layer; a token none of whose experts is held gets zero; both grouped
products take a share; and a layer that is told nothing compiles to the
program it compiled to before there was a share to tell."""

import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import common, deepseek, dots3, keye, mellum
from accelerate_tpu.ops import grouped_experts as ge

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "dots3_note_reference_for_shares", os.path.join(
            ROOT, "chipbench", "references", "dots3_note.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
E, K, H, F, T = 8, 2, 64, 32, 40


def _layer(seed=0):
    k = jax.random.split(jax.random.key(seed), 9)
    w = lambda key, *shape: jax.random.normal(key, shape) * 0.05  # noqa: E731
    return {
        "router": {"kernel": w(k[0], H, E),
                   "e_score_correction_bias": w(k[1], E)},
        "experts": {"gate_proj": w(k[2], E, H, F), "up_proj": w(k[3], E, H, F),
                    "down_proj": w(k[4], E, F, H)},
        "shared": {"gate_proj": {"kernel": w(k[5], H, F)},
                   "up_proj": {"kernel": w(k[6], H, F)},
                   "down_proj": {"kernel": w(k[7], F, H)}},
    }, jax.random.normal(k[8], (1, T, H))


def _share(layer, first, count):
    """The layer as the chip that holds experts first .. first+count-1
    has it: the whole router, its own experts' matrices."""
    cut = {name: m[first:first + count]
           for name, m in layer["experts"].items()}
    return dict(layer, experts=cut)


def _cfg(held=None):
    return dots3.Dots3Config.tiny(n_routed_experts=E, num_experts_per_tok=K,
                                  experts_held=held)


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts in 4 shares of 2: the routed parts of all four shares plus
    the shared expert counted ONCE are the uncut REFERENCE's whole layer
    (every expert by a masked combine), and the uncut program's."""
    layer, x = _layer()
    with jax.default_matmul_precision("highest"):
        ref_cfg = {"num_experts_per_tok": K, "norm_topk_prob": True,
                   "routed_scaling_factor": 1.0, "experts_held": [0, E]}
        whole = REF.routed(ref_cfg, layer, x[0]) + REF.shared(layer, x[0])
        shared = np.asarray(REF.shared(layer, x[0]))
        parts = []
        for first in range(0, E, 2):
            y, counts = deepseek.moe_layer(_cfg((first, 2)),
                                           _share(layer, first, 2), x)
            # every chip computes the shared expert alike: counted once
            parts.append(np.asarray(y[0]) - shared)
            assert counts.shape == (E,) and int(counts.sum()) == T * K
            # the reference, given the same share, gives the same part
            want = REF.routed(dict(ref_cfg, experts_held=[first, 2]),
                              _share(layer, first, 2), x[0])
            assert np.abs(parts[-1] - np.asarray(want)).max() < 1e-5
        uncut, _ = deepseek.moe_layer(_cfg(None), layer, x)
    assert np.abs(sum(parts) + shared - np.asarray(whole)).max() < 1e-5
    assert np.abs(np.asarray(uncut[0]) - np.asarray(whole)).max() < 1e-5
    # no part is the whole, and none is nothing
    assert all(1e-3 < np.abs(p).max() for p in parts)
    assert np.abs(parts[0] + shared - np.asarray(whole)).max() > 1e-3


def test_a_token_with_no_held_expert_gets_the_shared_expert_only():
    layer, x = _layer(1)
    experts, _ = ge.sigmoid_topk_route(
        x[0], layer["router"]["kernel"],
        layer["router"]["e_score_correction_bias"], K)
    held = np.asarray((experts >= 6).any(axis=-1))
    assert held.any() and not held.all()
    y, _ = deepseek.moe_layer(_cfg((6, 2)), _share(layer, 6, 2), x)
    shared = deepseek._swiglu(layer["shared"], x[0])
    routed = np.asarray(y[0] - shared)
    assert np.abs(routed[~held]).max() == 0.0
    assert np.abs(routed[held]).min(axis=0).max() > 0


def test_the_weights_are_normalised_over_all_the_chosen():
    """A token with ONE of its two experts held here gets that expert at
    the weight the router gave it among both, not at 1."""
    layer, x = _layer(2)
    experts, weights = ge.sigmoid_topk_route(
        x[0], layer["router"]["kernel"],
        layer["router"]["e_score_correction_bias"], K)
    e = layer["experts"]
    got = ge.grouped_swiglu_experts(
        x[0], experts, weights, e["gate_proj"][:4], e["up_proj"][:4],
        e["down_proj"][:4], experts_held=(0, 4))
    one = np.flatnonzero(np.asarray(((experts < 4).sum(-1) == 1)))
    assert len(one)
    t = int(one[0])
    j = int(np.flatnonzero(np.asarray(experts[t] < 4))[0])
    idx = int(experts[t, j])
    alone = jax.nn.silu(x[0, t] @ e["gate_proj"][idx]) * (
        x[0, t] @ e["up_proj"][idx]) @ e["down_proj"][idx]
    assert 0.05 < float(weights[t, j]) < 0.95
    np.testing.assert_allclose(np.asarray(got[t]),
                               np.asarray(alone * weights[t, j]),
                               rtol=1e-4, atol=1e-6)


def test_a_share_says_how_many_experts_it_holds():
    layer, x = _layer()
    e = layer["experts"]
    experts, weights = ge.sigmoid_topk_route(
        x[0], layer["router"]["kernel"],
        layer["router"]["e_score_correction_bias"], K)
    with pytest.raises(ValueError, match="names 2 experts"):
        ge.grouped_swiglu_experts(x[0], experts, weights, e["gate_proj"],
                                  e["up_proj"], e["down_proj"],
                                  experts_held=(0, 2))
    for bad in ((7, 2), (-1, 2), (0, 0), (0, 2, 4)):
        with pytest.raises(ValueError, match="experts_held"):
            _cfg(bad)
    assert _cfg((2, 4)).experts_here == 4 and _cfg().experts_here == E


def test_the_rows_kernel_takes_a_share():
    """Few rows an expert on matrices of whole lane tiles: the held
    shapes pick the Pallas rows kernel (interpreted here), which walks
    the held experts that have rows and no other; a call that leaves
    every held expert without a row gives zeros, not what the kernel
    never wrote."""
    k = jax.random.split(jax.random.key(3), 5)
    h, f, held, tokens = 128, 128, 4, 6
    assert ge.few_rows_an_expert(tokens * K, held, h, f, jnp.float32)
    assert not ge.few_rows_an_expert(4096, held, h, f, jnp.float32)
    x = jax.random.normal(k[0], (tokens, h))
    gate, up = (jax.random.normal(k[i], (16, h, f)) * 0.05 for i in (1, 2))
    down = jax.random.normal(k[3], (16, f, h)) * 0.05
    experts = jax.random.randint(k[4], (tokens, K), 0, 16)
    weights = jnp.full((tokens, K), 0.5)
    whole = ge.grouped_swiglu_experts(x, experts, weights, gate, up, down)
    parts = [ge.grouped_swiglu_experts(
        x, experts, weights, gate[a:a + held], up[a:a + held],
        down[a:a + held], experts_held=(a, held)) for a in range(0, 16, held)]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    nobody = ge.grouped_swiglu_experts(
        x, jnp.full((tokens, K), 9), weights, gate[:held], up[:held],
        down[:held], experts_held=(0, held))
    assert np.abs(np.asarray(nobody)).max() == 0.0


def _chunk_operands(choose):
    """64 tokens x 4 over 16 experts of [128, 256]: many rows an expert
    for a share of 4; `choose(rng)` draws a token's 4 experts."""
    k = jax.random.split(jax.random.key(5), 4)
    rng = np.random.default_rng(5)
    h, f, tokens = 128, 256, 64
    x = jax.random.normal(k[0], (tokens, h))
    gate, up = (jax.random.normal(k[i], (16, h, f)) * 0.05 for i in (1, 2))
    down = jax.random.normal(k[3], (16, f, h)) * 0.05
    experts = jnp.asarray(np.stack([choose(rng) for _ in range(tokens)]),
                          jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 0.9, (tokens, 4)), jnp.float32)
    return x, experts, weights, gate, up, down


WHERE_A_CHUNK_LANDS = {
    # its share of a chunk's 256 assignments -> passes of 128 rows
    "a-share-of-them": (lambda r: r.choice(16, 4, replace=False), 1),
    "nearly-all-here": (lambda r: np.append(
        r.choice(4, 3, replace=False), r.choice(16)), 2),
    "none-here": (lambda r: 4 + r.choice(12, 4, replace=False), 0),
}


@pytest.mark.parametrize("case", WHERE_A_CHUNK_LANDS)
def test_a_chunks_held_rows_go_through_the_rows_kernel_in_windows(
        case, monkeypatch):
    """MANY rows an expert and a share: the held rows alone are
    multiplied, a window of sorted rows a pass and as many passes as they
    need (dropless: all of a chunk's rows if they all land here, none if
    none does), by the rows kernel in blocks of lanes; `ragged_dot` is not
    in the program. The first share's part is the reference's, expert by
    expert."""
    choose, passes = WHERE_A_CHUNK_LANDS[case]
    monkeypatch.setattr(ge, "HELD_ROWS_WINDOW", 128)
    x, experts, weights, gate, up, down = _chunk_operands(choose)
    assert ge.held_rows_in_windows(64 * 4, 4, 128, 256, jnp.float32)
    assert not ge.held_rows_in_windows(6 * 4, 4, 128, 256, jnp.float32)
    assert not ge.held_rows_in_windows(64 * 4, 4, 64, 32, jnp.float32)
    held = int(np.sum(np.asarray(experts) < 4))
    assert -(-held // 128) == passes
    fn = functools.partial(ge.grouped_swiglu_experts, experts_held=(0, 4))
    text = str(jax.make_jaxpr(fn)(x, experts, weights, gate[:4], up[:4],
                                  down[:4]))
    assert "ragged_dot" not in text and "while" in text
    got = fn(x, experts, weights, gate[:4], up[:4], down[:4])
    want = jnp.zeros_like(x)
    with jax.default_matmul_precision("highest"):
        for e in range(4):
            w = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
            want = want + w[:, None] * (
                (jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_the_windowed_shares_add_up_to_the_uncut_layer(monkeypatch):
    """16 experts in 4 shares of 4, each share's rows through the rows
    kernel in windows: the parts add up to the uncut layer's `ragged_dot`."""
    monkeypatch.setattr(ge, "HELD_ROWS_WINDOW", 128)
    x, experts, weights, gate, up, down = _chunk_operands(
        WHERE_A_CHUNK_LANDS["a-share-of-them"][0])
    whole = ge.grouped_swiglu_experts(x, experts, weights, gate, up, down)
    parts = [ge.grouped_swiglu_experts(
        x, experts, weights, gate[a:a + 4], up[a:a + 4], down[a:a + 4],
        experts_held=(a, 4)) for a in range(0, 16, 4)]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)


def test_a_block_of_lanes_fits_the_rows_kernels_vmem():
    """The cell's matrices (5120 x 1536 and back, bf16, 15.7 MB) go
    through in blocks of under 8 MB that divide them in whole lane tiles."""
    assert ge._lane_tile(5120, 1536, jnp.bfloat16) == 768
    assert ge._lane_tile(1536, 5120, jnp.bfloat16) == 2560
    assert ge._lane_tile(128, 256, jnp.float32) == 256
    assert ge._lane_tile(1 << 16, 256, jnp.float32) == 128


# ---------------------------------------------------------------------------
# a layer that is told nothing is the layer it was
# ---------------------------------------------------------------------------


def _parent_grouped_swiglu_experts(x, experts, weights, gate, up, down):
    """`grouped_swiglu_experts` as it stood before a layer could be told
    its share (PR 41's tree), kept here to compile against."""
    T, k = experts.shape
    E, h, f = gate.shape
    with common.part("moe.sort"):
        flat = experts.reshape(T * k)
        order = jnp.argsort(flat, stable=True)
        sizes = ge.expert_counts(experts, E)
        rows = x[order // k]
    with common.part("moe.experts"):
        if ge.few_rows_an_expert(T * k, E, h, f, x.dtype):
            product = functools.partial(ge.grouped_rows_matmul, sizes=sizes)
        else:
            def product(a, w):
                return jax.lax.ragged_dot(a, w.astype(a.dtype), sizes,
                                          preferred_element_type=jnp.float32)

        act = (jax.nn.silu(product(rows, gate))
               * product(rows, up)).astype(x.dtype)
        out = product(act, down)
    with common.part("moe.combine"):
        back = jnp.zeros((T * k,), order.dtype).at[order].set(
            jnp.arange(T * k, dtype=order.dtype))
        return jnp.sum(out[back].reshape(T, k, -1)
                       * weights[:, :, None].astype(jnp.float32), axis=1)


def _text(layer_fn, config, m, x):
    """The compiled expert layer without metadata, instructions numbered
    in order of appearance (names are metadata too)."""
    text = jax.jit(lambda m, x: layer_fn(config, m, x)).lower(
        m, x).compile().as_text()
    text = re.sub(r', metadata=\{(?:[^}"]|"[^"]*")*\}', "", text)
    text = re.sub(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(?:.+\n)*", "", text, flags=re.M)
    names: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda g: names.setdefault(g.group(0), f"%{len(names)}"),
                  text)


@pytest.mark.parametrize("family", ["joyai", "mellum", "keye"])
def test_a_layer_that_holds_every_expert_compiles_to_the_program_it_had(
        family, monkeypatch):
    """The three cells that hold every expert: their expert layer (the
    sigmoid-routed one with a shared expert; the softmax-routed one at
    mellum's and at keye's tiny shapes), traced through today's
    `grouped_swiglu_experts` and through the parent's, compiles to one
    text."""
    if family == "joyai":
        cfg, fn = deepseek.DeepseekConfig.tiny(), deepseek.moe_layer
        m = deepseek.init_params(cfg, jax.random.key(0))["layers"][1]["moe"]
    else:
        module = {"mellum": mellum, "keye": keye}[family]
        cfg = {"mellum": mellum.MellumConfig, "keye": keye.KeyeConfig}[
            family].tiny()
        fn = common.softmax_moe_layer
        m = module.init_params(cfg, jax.random.key(0))["layers"][0]["moe"]
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.hidden_size))
    now = _text(fn, cfg, m, x)
    assert "ragged" in now or " dot(" in now
    monkeypatch.setattr(ge, "grouped_swiglu_experts",
                        _parent_grouped_swiglu_experts)
    monkeypatch.setattr(deepseek, "grouped_swiglu_experts",
                        lambda *a, experts_held=None:
                        _parent_grouped_swiglu_experts(*a))
    assert _text(fn, cfg, m, x) == now
