"""The LM head and its cross-entropy as one op (`common.fused_head_loss`,
`llama.causal_lm_loss` / `loss_plan`): same loss and gradients as the
whole-logits path, gradients made in the forward, one vocabulary-wide
projection. CPU, float32 unless a case says bf16, tiny shapes."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import llama
from accelerate_tpu.models.common import fused_head_loss, token_nll

B, S, H, V = 2, 48, 64, 256  # LlamaConfig.tiny()'s widths; S = 48 labels


def _batch(mask_kind):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, V, (B, S + 1)).astype(np.int32)
    if mask_kind == "none":
        return {"input_ids": ids}
    mask = np.ones((B, S + 1), np.int32)
    mask[0, 30:] = 0  # a row with a masked tail
    if mask_kind == "row":
        mask[1, :] = 0  # and a fully masked row
    return {"input_ids": ids, "attention_mask": mask}


@functools.lru_cache(maxsize=None)
def _model(tied):
    cfg = llama.LlamaConfig.tiny(max_position_embeddings=64,
                                 tie_word_embeddings=tied)
    return cfg, llama.init_params(cfg, jax.random.key(0))


@functools.lru_cache(maxsize=None)
def _value_and_grad(tied, mask_kind, chunk):
    cfg, params = _model(tied)
    batch = _batch(mask_kind)
    out = jax.jit(jax.value_and_grad(
        lambda p: llama.causal_lm_loss(cfg, p, batch, loss_chunk_size=chunk))
    )(params)
    return jax.tree_util.tree_map(np.asarray, out)


def _assert_trees_close(got, want, **tol):
    got_leaves, want_leaves = (jax.tree_util.tree_leaves_with_path(t)
                               for t in (got, want))
    assert len(got_leaves) == len(want_leaves)
    for (path, a), (_, b) in zip(got_leaves, want_leaves):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            err_msg=jax.tree_util.keystr(path), **tol)


@pytest.mark.parametrize("chunk", [24, 12], ids=["2blocks", "4blocks"])
@pytest.mark.parametrize("mask_kind", ["none", "tail", "row"])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_loss_and_all_gradients_match_whole_logits(tied, mask_kind, chunk):
    assert llama.loss_plan(B, S, V, chunk)["path"] == "fused"
    assert llama.loss_plan(B, S, V, 10_000)["path"] == "full"
    loss, grads = _value_and_grad(tied, mask_kind, chunk)
    full, g_full = _value_and_grad(tied, mask_kind, 10_000)
    np.testing.assert_allclose(loss, full, rtol=2e-6)
    _assert_trees_close(grads, g_full, rtol=2e-5, atol=2e-6)
    if mask_kind == "row":  # the count guard: a masked row adds nothing
        assert np.isfinite(loss) and loss > 0


def _op_inputs(tied, dtype=jnp.float32):
    k = jax.random.split(jax.random.key(3), 4)
    hidden = jax.random.normal(k[0], (B, S, H), jnp.float32).astype(dtype)
    head = (0.1 * jax.random.normal(
        k[1], (V, H) if tied else (H, V), jnp.float32)).astype(dtype)
    labels = jax.random.randint(k[2], (B, S), 0, V)
    weights = (jax.random.uniform(k[3], (B, S)) > 0.2).astype(jnp.float32)
    return hidden, head, labels, weights


def _plain_loss_sum(hidden, head, labels, weights, tied):
    logits = jnp.einsum("bsh,vh->bsv" if tied else "bsh,hv->bsv", hidden,
                        head, preferred_element_type=jnp.float32)
    return jnp.sum(token_nll(logits, labels) * weights)


@pytest.mark.parametrize("blocks", [1, 2, 4])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_op_matches_plain_projection_and_nll(tied, blocks):
    """The op alone, a block that is the whole S included."""
    hidden, head, labels, weights = _op_inputs(tied)
    got = jax.value_and_grad(
        lambda h, w: fused_head_loss(h, w, labels, weights, tied, S // blocks),
        argnums=(0, 1))(hidden, head)
    want = jax.value_and_grad(
        lambda h, w: _plain_loss_sum(h, w, labels, weights, tied),
        argnums=(0, 1))(hidden, head)
    _assert_trees_close(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("scale", [0.5, 3.0])
def test_backward_scales_both_gradients_by_the_cotangent(scale):
    hidden, head, labels, weights = _op_inputs(True)
    _, vjp = jax.vjp(
        lambda h, w: fused_head_loss(h, w, labels, weights, True, S // 2),
        hidden, head)
    unit, scaled = vjp(jnp.float32(1.0)), vjp(jnp.float32(scale))
    _assert_trees_close(scaled, jax.tree_util.tree_map(
        lambda g: scale * g, unit), rtol=1e-6)
    assert float(jnp.abs(unit[0]).max()) > 0 and float(jnp.abs(unit[1]).max()) > 0


def test_not_differentiated_it_computes_the_loss_alone():
    hidden, head, labels, weights = _op_inputs(False)
    got = fused_head_loss(hidden, head, labels, weights, False, S // 4)
    want = _plain_loss_sum(hidden, head, labels, weights, False)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    jaxpr = jax.make_jaxpr(
        lambda h, w: fused_head_loss(h, w, labels, weights, False, S // 4)
    )(hidden, head)
    assert len(_vocab_wide_products(jaxpr.jaxpr, V)) == 1


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_bf16_operands_stay_within_bf16_noise_of_float32(tied):
    inputs32 = _op_inputs(tied)
    hidden, head = (x.astype(jnp.bfloat16) for x in inputs32[:2])
    labels, weights = inputs32[2:]

    def grads(h, w):
        return jax.value_and_grad(
            lambda h, w: fused_head_loss(h, w, labels, weights, tied, S // 2),
            argnums=(0, 1))(h, w)

    loss, (dh, dw) = grads(hidden, head)
    # float32 arithmetic on the SAME (bf16-rounded) operands
    loss32, (dh32, dw32) = grads(hidden.astype(jnp.float32),
                                 head.astype(jnp.float32))
    assert dh.dtype == jnp.bfloat16 and dw.dtype == jnp.bfloat16
    np.testing.assert_allclose(loss, loss32, rtol=1e-3)
    for got, want in ((dh, dh32), (dw, dw32)):
        got, want = np.asarray(got, np.float32), np.asarray(want)
        # bf16 keeps 8 bits: d rounded once (2^-9 relative) into sums of
        # up to B * S terms, then the result rounded once
        assert np.abs(got - want).max() <= 2e-2 * np.abs(want).max()
        assert (np.linalg.norm(got - want)
                <= 1e-2 * np.linalg.norm(want))


def _vocab_wide_products(jaxpr, vocab):
    """Every dot_general of a jaxpr (sub-jaxprs included) with a
    vocabulary-sized dimension on an operand or on its result."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                vocab in v.aval.shape for v in (*eqn.invars, *eqn.outvars)):
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_vocab_wide_products(sub, vocab))
    return found


def _all_shapes(jaxpr):
    for eqn in jaxpr.eqns:
        for v in (*eqn.invars, *eqn.outvars):
            if hasattr(v.aval, "shape"):
                yield tuple(v.aval.shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_shapes(sub)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_gradient_program_projects_the_logits_once(tied):
    """Three vocabulary-wide products (logits, hidden rows' gradient, head's
    gradient), no [B, S, V] array: a second projection in the backward, or
    whole logits, cannot come back unnoticed."""
    # a vocabulary no other dimension of the model equals
    cfg = llama.LlamaConfig.tiny(max_position_embeddings=64, vocab_size=272,
                                 tie_word_embeddings=tied)
    params = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    batch = {"input_ids": jax.ShapeDtypeStruct((B, S + 1), jnp.int32)}
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, b: llama.causal_lm_loss(cfg, p, b, loss_chunk_size=12))
    )(params, batch).jaxpr
    products = _vocab_wide_products(jaxpr, 272)
    assert len(products) == 3, [str(e) for e in products]
    shapes = set(_all_shapes(jaxpr))
    assert (B, S, 272) not in shapes
    assert (B, 12, 272) in shapes  # one block of logits is what exists


def test_loss_plan_picks_from_shapes_alone():
    plan = llama.loss_plan(2, 2048, 151936)
    assert plan["path"] == "fused"
    assert plan["rows_per_block"] >= 1024
    assert plan["rows_per_block"] * plan["blocks"] == 2 * 2048
    # one block of float32 logits stays under the budget in the code
    assert plan["rows_per_block"] * 151936 * 4 <= llama._LOGITS_BLOCK_BYTES
    # the caller's explicit word wins
    assert llama.loss_plan(2, 2048, 151936, 128) == {
        "path": "fused", "rows_per_block": 256, "blocks": 16}
    assert llama.loss_plan(2, 2048, 151936, 2048)["path"] == "full"
    # S does not divide (prime), or the whole logits fit one block
    assert llama.loss_plan(2, 2039, 151936)["path"] == "full"
    assert llama.loss_plan(2, 48, 256) == {
        "path": "full", "rows_per_block": 96, "blocks": 1}
    # a wider batch takes fewer positions a block, a narrower head more
    assert (llama.loss_plan(16, 2048, 151936)["rows_per_block"]
            <= plan["rows_per_block"])
    assert llama.loss_plan(16, 2048, 32000)["blocks"] < 16


def test_fp8_state_passes_through_the_fused_path():
    cfg, params = _model(True)
    batch = _batch("tail")
    fp8 = llama.init_fp8_state(cfg)

    def loss_fn(p, chunk):
        return llama.causal_lm_loss(cfg, p, batch, loss_chunk_size=chunk,
                                    fp8_state=fp8)

    (loss, new_fp8), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, 12), has_aux=True))(params)
    (full, full_fp8), g_full = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, 10_000), has_aux=True))(params)
    np.testing.assert_allclose(loss, full, rtol=2e-6)
    _assert_trees_close(grads, g_full, rtol=2e-5, atol=2e-6)
    _assert_trees_close(new_fp8, full_fp8, rtol=1e-6)


@pytest.mark.parametrize("axes", [{"data": 2, "fsdp": 4},
                                  {"data": 2, "model": 4}],
                         ids=["data2-fsdp4", "data2-model4"])
def test_sharded_params_and_batch_give_the_same_loss_and_gradients(axes):
    """Under a mesh (GSPMD): the op traces under `jit` with a sharded head
    and a batch sharded over `data`, and gives the single-device numbers."""
    import optax

    from accelerate_tpu import TrainState
    from accelerate_tpu.accelerator import Accelerator
    from accelerate_tpu.utils import MeshConfig

    cfg, params = _model(True)
    batch = _batch("tail")
    want_loss, want_grads = _value_and_grad(True, "tail", 10_000)

    acc = Accelerator(mesh_config=MeshConfig(axes=axes))
    state = acc.prepare(TrainState.create(apply_fn=None, params=params,
                                           tx=optax.sgd(0.0)))
    (sharded_batch,) = list(acc.prepare([batch]))
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, b: llama.causal_lm_loss(cfg, p, b, loss_chunk_size=12))
    )(state.params, sharded_batch)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    _assert_trees_close(grads, want_grads, rtol=1e-4, atol=1e-5)
