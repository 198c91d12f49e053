"""A dropless expert layer: route, sort the assignments by expert, grouped
matrix products over the sorted rows, weighted sum back per token.

`parallel/moe.py` dispatches into fixed-capacity buffers (tokens over
capacity are dropped) and `models/mixtral.py`'s "dense" form runs every
expert on every token. Neither serves a model with hundreds of small
experts: here no token is dropped at any imbalance, the products cost
`tokens x top_k` expert applications (not `tokens x num_experts`), and an
expert's weights are read once a call. The grouped product is
`jax.lax.ragged_dot`, which the TPU compiler lowers to a grouped-matmul
kernel (`ragged-dot` in a device trace) and every other backend to a
plain masked product.

Shapes are static whatever the routing: `tokens * top_k` rows, sorted by
expert, with the group sizes as data.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["sigmoid_topk_route", "softmax_topk_route", "expert_counts",
           "grouped_swiglu_experts"]


def sigmoid_topk_route(x, router_kernel, correction_bias, top_k: int,
                       scaling_factor: float = 1.0, norm_topk: bool = True):
    """`noaux_tc` routing with one group: scores `s = sigmoid(x W_r)` in
    float32; the experts are the `top_k` of `s + correction_bias`; their
    weights are `s` at those experts WITHOUT the bias, divided by their
    sum (`norm_topk`), times `scaling_factor`. x [T, h], router_kernel
    [h, E], correction_bias [E] -> (experts [T, k] int32, weights [T, k]
    float32). Operands that are bfloat16 values multiply exactly into the
    float32 accumulator, so this is the float32 product of the published
    router on such inputs."""
    with jax.named_scope("moe.route"):
        scores = jax.nn.sigmoid(jnp.dot(
            x, router_kernel.astype(x.dtype),
            preferred_element_type=jnp.float32))
        _, experts = jax.lax.top_k(
            scores + correction_bias.astype(jnp.float32), top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if norm_topk:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                 + 1e-20)
        return experts.astype(jnp.int32), weights * scaling_factor


def softmax_topk_route(x, router_kernel, top_k: int, norm_topk: bool = True):
    """Softmax routing: `p = softmax(x W_r)` over ALL experts in float32;
    the experts are the `top_k` of `p` and their weights `p` at those
    experts, divided by their sum where `norm_topk`. No bias in the
    choice and no scaling factor. x [T, h], router_kernel [h, E] ->
    (experts [T, k] int32, weights [T, k] float32)."""
    with jax.named_scope("moe.route"):
        probs = jax.nn.softmax(jnp.dot(
            x, router_kernel.astype(x.dtype),
            preferred_element_type=jnp.float32), axis=-1)
        weights, experts = jax.lax.top_k(probs, top_k)
        if norm_topk:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return experts.astype(jnp.int32), weights


def expert_counts(experts, num_experts: int, token_mask=None):
    """Assignments per expert [E] int32 of `experts` [T, k]; tokens whose
    `token_mask` [T] is False (padding, dead lanes) are not counted."""
    hit = experts[:, :, None] == jnp.arange(num_experts, dtype=jnp.int32)
    if token_mask is not None:
        hit = hit & token_mask[:, None, None]
    return jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)


def grouped_swiglu_experts(x, experts, weights, gate, up, down):
    """`y[t] = sum_k weights[t, k] * E_{experts[t, k]}(x[t])` with every
    expert `W_down(silu(W_gate x) * W_up x)`. x [T, h]; experts, weights
    [T, k]; gate, up [E, h, f]; down [E, f, h]. Products take x's dtype
    with float32 accumulation; returns float32 [T, h]."""
    T, k = experts.shape
    E = gate.shape[0]
    with jax.named_scope("moe.sort"):
        flat = experts.reshape(T * k)
        order = jnp.argsort(flat, stable=True)
        sizes = expert_counts(experts, E)
        rows = x[order // k]                                # [T * k, h]
    with jax.named_scope("moe.experts"):
        def product(a, w):
            return jax.lax.ragged_dot(a, w.astype(a.dtype), sizes,
                                      preferred_element_type=jnp.float32)

        act = (jax.nn.silu(product(rows, gate))
               * product(rows, up)).astype(x.dtype)
        out = product(act, down)                            # [T * k, h] f32
    with jax.named_scope("moe.combine"):
        back = jnp.zeros((T * k,), order.dtype).at[order].set(
            jnp.arange(T * k, dtype=order.dtype))
        return jnp.sum(out[back].reshape(T, k, -1)
                       * weights[:, :, None].astype(jnp.float32), axis=1)
