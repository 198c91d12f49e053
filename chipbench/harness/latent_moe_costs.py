"""Operations and bytes that latent paged attention and a routed expert
layer need, from shapes alone: the yardstick of the roofline shares of a
latent paged-attention kernel and of grouped expert products. Beside
`flops.py`, under the same rules: a multiply-add counts as two
operations, nothing computed twice counts twice, and what is counted is
the least an algorithm needs, not what a kernel happens to move.
"""

from __future__ import annotations


def latent_attention_cost(lengths, heads: int, key_width: int,
                          value_width: int, row_bytes_per_el: int = 2,
                          io_bytes_per_el: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE absorbed latent decode-attention call of
    ONE layer: each slot's one query token (all `heads` of it) attends to
    its `length` cached tokens plus its own new token. A cached token is
    one row of `key_width` numbers (the compressed KV and the shared rope
    key) whose first `value_width` are also the value: operations are a
    score over `key_width` and a weighted sum over `value_width` per head
    and token; bytes are every live row ONCE (it serves all heads, and as
    key and value both), the absorbed queries read and the latent outputs
    written."""
    ops = 0.0
    byts = 0.0
    for n in lengths:
        ctx = n + 1
        ops += 2 * (key_width + value_width) * heads * ctx
        byts += ctx * key_width * row_bytes_per_el
        byts += heads * (key_width + value_width) * io_bytes_per_el
    return ops, byts


def expert_products_cost(token_expert_pairs: float, experts_touched: float,
                         hidden: int, expert_width: int,
                         weight_bytes_per_el: int = 2) -> tuple[float, float]:
    """(operations, bytes) of the routed experts' three products (gate,
    up, down) of ONE expert layer in ONE call: `2 x 3 x hidden x
    expert_width` operations a routed token-expert pair, and the three
    matrices of every expert that got a token read once. Activations are
    left out of the bytes: a few per cent of the weights at a chunk's
    size, and what a kernel needs of them depends on its tiling."""
    per_expert = 3 * hidden * expert_width
    return (2.0 * per_expert * token_expert_pairs,
            float(per_expert * weight_bytes_per_el * experts_touched))
