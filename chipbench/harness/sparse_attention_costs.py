"""Operations and bytes that decode attention over SELECTED keys needs
(a learned indexer scores every cached key, the `topk` best are attended),
from shapes alone: the yardstick of the roofline share of the indexer-score
kernel, the selection and the sparse paged-attention kernel together.
Beside `flops.py`, under the same rules: a multiply-add counts as two
operations, nothing computed twice counts twice, and what is counted is
the least an algorithm needs, not what a kernel happens to move (a kernel
that copies every page holding a selected key reads more than the selected
rows; that is its loss, not the yardstick's). The selection itself is
counted as free: a k-th value needs no operation on the MXU and no byte
beyond the scores, which never have to leave the chip's vector memory.
"""

from __future__ import annotations


def keys_selected(length: int, topk: int) -> int:
    """Keys the one query token of a slot attends to in one layer: the
    `topk` best of its `length` cached tokens and itself, all of them
    while there are no more than `topk`."""
    return min(length + 1, topk)


def indexer_score_cost(lengths, index_heads: int, index_width: int,
                       key_bytes_per_el: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE indexer-score call of ONE layer: each
    slot's `index_heads` index queries of `index_width` lanes against the
    ONE index key of each of its `length` cached tokens (a product over
    `index_width` a head and key, then a ReLU, a weight and a sum that are
    not counted); every cached index key read once."""
    ops = 0.0
    byts = 0.0
    for n in lengths:
        ops += 2.0 * index_heads * index_width * n
        byts += float(n) * index_width * key_bytes_per_el
    return ops, byts


def sparse_attention_cost(lengths, topk: int, heads: int, kv_heads: int,
                          head_dim: int, row_bytes_per_el: int = 2,
                          io_bytes_per_el: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE sparse decode-attention call of ONE
    layer: each slot's one query token (all `heads` of it) attends to its
    `keys_selected` keys: a score and a weighted sum over `head_dim` a head
    and key; the K row and the V row (`kv_heads x head_dim` each) of every
    selected key read once, a slot's q read and its output written."""
    ops = 0.0
    byts = 0.0
    for n in lengths:
        k = keys_selected(n, topk)
        ops += 2.0 * 2 * head_dim * heads * k
        byts += 2.0 * k * kv_heads * head_dim * row_bytes_per_el
        byts += 2.0 * heads * head_dim * io_bytes_per_el
    return ops, byts
