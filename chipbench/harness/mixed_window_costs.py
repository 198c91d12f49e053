"""Operations and bytes that paged decode attention needs in a model whose
layers differ in kind (sliding-window and full attention mixed by layer),
from shapes alone: the yardstick of the roofline share of the two
paged-attention kernels of such a model. Beside `flops.py`, under the same
rules: a multiply-add counts as two operations, nothing computed twice
counts twice, and what is counted is the least an algorithm needs, not
what a kernel happens to move (a kernel that copies whole groups of pages
reads more than the keys a window reaches; that is its loss, not the
yardstick's).
"""

from __future__ import annotations

from chipbench.harness import flops


def keys_seen(length: int, window: int | None) -> int:
    """Keys the one query token of a slot attends to in one layer: its
    `length` cached tokens and itself in a full layer (`window` None), the
    last `window` of those in a sliding layer (key j is seen from position
    i iff 0 <= i - j < window)."""
    ctx = length + 1
    return ctx if window is None else min(ctx, window)


def decode_attention_cost(lengths, heads: int, kv_heads: int, head_dim: int,
                          window: int | None = None) -> tuple[float, float]:
    """(operations, bytes) of ONE paged decode-attention call of ONE layer
    of the kind `window` says, over the slots' `lengths`:
    `flops.paged_attention_cost` (a score and a weighted sum over
    `head_dim` for each of `heads` query heads a key, its K and V rows of
    `kv_heads x head_dim` bf16 numbers read once, a slot's q read and its
    output written) over the keys each slot SEES in such a layer."""
    return flops.paged_attention_cost(
        [keys_seen(n, window) - 1 for n in lengths], heads, kv_heads,
        head_dim)


def layer_windows(cfg: dict) -> list:
    """The window of every layer of a configuration with `layer_types`:
    None for a `full_attention` layer, `sliding_window` for a
    `sliding_attention` one."""
    return [cfg["sliding_window"] if kind == "sliding_attention" else None
            for kind in cfg["layer_types"]]
