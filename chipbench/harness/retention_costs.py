"""Operations and bytes that power retention needs, from shapes alone: the
yardstick of the roofline shares of the decode step's and the prefill
chunk's retention. Beside `flops.py`, under the same rules: a multiply-add
counts as two operations, nothing computed twice counts twice, and what is
counted is the least the algorithm needs, not what a kernel happens to
move. So the state of a head counts `d (d + 1) / 2` rows (the symmetric
second power of a `d`-lane key: 8,256 for d = 128), whatever layout a
program pads it to.
"""

from __future__ import annotations


def state_rows(head_dim: int, degree: int = 2) -> int:
    """Rows of a KV head's state: the entries of the symmetric `degree`-th
    power of a `head_dim`-lane vector."""
    if degree == 1:
        return head_dim
    if degree == 2:
        return head_dim * (head_dim + 1) // 2
    raise ValueError(f"power retention of degree 1 or 2; got {degree}")


def decode_step_cost(live_lanes: int, heads: int, kv_heads: int,
                     head_dim: int, degree: int = 2, state_bytes_per_el: int = 4,
                     io_bytes_per_el: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE decode step of ONE layer: every live lane
    decays its state, adds `phi(k) v^T`, and answers its query heads from
    it. Bytes: the state `S` (rows x head_dim a KV head) and the
    normaliser `z` (rows a KV head) read AND written once; q, k, v and the
    gate in, o out. Operations: a multiply and a multiply-add an entry of
    `S` and of `z` for the update (3), and a multiply-add an entry and
    query head for `phi(q)^T S` and `phi(q) . z` (2 each)."""
    rows = state_rows(head_dim, degree)
    entries = kv_heads * rows * (head_dim + 1)          # S and z
    ops = 3.0 * entries + 2.0 * heads * rows * (head_dim + 1)
    byts = (2.0 * entries * state_bytes_per_el
            + (2 * heads + 2 * kv_heads) * head_dim * io_bytes_per_el
            + kv_heads * 4)
    return live_lanes * ops, live_lanes * byts


def chunk_cost(chunk: int, heads: int, kv_heads: int, head_dim: int,
               degree: int = 2, state_bytes_per_el: int = 4,
               io_bytes_per_el: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE prefill chunk of ONE layer of ONE
    sequence, its three products: inside the chunk the masked `A = decay x
    (Q K^T)^p` and `A V` over the causal half (two products a query head);
    across chunks `phi(Q) S_0` (a query head against its KV head's state);
    and the state's update `sum_s w_s phi(k_s) v_s^T` (a KV head). The
    normaliser's share rides in the `+ 1`. Bytes: the state read and
    written once, q, k, v in and o out."""
    rows = state_rows(head_dim, degree)
    pairs = chunk * (chunk + 1) / 2.0
    ops = (2 * 2 * pairs * head_dim * heads
           + 2.0 * chunk * rows * (head_dim + 1) * heads
           + 2.0 * chunk * rows * (head_dim + 1) * kv_heads)
    byts = (2.0 * kv_heads * rows * (head_dim + 1) * state_bytes_per_el
            + chunk * (2 * heads + 2 * kv_heads) * head_dim * io_bytes_per_el)
    return ops, byts
