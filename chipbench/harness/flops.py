"""Operations and bytes that the algorithm needs, from shapes alone.

The yardstick for MFU and roofline shares: kept with the benchmark so that
no PR that claims a gain can move it. Recomputed operations (remat) never
count. A multiply-add counts as two operations.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> tuple[int, int]:
    """(parameters of one layer's matmuls, parameters of the output head)."""
    h = cfg["hidden_size"]
    hd = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    layer = h * h + 2 * h * kv + h * h + 3 * h * cfg["intermediate_size"]
    return layer, h * cfg["vocab_size"]


def forward_flops_per_token(cfg: dict, context: float) -> float:
    """Forward operations for one token that attends to `context` keys
    (its own position included): every weight matrix once (the embedding
    lookup is a gather and costs none; the head is a matmul), plus QK^T
    and PV over the context."""
    layer, head = matmul_params(cfg)
    L = cfg["num_hidden_layers"]
    attn = 2 * 2 * cfg["hidden_size"] * context  # QK^T and PV, all heads
    return 2.0 * (L * layer + head) + L * attn


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (2x forward) for one token of a causal sequence
    of `seq_len`: a token attends on average to (seq_len + 1) / 2 keys."""
    return 3.0 * forward_flops_per_token(cfg, (seq_len + 1) / 2.0)


def flash_attention_cost(batch: int, seq_len: int, heads: int, kv_heads: int,
                         head_dim: int, bytes_per_el: int = 2,
                         backward: bool = True) -> tuple[float, float]:
    """(operations, bytes) of causal self-attention over [batch, seq_len]
    as a fused kernel needs them. Forward: QK^T and PV over the causal
    half, 2 matmuls. Backward: 5 matmuls of the same size (recomputed
    scores, dV, dP, dQ, dK). Bytes: q, k, v, o read or written once each
    way (k and v at their kv_heads width), and for the backward q, k, v, o,
    do read and dq, dk, dv written."""
    pairs = batch * heads * seq_len * (seq_len + 1) / 2.0
    fwd = 2 * 2 * pairs * head_dim
    ops = fwd * (1 + 2.5) if backward else fwd
    q_el = batch * seq_len * heads * head_dim
    kv_el = batch * seq_len * kv_heads * head_dim
    fwd_bytes = (2 * q_el + 2 * kv_el) * bytes_per_el
    bwd_bytes = (4 * q_el + 4 * kv_el) * bytes_per_el
    return ops, fwd_bytes + (bwd_bytes if backward else 0)


def paged_attention_cost(lengths, heads: int, kv_heads: int, head_dim: int,
                         kv_bytes_per_el: int = 2,
                         io_bytes_per_el: int = 2) -> tuple[float, float]:
    """(operations, bytes) of ONE paged-decode attention call of ONE layer:
    each slot's one query row attends to its `length` cached tokens plus
    its own new token. Bytes: the live K and V rows of every slot (not the
    page table's capacity), the new K/V row, q in and out written."""
    ops = 0.0
    byts = 0.0
    for n in lengths:
        ctx = n + 1
        ops += 2 * 2 * heads * head_dim * ctx
        byts += 2 * ctx * kv_heads * head_dim * kv_bytes_per_el
        byts += 2 * heads * head_dim * io_bytes_per_el
    return ops, byts


def roofline_seconds(ops: float, byts: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / peaks["bf16_flops"]
    t_bytes = byts / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
