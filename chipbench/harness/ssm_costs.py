"""Operations and bytes that a state-space layer's selective scan needs,
from shapes alone: the yardstick of the roofline share of the decode step's
scan. Beside `flops.py`, under the same rules: a multiply-add counts as two
operations, nothing computed twice counts twice, and what is counted is the
least the algorithm needs, not what a kernel happens to move.

A Mamba-1 layer keeps, a sequence, a state of `d_inner x d_state` numbers
and the last `d_conv - 1` rows of `d_inner` of its convolution's input.
"""

from __future__ import annotations


def state_elements(cfg: dict) -> tuple[int, int]:
    """(numbers of a layer's state a sequence, numbers of its window)."""
    d = cfg["mamba_expand"] * cfg["hidden_size"]
    return d * cfg["mamba_d_state"], d * (cfg["mamba_d_conv"] - 1)


def mamba_layers(cfg: dict) -> int:
    """Layers that scan: all but the attention layers (layer i attends iff
    `i % attn_layer_period == attn_layer_offset`)."""
    return sum(1 for i in range(cfg["num_hidden_layers"])
               if i % cfg["attn_layer_period"] != cfg["attn_layer_offset"])


def decode_scan_cost(live_lanes: int, cfg: dict, state_bytes_per_el: int = 4,
                     window_in_kernel: bool = False) -> tuple[float, float]:
    """(operations, bytes) of ONE decode step's scan in ONE layer: every
    live lane's state read AND written once (`exp(dt A) S + (dt x) B`, then
    `S C`: an exponential, a multiply for `dt A`, a multiply-add for the
    update and a multiply-add for the answer an element, 6 counted), `dt`,
    `dt x` in and `y` out a channel in float32, `B` and `C` in.
    `window_in_kernel`: the scan's kernel also moves the convolution window
    (read and written once a lane); where the window's three rows are a
    slice update outside the kernel, as here, the kernel's bytes are the
    state's."""
    state, window = state_elements(cfg)
    d = cfg["mamba_expand"] * cfg["hidden_size"]
    ops = 6.0 * state
    byts = (2.0 * state * state_bytes_per_el + 3 * d * 4
            + 2 * cfg["mamba_d_state"] * 4)
    if window_in_kernel:
        byts += 2.0 * window * state_bytes_per_el
    return live_lanes * ops, live_lanes * byts
