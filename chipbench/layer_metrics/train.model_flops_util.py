"""Model FLOP/s utilisation over the WHOLE window: tokens per second per
chip x operations per token (forward + backward, no recompute,
`harness/flops.py`) over the chip's published bf16 peak."""


def read(run):
    c = run.counters
    if run.peaks is None or "flops_per_token" not in c:
        return None
    rate = c["tokens"] / run.window_s / c["chips"]
    return 100.0 * rate * c["flops_per_token"] / run.peaks["bf16_flops"]
