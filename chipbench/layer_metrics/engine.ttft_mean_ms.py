"""Mean time to first token over the window's measured requests: the
steadier companion of the p90. In a closed loop first-token times sit in
modes (one suffix chunk; a chunk behind a decode step; behind two), and
a tail percentile jumps between modes where a mean moves smoothly."""


def read(run):
    ttft = run.samples.get("ttft_s")
    return 1e3 * sum(ttft) / len(ttft) if ttft else None
