"""Share of the window's admitted prompt tokens that came from the prefix
cache: serving_prefix_tokens_reused_total over serving_prompt_tokens_total,
window deltas of the engine's own counters."""


def read(run):
    c = run.counters
    if not c.get("prompt_tokens"):
        return None
    return 100.0 * c["prefix_tokens_reused"] / c["prompt_tokens"]
