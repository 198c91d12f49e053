"""Share of the KV pool's pages held when the window closes, by live slots
or by the prefix cache (engine's allocator, read by the harness): how much
of the reserved pool the traffic has filled."""


def read(run):
    pool = run.counters.get("num_pages")
    if not pool:
        return None
    return 100.0 * run.counters["pages_held_at_close"] / pool
