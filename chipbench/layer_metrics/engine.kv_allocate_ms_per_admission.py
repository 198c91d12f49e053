"""Median duration of `serving.kv.allocate`: the prefix match over the
prompt's pages, eviction where the pool is full, the pages' reservation."""
from chipbench.harness import host_phases


def read(run):
    return host_phases.span_median_ms(run, host_phases.PREFIX + "kv.allocate")
