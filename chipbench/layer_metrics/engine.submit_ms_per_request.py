"""Median duration of `serving.submit`: what one `Engine.submit` holds the
engine's thread for (admission of the newcomer included, where a slot is
free)."""
from chipbench.harness import host_phases


def read(run):
    return host_phases.span_median_ms(run, host_phases.PREFIX + "submit")
