"""Share of the device's idle time (the gaps between its operations in the
traced window) that no `serving.*` span overlaps: the caller's loop, here
the benchmark's own generator and sampling."""
from chipbench.harness import host_phases


def read(run):
    got = host_phases.read_phases(run)
    if got is None:
        return None
    idle = host_phases.idle_by_phase(run.trace.events, got[0])
    total = sum(idle.values())
    if total <= 0:
        return None
    return 100.0 * idle.get(host_phases.OUTSIDE, 0.0) / total
