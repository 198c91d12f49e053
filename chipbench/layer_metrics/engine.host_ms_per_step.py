"""Host milliseconds of one engine step: self time of every `serving.*`
span of the traced window except `serving.host_read` (where the host waits
for the chip), over the engine steps (dispatches of decode, prefill and
verify). Submits are in it: they run on the engine's thread between steps.
An upper bound: the profiler's own host cost is inside the spans."""
from chipbench.harness import host_phases


def read(run):
    return host_phases.ms_per_step(run)
