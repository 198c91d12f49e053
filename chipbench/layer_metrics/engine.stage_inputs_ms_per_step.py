"""From the scheduler's decision to the device: `serving.schedule`,
`serving.stage_inputs` and the dispatch spans (`serving.decode`,
`serving.prefill`, ...), self time, milliseconds over the engine steps."""
from chipbench.harness import host_phases


def read(run):
    return host_phases.ms_per_step(run, host_phases.STAGE_SPANS)
