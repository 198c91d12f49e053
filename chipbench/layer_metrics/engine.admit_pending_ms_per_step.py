"""`serving.admit_pending` wherever it opens (under `Engine.step` or under
`Engine.submit`) with what is nested in it (`serving.kv.allocate`, the
admit program's dispatch): milliseconds over the engine steps."""
from chipbench.harness import host_phases


def read(run):
    return host_phases.ms_per_step(
        run, (host_phases.PREFIX + "admit_pending",))
