"""`serving.commit` with what is nested in it (`serving.kv.release`): the
per-slot token loop, the requests it finishes, their pages' release and
publication; milliseconds over the engine steps."""
from chipbench.harness import host_phases


def read(run):
    return host_phases.ms_per_step(run, (host_phases.PREFIX + "commit",))
