"""Share of the traced window that the engine's thread spends inside
`serving.host_read`: the host waits for the chip, not the chip for the
host. With the device's idle share it should come to about the whole."""
from chipbench.harness import host_phases


def read(run):
    got = host_phases.read_phases(run)
    if got is None or not run.trace.window_s:
        return None
    _, groups, _ = got
    return 100.0 * groups.get(host_phases.WAIT, 0.0) / 1e9 / run.trace.window_s
