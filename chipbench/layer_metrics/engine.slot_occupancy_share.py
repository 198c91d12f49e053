"""Mean share of the engine's slots that held a request, sampled after
every engine step of the window."""


def read(run):
    occ = run.samples.get("occupancy")
    return 100.0 * sum(occ) / len(occ) if occ else None
