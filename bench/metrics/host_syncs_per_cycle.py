"""Host-synchronizing dispatches per speculation cycle, from the
program's ``host_sync`` counter as each ``CycleReport`` carries it."""


def read(run):
    vals = [c.host_syncs for c in run.cycles if c.groups]
    return sum(vals) / len(vals) if vals else None
