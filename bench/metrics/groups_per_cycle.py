"""Sub-cycle groups per speculation cycle: how many distinct (chain,
window) programs per-slot routing runs each cycle."""


def read(run):
    vals = [len(c.groups) for c in run.cycles if c.groups]
    return sum(vals) / len(vals) if vals else None
