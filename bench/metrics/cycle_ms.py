"""Mean host wall time of one speculation cycle (all its groups), in
milliseconds."""


def read(run):
    vals = [c.wall_s for c in run.cycles if c.groups]
    return 1e3 * sum(vals) / len(vals) if vals else None
