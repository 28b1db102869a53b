"""Share of the window's host wall time that the engine's clock does not
bill: everything outside ``admit`` and ``run_cycle`` (request bookkeeping,
retirement, sorting, metrics).  Moves ``tokens_per_s``."""


def read(run):
    if not run.cycles:
        return None
    billed = sum(run.admit_s) + sum(c.wall_s for c in run.cycles)
    return 100.0 * (run.wall_s - billed) / run.wall_s
