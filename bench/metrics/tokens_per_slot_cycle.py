"""Tokens committed per active slot per speculation cycle, averaged over
the window's cycles (``CycleReport.acc_mean``): what chain routing and
multi-level verification buy per cycle."""


def read(run):
    vals = [c.acc_mean for c in run.cycles if c.groups]
    return sum(vals) / len(vals) if vals else None
