"""Share of the window's cycle groups that ran the per-op path instead of
one fused device program (``CycleReport.per_op_groups`` over the groups):
every ``profile_every``-th cycle, a chain with no timing yet, a catch-up gap
or a full cache."""


def read(run):
    cycles = [c for c in run.cycles
              if c.groups and getattr(c, "per_op_groups", None) is not None]
    groups = sum(len(c.groups) for c in cycles)
    if not groups:
        return None
    return 100.0 * sum(c.per_op_groups for c in cycles) / groups
