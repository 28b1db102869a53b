"""Share of the window's host wall time spent admitting requests: the sum of
the traced window's ``RouterSession.admit`` times (``Run.admit_s``) over its
wall time.  Silent where the run kept no admission times."""


def read(run):
    if not run.admit_s or not run.wall_s:
        return None
    return 100.0 * sum(run.admit_s) / run.wall_s
