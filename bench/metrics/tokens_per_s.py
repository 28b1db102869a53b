"""Committed output tokens of every request of the window over the host
wall time of the window, the drain of each batch included."""


def read(run):
    return run.committed_tokens / run.wall_s if run.wall_s > 0 else None
