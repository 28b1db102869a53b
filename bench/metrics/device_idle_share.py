"""Share of the traced window in which no operation ran on the device
(1 - busy / window, from the profiler trace)."""


def read(run):
    if not run.trace or run.trace["busy_s"] is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
