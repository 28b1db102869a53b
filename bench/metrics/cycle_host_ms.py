"""Mean host time of one speculation cycle outside its waits on the device,
in milliseconds: ``CycleReport.wall_s`` less ``wait_s``, the seconds of the
cycle's ``cycle.wait`` spans.  The device idles through most of it."""


def read(run):
    vals = [c.wall_s - c.wait_s for c in run.cycles
            if c.groups and getattr(c, "wait_s", None) is not None]
    return 1e3 * sum(vals) / len(vals) if vals else None
