"""Share of the traced window's busy device time spent under the expert
layer's ``moe`` name scope (router, held experts, shared expert), at any
depth: each op counts its exclusive time (``Run.scoped_busy``), so the
share is at most 100%.  Silent where no op carries the scope."""


def read(run):
    busy = sum(run.scoped_busy.values())
    moe = sum(s for path, s in run.scoped_busy.items()
              if "moe" in path.split("/"))
    return 100.0 * moe / busy if moe > 0 else None
