"""Share of the traced window's busy device time spent under the Gated
DeltaNet mixer's ``gdn`` name scope (its ``gdn.conv`` and ``gdn.delta``
inside it), at any depth: each op counts its exclusive time
(``Run.scoped_busy``), so the share is at most 100%.  Silent where no op
carries the scope."""


def read(run):
    busy = sum(run.scoped_busy.values())
    gdn = sum(s for path, s in run.scoped_busy.items()
              if "gdn" in path.split("/"))
    return 100.0 * gdn / busy if gdn > 0 else None
