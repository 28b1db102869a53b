"""Share of the traced window's busy device time spent under the paged
cache's ``kv_gather`` and ``kv_write`` name scopes, at any depth: each op
counts its exclusive time (``Run.scoped_busy``), so the share is at most
100%.  Silent where no op carries either scope."""
from bench.phases import KV_SCOPES, kv_scope


def read(run):
    busy = sum(run.scoped_busy.values())
    kv = sum(s for path, s in run.scoped_busy.items()
             if kv_scope(path) in KV_SCOPES)
    return 100.0 * kv / busy if kv > 0 else None
