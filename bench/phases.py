"""Where a traced run's time goes, by the program's own spans and scopes.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s>

Makes ``bench/run.py``'s traced run of the cell (every line it prints,
its result line included) and reads three more things from the same run:

* ``spans``: the program's span table over the window (count, seconds
  and self seconds per span, from ``PerformanceProfiler.spans``) and the
  fused-path fallbacks by reason;
* ``idle_by_span``: the idle seconds of every gap in the trace, split by
  the innermost host span over each part of it (``engine`` where only the
  window covers it);
* ``scoped_busy``: device seconds by the top-level name scope of each op,
  each op counting its exclusive time, and the share under the paged
  cache's ``kv_gather``/``kv_write`` scopes.

On a program without spans or scopes these read empty, and nothing fails.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import glob
import json
import os
import re
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import tracereduce  # noqa: E402

Event = Tuple[str, int, int]                 # (name, start_ns, duration_ns)
ScopedEvent = Tuple[str, int, int, str]      # ... plus the op's scope path

PROGRAM_SPANS = (
    "cycle", "cycle.schedule", "cycle.prepare", "cycle.dispatch",
    "cycle.wait", "cycle.mirror", "cycle.per_op", "cycle.finish",
    "op.prefill", "op.insert", "op.draft", "op.verify", "op.rollback",
    "op.draft_tree", "op.verify_tree",
    "serve.queue", "serve.admit", "serve.collect", "serve.retire")
# the stat of a device op's event metadata that holds its framework op
# path ("jit(f)/scope/.../primitive"); else any stat that reads like one
SCOPE_STATS = ("tf_op",)
# the name scopes of the fused programs' phases and of the paged cache
PHASE_SCOPE = re.compile(r"^(gap_prefix|decode|draft|verify\.\d+|rollback"
                         r"|commit)$")
KV_SCOPES = ("kv_gather", "kv_write")
_TRANSFORM = re.compile(r"^[A-Za-z_]*jit\(.*\)$")


def _window(spans: Sequence[Event]) -> Tuple[int, int]:
    win = [s for s in spans if s[0] == tracereduce.WINDOW_SPAN]
    if not win:
        raise ValueError("trace holds no window span")
    return min(s[1] for s in win), max(s[1] + s[2] for s in win)


def _gaps(events: Sequence[Event], w0: int, w1: int
          ) -> List[Tuple[int, int]]:
    """Stretches of [w0, w1) in which no event of one device ran."""
    gaps, t = [], w0
    for _, s, d in sorted(events, key=lambda e: e[1]):
        a, b = max(s, w0), min(s + d, w1)
        if b <= a:
            continue
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    return gaps


def idle_by_span(devices: Sequence[Sequence[Event]],
                 spans: Sequence[Event]) -> Dict[str, float]:
    """Idle seconds of the window, averaged over the devices, by the
    innermost host span over each part of each gap; largest first."""
    w0, w1 = _window(spans)
    inner = sorted((s for s in spans if s[0] != tracereduce.WINDOW_SPAN),
                   key=lambda s: s[1])
    starts = [s[1] for s in inner]
    longest = max((s[2] for s in inner), default=0)
    out: Dict[str, float] = collections.defaultdict(float)
    for events in devices:
        for a, b in _gaps(events, w0, w1):
            lo = bisect.bisect_left(starts, a - longest)
            hi = bisect.bisect_left(starts, b)
            over = [s for s in inner[lo:hi] if s[1] + s[2] > a]
            cuts = sorted({a, b} | {x for s in over
                                    for x in (s[1], s[1] + s[2])
                                    if a < x < b})
            for p, q in zip(cuts, cuts[1:]):
                cover = [s for s in over if s[1] <= p and s[1] + s[2] >= q]
                name = min(cover, key=lambda s: s[2])[0] if cover \
                    else "engine"
                out[name] += (q - p) / 1e9
    n = max(len(devices), 1)
    return {k: v / n for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def top_scope(path: str) -> str:
    """The fused-cycle phase scope an op's path runs under (``decode``,
    ``verify.2``, ...); ``unscoped`` for an op under none of them: an
    XLA-inserted copy, or an op of a per-op program."""
    return next((p for p in path.split("/")[1:-1] if PHASE_SCOPE.match(p)),
                "unscoped")


def kv_scope(path: str) -> str:
    """``kv_gather`` or ``kv_write`` for an op under either scope at any
    depth, else ``other``."""
    parts = path.split("/")
    return next((k for k in KV_SCOPES if k in parts), "other")


def scoped_busy(devices: Sequence[Sequence[ScopedEvent]],
                spans: Sequence[Event],
                key: Callable[[str], str] = top_scope) -> Dict[str, float]:
    """Device seconds inside the window, averaged over the devices, by
    ``key(scope path)`` of each op.  An op counts its exclusive time: its
    duration less that of the ops nested in it on the same line (a loop
    and the fusions it runs are counted once)."""
    w0, w1 = _window(spans)
    out: Dict[str, float] = collections.defaultdict(float)
    for events in devices:
        for e, x in zip(events, tracereduce.exclusive_ns(events, w0, w1)):
            if x > 0:
                out[key(e[3])] += x / 1e9
    n = max(len(devices), 1)
    return {k: v / n for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes, i: int = 0, end: Optional[int] = None):
    """(field number, value) of each field of one protobuf message in
    ``buf[i:end]``; a length-delimited value is its ``(start, end)``."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _map_values(buf: bytes, entry: Tuple[int, int]) -> List[Tuple[int, int]]:
    return [v for f, v in _fields(buf, *entry) if f == 2]


def op_paths(xspace: bytes) -> Tuple[Dict[str, Dict[str, str]],
                                     Optional[str]]:
    """{device plane: {op event name: scope path}} from a serialized
    ``XSpace``, and the stat the paths were read from.  ``ProfileData``
    shows an event's own stats, but the device keeps each op's framework
    path (``jit(f)/scope/.../primitive``) in the op's event metadata, so
    this reads the planes' metadata tables (XPlane fields 4 and 5, the
    lines skipped)."""
    out: Dict[str, Dict[str, str]] = {}
    used: Optional[str] = None
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(xspace, *plane):
            if g == 2:
                name = bytes(xspace[v[0]:v[1]]).decode()
                if not name.startswith("/device:"):
                    break
            elif g == 4:
                events.extend(_map_values(xspace, v))
            elif g == 5:
                for sm in _map_values(xspace, v):
                    d = dict(_fields(xspace, *sm))
                    if 2 in d:
                        stat_names[d.get(1, 0)] = bytes(
                            xspace[d[2][0]:d[2][1]]).decode()
        if not name.startswith("/device:"):
            continue
        paths = out.setdefault(name, {})
        for em in events:
            ev_name, stats = "", {}
            for g, v in _fields(xspace, *em):
                if g == 2:
                    ev_name = bytes(xspace[v[0]:v[1]]).decode()
                elif g == 5:
                    st = dict(_fields(xspace, *v))
                    key = stat_names.get(st.get(1, 0))
                    if 5 in st:
                        stats[key] = bytes(
                            xspace[st[5][0]:st[5][1]]).decode()
                    elif 7 in st:
                        stats[key] = stat_names.get(st[7], "")
            key = next((k for k in SCOPE_STATS if "/" in stats.get(k, "")),
                       None) or next(
                (k for k, p in stats.items()
                 if "/" in p and _TRANSFORM.match(p.split("/", 1)[0])), None)
            if key is not None:
                paths.setdefault(ev_name, stats[key])
                used = used or key
    return out, used


def read_scoped(trace_dir: str) -> Tuple[List[List[ScopedEvent]],
                                         Optional[str]]:
    """(device ops with their scope path per chip, the stat the path was
    read from, or None where no op carries one) from the newest
    ``.xplane.pb`` under a trace directory."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    raw = Path(files[-1]).read_bytes()
    paths, used = op_paths(raw)
    devices: List[List[ScopedEvent]] = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not plane.name.startswith("/device:"):
            continue
        where = paths.get(plane.name, {})
        events = [(tracereduce.op_name(e.name), int(e.start_ns),
                   int(e.duration_ns), where.get(e.name, ""))
                  for ln in plane.lines if ln.name == "XLA Ops"
                  for e in ln.events]
        if events:
            devices.append(events)
    return devices, used


def span_delta(before: Dict[str, List[float]],
               after: Dict[str, List[float]]) -> Dict[str, List[float]]:
    """[count, seconds, self seconds] per span between two snapshots of a
    profiler's span table."""
    out = {}
    for k, row in after.items():
        b = before.get(k, [0, 0.0, 0.0])
        d = [row[i] - b[i] for i in range(3)]
        if d[0]:
            out[k] = [int(d[0]), d[1], d[2]]
    return out


def main(argv=None, **run_kw) -> int:
    """``run_kw`` go to ``bench.run.run_cell`` (a test runs a tiny cell on
    the CPU through them)."""
    from bench import run as br
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)

    def serve_window(serving, mix, seed, seconds):
        prof = serving.router.profiler
        spans0 = {k: list(v) for k, v in getattr(prof, "spans", {}).items()}
        counters0 = dict(prof.counters)
        out = serve_window0(serving, mix, seed, seconds)
        spans = span_delta(spans0, getattr(prof, "spans", {}))
        cycles = spans.get("cycle", [0])[0]
        print("spans " + json.dumps(dict(
            cycles=cycles, spans=spans,
            per_cycle_ms={k: 1e3 * v[1] / cycles for k, v in spans.items()}
            if cycles else {},
            counters={k: v - counters0.get(k, 0.0)
                      for k, v in prof.counters.items()
                      if k.startswith(("fallback.", "groups", "host_sync"))})),
            flush=True)
        return out

    def read_xplane(trace_dir, span_names):
        out = read_xplane0(trace_dir, span_names)
        scoped, stat = read_scoped(trace_dir)
        _, spans, _ = out
        busy = scoped_busy(scoped, spans, key=lambda p: "busy")
        kv = scoped_busy(scoped, spans, key=kv_scope)
        kv_s = sum(v for k, v in kv.items() if k in KV_SCOPES)
        print("phases " + json.dumps(dict(
            scope_stat=stat,
            idle_by_span=idle_by_span(out[0], spans),
            scoped_busy=scoped_busy(scoped, spans),
            kv_busy=kv,
            kv_device_share=(100.0 * kv_s / busy["busy"]
                             if stat and busy.get("busy") else None))),
            flush=True)
        return out

    serve_window0, read_xplane0, spans0 = (br.serve_window,
                                           tracereduce.read_xplane, br.SPANS)
    br.serve_window, tracereduce.read_xplane = serve_window, read_xplane
    br.SPANS = tuple(spans0) + PROGRAM_SPANS
    try:
        return br.run_cell(a.workload, a.seed, a.seconds, True, **run_kw)
    finally:
        br.serve_window, tracereduce.read_xplane, br.SPANS = (
            serve_window0, read_xplane0, spans0)

if __name__ == "__main__":
    sys.exit(main())
