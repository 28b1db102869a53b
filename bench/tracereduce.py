"""Reduction of a profiler trace to device busy time and a breakdown.

The benchmark's traced run wraps its window in a host span named
``window`` and the serving calls in spans named after them (``admit``,
``run_cycle``, ``retire``).  From the trace it reads:

* busy: the union of the device's operation intervals (the ``XLA Ops``
  line of each ``/device:`` plane) inside the window, averaged over the
  chips in use;
* ``device_ops``: the operations that took most device time, summed by
  name, each op counting its exclusive time (``exclusive_ns``: a loop
  does not count the ops that run inside it);
* ``idle_gaps``: the longest stretches with no device operation, each
  named by the innermost benchmark span that covers its middle (``engine``
  where only the window covers it).
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "window"
TOP = 10

Event = Tuple[str, int, int]      # (name, start_ns, duration_ns)


def op_name(hlo: str) -> str:
    """Short name of a device op from the trace's HLO text: the op and, for
    an array result, its type (``copy.193 bf16[24,2048,16,64]``)."""
    name, _, rest = hlo.partition(" = ")
    name = name.strip().lstrip("%")
    kind = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{name} {kind}" if kind[:1].isalpha() and "[" in kind else name


def read_xplane(trace_dir: str, span_names: Sequence[str]):
    """(device events per chip, host spans, device plane -> line names)
    from the newest ``.xplane.pb`` under a ``jax.profiler`` trace
    directory."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices: List[List[Event]] = []
    spans: List[Event] = []
    wanted = set(span_names) | {WINDOW_SPAN}
    layout: Dict[str, List[str]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            layout[plane.name] = [ln.name for ln in plane.lines]
            lines = [ln for ln in plane.lines if ln.name == "XLA Ops"]
            if not lines:
                continue
            devices.append([(op_name(e.name), int(e.start_ns),
                             int(e.duration_ns))
                            for ln in lines for e in ln.events])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend((e.name, int(e.start_ns), int(e.duration_ns))
                             for e in ln.events if e.name in wanted)
    return devices, spans, layout


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def exclusive_ns(events: Sequence[Tuple], w0: int, w1: int) -> List[int]:
    """Nanoseconds of each event (``(name, start_ns, duration_ns, ...)``
    of one device line) inside [w0, w1) that no event nested in it covers,
    in the order given: a loop and the fusions it runs are counted once.
    An event that starts inside another and ends after it gives up only
    the part they share."""
    def inside(a: int, b: int) -> int:
        return max(0, min(b, w1) - max(a, w0))
    excl = [inside(e[1], e[1] + e[2]) for e in events]
    stack: List[int] = []
    for i in sorted(range(len(events)),
                    key=lambda i: (events[i][1], -events[i][2])):
        s, d = events[i][1], events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = events[stack[-1]]
            excl[stack[-1]] -= inside(s, min(s + d, p[1] + p[2]))
        stack.append(i)
    return excl


def reduce(devices: Sequence[Sequence[Event]], spans: Sequence[Event]
           ) -> Dict:
    """busy_s, window_s, device_ops and idle_gaps of one traced window."""
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError("trace holds no window span")
    w0 = min(s[1] for s in win)
    w1 = max(s[1] + s[2] for s in win)
    inner = sorted((s for s in spans if s[0] != WINDOW_SPAN),
                   key=lambda s: s[1])
    busy_total = 0
    op_time: Dict[str, int] = {}
    gaps: List[Tuple[int, int]] = []
    for events in devices:
        iv = []
        for (name, s, d), own in zip(events, exclusive_ns(events, w0, w1)):
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            iv.append((a, b))
            if own > 0:
                op_time[name] = op_time.get(name, 0) + own
        merged = _merge(iv)
        busy_total += sum(b - a for a, b in merged)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2) if edges[i + 1] > edges[i])
    n = max(len(devices), 1)

    def name_of(mid: int) -> str:
        best = None
        for nm, s, d in inner:
            if s > mid:
                break
            if s + d >= mid and (best is None or d < best[1]):
                best = (nm, d)
        return best[0] if best else "engine"

    gaps.sort(key=lambda g: g[0] - g[1])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return dict(
        busy_s=busy_total / n / 1e9 if devices else None,
        window_s=(w1 - w0) / 1e9,
        device_ops=[[k, v / 1e9] for k, v in ops],
        idle_gaps=[[name_of((a + b) // 2), (b - a) / 1e9]
                   for a, b in gaps[:TOP]],
    )
