"""PerformanceProfiler (paper §4.6): low-overhead wall-time + counter
metrics, EMA-smoothed (paper §4.2 input metrics), feeding the
ModelChainScheduler's adaptive loop, plus the host spans of the serving
path (``span``/``wait``), which land in a ``jax.profiler`` trace on the
device ops' clock and in an in-memory table.
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

_now = time.perf_counter


class EMA:
    """T_new = a * measured + (1 - a) * T_old (paper §4.2)."""

    def __init__(self, alpha: float = 0.3):
        self.alpha = alpha
        self.value: Optional[float] = None
        self.count = 0

    def update(self, x: float) -> float:
        self.value = x if self.value is None else (
            self.alpha * x + (1 - self.alpha) * self.value)
        self.count += 1
        return self.value

    def get(self, default: float = 0.0) -> float:
        return default if self.value is None else self.value


class _Span:
    """One open host span: a ``TraceAnnotation`` plus its row of the
    profiler's span table (count, seconds, self seconds)."""

    __slots__ = ("prof", "name", "ann", "t0")

    def __init__(self, prof: "PerformanceProfiler", name: str, args: dict):
        self.prof = prof
        self.name = name
        self.ann = TraceAnnotation(name, **args)

    def __enter__(self):
        self.prof._open.append(0.0)
        self.ann.__enter__()
        self.t0 = _now()
        return self

    def __exit__(self, etype, value, tb):
        dt = _now() - self.t0
        self.ann.__exit__(etype, value, tb)
        prof = self.prof
        stack = prof._open
        child = stack.pop()
        if stack:
            stack[-1] += dt
        row = prof.spans.get(self.name)
        if row is None:
            prof.spans[self.name] = [1, dt, dt - child]
        else:
            row[0] += 1
            row[1] += dt
            row[2] += dt - child
        return False


class PerformanceProfiler:
    """Gathers (op, model) -> EMA wall time; plus counters and spans.

    Keys used by the scheduler:
      ("decode1", m)        — per-token single-step decode time T_i
      ("decode_level", m, branching) — per-level tree-draft forward time
                              for one tree shape (a level decodes several
                              sibling nodes at once, so it is NOT
                              comparable to decode1, and distinct shapes
                              must not share an EMA)
      ("verify", m, T)      — verify-pass wall time for block length T
      ("prefill", m)        — prefill time (chain-switch catch-up cost)

    Diagnostics-only keys:
      ("verify1", m)        — amortized per-token verify time (dt / (T+1)),
                              the verify analogue of decode1
      ("fused_cycle", c)    — whole fused-cycle wall time per chain group

    Load-signal key (SLO-aware scheduling + admission shed policy):
      ("cycle_wall", "session") — wall time of one whole RouterSession
                              cycle across all sub-cycle groups (query it
                              via ``cycle_time()``); deliberately NOT in
                              the scheduler's Eq. 7 inputs snapshot — the
                              LoadSignal carries it instead

    The ``host_sync`` counter tallies host-synchronizing waits on the
    device, each inside a ``cycle.wait`` span (``wait()``): one per per-op
    processor call on the per-op path, ONE per cycle group on the fused
    path — ``tests/test_fused_cycle.py::test_fused_fewer_host_syncs``
    pins the gap.

    Spans (``span(name, **args)``) have fixed names; what varies (model,
    chain, request id) goes in ``args``, which reach the trace as the
    event's stats.  ``spans[name]`` is ``[count, seconds, self seconds]``,
    self seconds being the span's time less that of the spans opened
    inside it.  With no ``jax.profiler`` session active a span costs a
    few microseconds of host time, so spans are always on.
    """

    def __init__(self, alpha: float = 0.3):
        self.alpha = alpha
        self.emas: Dict[tuple, EMA] = collections.defaultdict(
            lambda: EMA(self.alpha))
        self.counters: Dict[str, float] = collections.defaultdict(float)
        self.spans: Dict[str, List[float]] = {}
        self._open: List[float] = []     # children seconds per open span

    def span(self, name: str, **args) -> _Span:
        """Context manager: a host span ``name`` in the trace and in
        ``spans``."""
        return _Span(self, name, args)

    def wait(self) -> _Span:
        """Context manager around a blocking wait on the device: a
        ``cycle.wait`` span that also counts one ``host_sync``."""
        self.counters["host_sync"] += 1
        return _Span(self, "cycle.wait", {})

    @contextlib.contextmanager
    def timed(self, op: str, model: str, tokens: int = 1, **meta):
        """An ``op.<op>`` span whose wall time also feeds the (op, model)
        EMA."""
        with self.span(f"op.{op}", model=model):
            t0 = time.perf_counter()
            yield
            dt = time.perf_counter() - t0
        self.record(op, model, dt, tokens, **meta)

    def record(self, op: str, model: str, wall_s: float, tokens: int = 1,
               **meta):
        key = (op, model) + ((meta["block"],) if "block" in meta else ())
        self.emas[key].update(wall_s)
        self.counters[f"{op}.{model}.calls"] += 1
        self.counters[f"{op}.{model}.tokens"] += tokens

    def count(self, name: str, inc: float = 1.0):
        self.counters[name] += inc

    # ---- queries used by the scheduler --------------------------------
    def decode_time(self, model: str, default: float) -> float:
        return self.emas[("decode1", model)].get(default)

    def level_time(self, model: str, branching: tuple,
                   default: float) -> float:
        """Tree-draft per-level forward time for one tree shape (falls
        back to ``default`` — typically the linear decode time — until
        that shape has run a cycle)."""
        return self.emas[("decode_level", model, branching)].get(default)

    def verify_time(self, model: str, block: int,
                    default: float) -> float:
        e = self.emas[("verify", model, block)]
        if e.count > 0:
            return e.get(default)
        # fall back to nearest measured block length
        cands = [(k[2], v) for k, v in self.emas.items()
                 if len(k) == 3 and k[0] == "verify" and k[1] == model
                 and v.count > 0]
        if cands:
            blk, v = min(cands, key=lambda kv: abs(kv[0] - block))
            return v.get(default) * (block / max(blk, 1)) ** 0.5
        return default

    def prefill_time(self, model: str, default: float) -> float:
        return self.emas[("prefill", model)].get(default)

    def cycle_time(self, default: float = 0.0) -> float:
        """EMA wall time of one whole speculative cycle (all sub-cycle
        groups), recorded by ``RouterSession.run_cycle`` under
        ``("cycle_wall", "session")`` — the load signal's estimate of how
        long a queued request waits per cycle boundary (SLO-aware
        scheduling and the admission shed policy both read it)."""
        return self.emas[("cycle_wall", "session")].get(default)
