"""One run of one benchmark cell on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is comes from data: ``BENCHMARK.json`` names its
configuration (``bench/configs/<config>.json``: the published sizes and the
architecture of each pool member, the planted agreement, the slot count and
the comparison's limits), its traffic mix (``bench/traffic/<mix>.json``) and
its metrics (one reader each, ``bench/metrics/<metric>.py``).  Each
member's ``arch`` names its module, ``bench/arch/<arch>.py`` (the interface
is in ``bench/arch/__init__.py``).

A run builds the pool's bf16 weights on the device from ``--seed``, serves
the mix through ``ServingEngine`` with the router's defaults, warms every
shape the mix uses, then measures for ``--seconds``.  After the window it
replays every request through the target architecture's float32 reference
(``bench/check.py``).  Earlier lines report the set-up split, the row
capacity, the programs compiled inside the window (there should be none;
the result's ``window_compiles`` counts them) and the comparison; the
numbers compared end standard error, and the last line of standard output
is the JSON result.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import phases  # noqa: E402
from bench import tracereduce  # noqa: E402
from bench import traffic as tg  # noqa: E402
from bench import weights as wt  # noqa: E402
from bench.check import judge  # noqa: E402

_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
SPANS = ("admit", "run_cycle", "retire")


# ---------------------------------------------------------------------------
# the manifest and its data files
# ---------------------------------------------------------------------------
def load_cell(workload: str, root: Path = ROOT):
    """(cell, configuration, traffic mix, end-to-end and per-layer metric
    entries that apply to the cell) from ``BENCHMARK.json``."""
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    cfg = json.loads((root / entry["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" /
                      f"{cell['traffic']}.json").read_text())

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]
    return (cell, cfg, mix,
            [m for m in manifest["end_to_end"] if applies(m)],
            [m for m in manifest["per_layer"] if applies(m)])


def _module(kind: str, name: str, root: Path):
    """``bench/<kind>/<name>.py`` under ``root``, loaded from its file."""
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of a metric, from its own file."""
    return _module("metrics", name, root).read


def arch_module(name: str, root: Path = ROOT):
    """The module of architecture ``name`` (``bench/arch/<name>.py``)."""
    return _module("arch", name, root)


def device_peaks(device_kind: str) -> Dict:
    """The device's row of ``bench/peaks.json``."""
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in peaks:
        raise KeyError(f"no published peaks for {device_kind!r}")
    return peaks[device_kind]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
class Serving:
    """The pool, its engine, and the benchmark's own copy of the weights
    (the reference reads those, never the program's).  Each member's
    weights, program configuration and parameter tree come from its
    architecture's module, ``archs[name]``."""

    def __init__(self, cfg: Dict, seed: int, root: Path = ROOT):
        from repro.core import ModelPool
        from repro.models.model import LanguageModel
        from repro.serving import ServingEngine
        self.cfg = cfg
        self.members = cfg["members"]
        self.target = self.members[-1]["name"]
        self.archs = {m["name"]: arch_module(m["arch"], root)
                      for m in self.members}
        self.weights: Dict[str, Dict] = {}
        self.make_weights(seed)
        self.pool = ModelPool()
        for m in self.members:
            arch = self.archs[m["name"]]
            pc = arch.program_config(m)
            self.pool.register(pc,
                               params=arch.to_program(self.weights[m["name"]]),
                               param_axes=LanguageModel(pc).param_axes())
        self.slots = int(cfg["slots"])
        self.engine = ServingEngine(self.pool, self.target,
                                    batch_size=self.slots,
                                    router_kwargs=dict(cfg["router"]))

    def make_weights(self, seed: int) -> None:
        """(Re)make every member's weights from ``seed``: all old ones are
        freed first, then the new ones are made largest first."""
        for m in self.members:
            self.weights.pop(m["name"], None)
            if getattr(self, "pool", None) is not None:
                self.pool.entry(m["name"]).params = None
        gc.collect()
        for i in reversed(range(len(self.members))):
            m = self.members[i]
            arch = self.archs[m["name"]]
            w = arch.make_weights(m["config"], self.cfg["planting"],
                                  m["planted"], wt.member_key(seed, i))
            jax.block_until_ready(w)
            self.weights[m["name"]] = w
            if getattr(self, "pool", None) is not None:
                self.pool.entry(m["name"]).params = arch.to_program(w)

    @property
    def router(self):
        return self.engine._router

    def row_cap(self, need: int) -> int:
        """The engine's per-row capacity for a run whose longest request
        needs ``need`` positions (``ServingEngine._run_continuous``: the
        speculation margin on top, rounded up to a power of two)."""
        r = self.router
        margin = r.gcap + (r.max_block + r.scheduler.max_chain_len) * 4
        cap = 64
        while cap < need + margin:
            cap *= 2
        return cap

    def class_of(self, token: int) -> str:
        for cls, (start, count) in self.cfg["planting"]["classes"].items():
            if start <= token < start + count:
                return cls
        return "other"


def to_requests(specs) -> List:
    from repro.data.workload import Request
    return [Request(request_id=s.request_id, arrival_s=s.arrival_s,
                    prompt=s.prompt, max_new_tokens=s.max_new_tokens,
                    dataset=s.cls) for s in specs]


# ---------------------------------------------------------------------------
# instrumentation (traced runs only)
# ---------------------------------------------------------------------------
class Recorder:
    """Wraps each serving session's ``admit``/``run_cycle``/``retire`` in a
    host span and keeps their ``CycleReport``s and admission times, and
    profiles the first ``TRACE_SECONDS`` of the window into ``trace_dir``
    (a longer device trace overflows the profiler's event buffer)."""

    TRACE_SECONDS = 20.0

    def __init__(self, serving: Serving, trace_dir: str):
        self.serving = serving
        self.trace_dir = trace_dir
        self.cycles: List = []
        self.admit_s: List[float] = []
        self.class_commits: Dict[str, List[int]] = collections.defaultdict(
            list)
        self._span = None
        self._t0 = 0.0
        self.stop_s = 0.0     # writing the trace out, inside the window

    def __enter__(self):
        router = self.serving.router
        orig = router.start_session

        def start_session(*a, **k):
            sess = orig(*a, **k)
            self._wrap(sess)
            return sess
        router.start_session = start_session
        jax.profiler.start_trace(self.trace_dir)
        self._span = jax.profiler.TraceAnnotation(tracereduce.WINDOW_SPAN)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def _stop_trace(self, force: bool = False) -> None:
        if self._span is None or (
                not force
                and time.perf_counter() - self._t0 < self.TRACE_SECONDS):
            return
        self._span.__exit__(None, None, None)
        self._span = None
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s += time.perf_counter() - t0

    def __exit__(self, *exc):
        self._stop_trace(force=True)
        del self.serving.router.start_session    # back to the class method
        return False

    def _wrap(self, sess):
        admit, run_cycle, retire = sess.admit, sess.run_cycle, sess.retire
        slot_cls: Dict[int, str] = {}
        ann = jax.profiler.TraceAnnotation

        def w_admit(slot, prompt, *a, **k):
            with ann("admit"):
                dt = admit(slot, prompt, *a, **k)
            self.admit_s.append(dt)
            slot_cls[slot] = self.serving.class_of(int(prompt[0]))
            self._stop_trace()
            return dt

        def w_cycle():
            with ann("run_cycle"):
                rep = run_cycle()
            self.cycles.append(rep)
            for s, cls in slot_cls.items():
                if rep.commits[s] > 0:
                    self.class_commits[cls].append(int(rep.commits[s]))
            self._stop_trace()
            return rep

        def w_retire(slot):
            with ann("retire"):
                out = retire(slot)
            slot_cls.pop(slot, None)
            return out
        sess.admit, sess.run_cycle, sess.retire = w_admit, w_cycle, w_retire


class CompileCounter:
    """Counts traces, backend compiles and persistent-cache loads, and
    keeps the name of every program compiled or loaded."""

    def __init__(self):
        self.n = collections.Counter()
        self.seconds = 0.0
        self.names: List[str] = []
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_kw):
        if event == _CACHE_HIT:
            self.n["cache_loads"] += 1

    def _duration(self, event, seconds, **kw):
        if event == _COMPILE:
            self.n["compiles"] += 1
            self.seconds += seconds
            self.names.append(str(kw.get("fun_name", "?")))
        elif event == _TRACE:
            self.n["traces"] += 1

    def snapshot(self):
        return dict(self.n), self.seconds, len(self.names)

    def close(self):
        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._duration)


# ---------------------------------------------------------------------------
# set-up, window, check
# ---------------------------------------------------------------------------
def warm_up(serving: Serving, mix: Dict, seed: int, cap: int) -> None:
    """Compile every shape the cell's traffic uses before the window: the
    first-admission prefill and the admission insert of each prompt length
    in every member (a session per length, its slots pinned to the whole
    chain), the cycle programs of every speculative (chain, window) the
    scheduler can pick, then two batches of the cell's own traffic, each
    followed by clearing the scheduler's timing EMAs (a departure from the
    router's defaults, stated in the configuration's ``assumed``)."""
    classes = serving.cfg["planting"]["classes"]
    chain = tuple(m["name"] for m in serving.members)
    start, count = next(iter(classes.values()))
    rng = np.random.default_rng([int(seed) % 2**63, 3])
    lengths = sorted(set(tg.strata_lengths(mix["prompt"])))
    for lp in lengths:
        sess = serving.router.start_session(serving.slots, cap,
                                            session_id="warm")
        for slot in range(min(2, serving.slots)):
            prompt = rng.integers(start, start + count, lp).astype(np.int64)
            sess.admit(slot, prompt, 16, chain=chain)
        sess.close()
    # every speculative (chain, window) the scheduler can pick, each slot
    # pinned to it, through a profiling cycle and fused ones
    sched = serving.router.scheduler
    for ch in sched.candidate_chains():
        for w in (sched.windows if len(ch) > 1 else ()):
            sess = serving.router.start_session(serving.slots, cap,
                                                session_id="warm")
            for slot in range(serving.slots):
                prompt = rng.integers(start, start + count,
                                      lengths[0]).astype(np.int64)
                sess.admit(slot, prompt, 3 * (w + 2), chain=ch, window=w)
            while sess.active.any():
                sess.run_cycle()
            sess.close()
    for rep in range(2):
        serving.engine.run(to_requests(
            tg.closed_batch(mix, classes, seed, 2**20 + rep, prefix="w")))
        # the EMAs hold what warm-up measured, compiles and cache loads
        # included, and routing from them changes from run to run (tokens/s
        # quartiles 14-16% apart on a v5e): forgetting them starts every
        # window from the same routing state, and the second batch compiles
        # what the router picks from it
        serving.router.profiler.emas.clear()


def offered_cap(serving: Serving, mix: Dict, seed: int) -> int:
    classes = serving.cfg["planting"]["classes"]
    return serving.row_cap(tg.row_need(tg.closed_batch(mix, classes, seed,
                                                       0)))


def serve_window(serving: Serving, mix: Dict, seed: int, seconds: float):
    """Serve the window; returns (requests, host wall seconds)."""
    classes = serving.cfg["planting"]["classes"]
    if mix["mode"] != "closed_batches":
        raise ValueError(f"unknown traffic mode {mix['mode']!r}")
    reqs: List = []
    t0 = time.perf_counter()
    k = 0
    while time.perf_counter() - t0 < seconds:
        batch = to_requests(tg.closed_batch(mix, classes, seed, k))
        serving.engine.run(batch)
        reqs.extend(batch)
        k += 1
    return reqs, time.perf_counter() - t0


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run.  ``spans`` and
    ``counters`` are what the window added to the program's span table
    (``{name: [count, s, self s]}``) and counters; ``scoped_busy`` (traced
    runs) is the window's exclusive device seconds by each op's scope path
    (``jit(<program>)/<scope>/.../<primitive>``, ``""`` where the trace
    names none), averaged over the chips; ``peaks`` the device's row of
    ``bench/peaks.json`` (empty off the TPU)."""
    requests: List
    wall_s: float
    setup_s: float
    committed_tokens: int
    cycles: List
    admit_s: List[float]
    trace: Optional[Dict]
    target_flops_per_token: float
    peak_flops: float
    spans: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    scoped_busy: Dict[str, float] = dataclasses.field(default_factory=dict)
    peaks: Dict = dataclasses.field(default_factory=dict)


def mean_context(reqs) -> float:
    """Mean number of keys a committed token attends to."""
    n = sum(len(r.output_tokens) for r in reqs if r.output_tokens is not None)
    s = sum(len(r.output_tokens) * (len(r.prompt)
                                    + (len(r.output_tokens) - 1) / 2.0)
            for r in reqs if r.output_tokens is not None)
    return s / n if n else 0.0


def reference_module(cfg: Dict, root: Path = ROOT):
    """The float32 reference of the pool's target: its architecture's
    ``REFERENCE``."""
    return arch_module(cfg["members"][-1]["arch"], root).REFERENCE


def use_compile_cache(cache_dir: Optional[Path]) -> None:
    """JAX's persistent cache in the checkout (``cache_dir``), unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept."""
    if cache_dir is None:
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, root: Path = ROOT,
             t_start: float = T_START,
             cache_dir: Optional[Path] = ROOT / ".jax_cache") -> int:
    """One run of cell ``workload``; prints its lines and returns the exit
    code."""
    counter = CompileCounter()
    try:
        return _run_cell(workload, seed, seconds, trace, require_tpu, root,
                         t_start, cache_dir, counter)
    finally:
        counter.close()


def _run_cell(workload, seed, seconds, trace, require_tpu, root, t_start,
              cache_dir, counter) -> int:
    cell, cfg, mix, e2e, per_layer = load_cell(workload, root)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell["chips"]):
        print(f"bench: cell {workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {dev.platform} device(s)",
              file=sys.stderr)
        return 2
    use_compile_cache(cache_dir)
    import repro.serving  # noqa: F401  (the program's import cost is set-up)
    t_import = time.perf_counter()

    serving = Serving(cfg, seed, root)
    t_weights = time.perf_counter()
    cap = offered_cap(serving, mix, seed)
    warm_up(serving, mix, seed, cap)
    t_warm = time.perf_counter()
    setup_compiles, setup_compile_s, n_setup = counter.snapshot()
    setup_s = t_warm - t_start
    print("setup " + json.dumps(dict(
        setup_s=setup_s, import_s=t_import - t_start,
        weights_s=t_weights - t_import, warmup_s=t_warm - t_weights,
        compile_s=setup_compile_s, **setup_compiles,
        slots=serving.slots, row_cap=cap)), flush=True)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    recorder = Recorder(serving, trace_dir) if trace else None
    prof = serving.router.profiler
    spans0 = {k: list(v) for k, v in prof.spans.items()}
    counters0 = dict(prof.counters)
    if trace:
        recorder.__enter__()
    try:
        reqs, wall = serve_window(serving, mix, seed, seconds)
    finally:
        if trace:
            recorder.__exit__(None, None, None)
    spans = phases.span_delta(spans0, prof.spans)
    counters = {k: v - counters0.get(k, 0.0) for k, v in
                prof.counters.items() if v != counters0.get(k, 0.0)}
    if trace:
        wall -= recorder.stop_s
    after, _, _ = counter.snapshot()
    in_window = {k: after.get(k, 0) - setup_compiles.get(k, 0)
                 for k in ("traces", "compiles", "cache_loads")}
    window_programs = sorted(set(counter.names[n_setup:]))
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", -1))
    committed = sum(len(r.output_tokens) for r in reqs
                    if r.output_tokens is not None)
    print("window " + json.dumps(dict(
        seconds=wall, requests=len(reqs), committed_tokens=committed,
        memory_peak_bytes=mem_peak,
        bytes_limit=int(stats.get("bytes_limit", -1)),
        **{f"window_{k}": v for k, v in in_window.items()},
        window_programs=window_programs)), flush=True)

    # the program's state is freed before the reference runs
    serving.engine = None
    serving.pool = None
    for m in serving.members[:-1]:          # the reference reads the target
        serving.weights.pop(m["name"])
    gc.collect()
    t_ref = time.perf_counter()
    target = serving.members[-1]
    target_arch = serving.archs[target["name"]]
    verdict = judge(target_arch.REFERENCE, serving.weights[target["name"]],
                    target["config"], reqs, cfg["limits"])
    print("check " + json.dumps(dict(
        reference_s=time.perf_counter() - t_ref, tokens=verdict["tokens"],
        requests=verdict["requests"], max_gap_by_class=verdict["by_class"])),
        flush=True)

    reduced = None
    scoped_busy: Dict[str, float] = {}
    if trace:
        t_read = time.perf_counter()
        devs, host, layout = tracereduce.read_xplane(trace_dir, SPANS)
        reduced = tracereduce.reduce(devs[:cell["chips"]], host)
        scoped, scope_stat = phases.read_scoped(trace_dir)
        scoped_busy = phases.scoped_busy(scoped[:cell["chips"]], host,
                                         key=lambda path: path)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ev = [e for d in devs[:cell["chips"]] for e in d]
        win = [s for s in host if s[0] == tracereduce.WINDOW_SPAN]
        print("trace " + json.dumps(dict(
            device_planes=layout, device_events=len(ev),
            device_extent_ns=[min(e[1] for e in ev),
                              max(e[1] + e[2] for e in ev)] if ev else None,
            window_ns=[win[0][1], win[0][1] + win[0][2]] if win else None,
            host_spans=len(host), scope_stat=scope_stat,
            scope_paths=len(scoped_busy),
            read_s=time.perf_counter() - t_read)), flush=True)
        print("classes " + json.dumps({
            cls: dict(slot_cycles=len(v), tokens_per_slot_cycle=
                      sum(v) / len(v)) for cls, v in
            recorder.class_commits.items()}), flush=True)

    peaks = device_peaks(dev.device_kind) if dev.platform == "tpu" else {}
    run = Run(requests=reqs, wall_s=wall, setup_s=setup_s,
              committed_tokens=committed,
              cycles=recorder.cycles if trace else [],
              admit_s=recorder.admit_s if trace else [],
              trace=reduced,
              target_flops_per_token=target_arch.flops_per_token(
                  target["config"], mean_context(reqs)),
              peak_flops=float(peaks.get("bf16_flops", "nan")),
              spans=spans, counters=counters, scoped_busy=scoped_busy,
              peaks=peaks)
    metrics = {}
    for m in (per_layer if trace else e2e):
        v = reader(m["name"], root)(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    result = {"correct": bool(verdict["correct"]), "attempted": len(reqs),
              "failed": verdict["compared"]["short_requests"]["value"],
              "metrics": metrics, "device": device,
              "window_compiles": in_window["compiles"]}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = verdict["compared"]
    print(f"window compiles = {in_window['compiles']} (none expected; "
          f"not part of the comparison)", file=sys.stderr)
    for name, c in verdict["compared"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    return run_cell(a.workload, a.seed, a.seconds, bool(a.trace))


if __name__ == "__main__":
    sys.exit(main())
