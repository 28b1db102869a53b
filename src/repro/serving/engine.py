"""Serving engine: schedules Poisson-arriving requests onto the SpecRouter
ChainRouter and collects the paper's §5 metrics (goodput, request
throughput, TTFT, TPOT, EAF, SLO attainment).

Batching model: **slot-level continuous batching** — a fixed pool of
``batch_size`` slots, per-slot request lifecycle

    QUEUED -> PREFILL -> DECODING -> DONE

New requests are admitted into freed slots *between* speculation cycles
(RouterSession.admit catch-up-prefills the new row in a forward over that
row alone; live rows are untouched) and finished rows retire without
stalling the others, so a long request never blocks the arrivals queued
behind it.  This is the
iteration-level scheduling that SLO-aware serving systems (SpecServe,
StreamServe) identify as the main goodput/p95-TTFT lever under load.
Routing is per-slot with lazy chain membership (see core/chain_router.py):
admission materializes a request only in its assigned chain's models —
O(chain) prefill work and KV footprint, not O(pool) — and each cycle runs
one masked sub-cycle per distinct (chain, window, tree) group
(``test_admission_touches_only_chain_members`` pins the admission
counters).

Speculation cycles are DEVICE-RESIDENT by default
(``router_kwargs["fused"]``): each sub-cycle group is one jitted program
and one host transfer per cycle, with periodic unfused profiling cycles
(``router_kwargs["profile_every"]``, default 16) refreshing the
scheduler's per-op timings; ``fused=False`` runs the per-op
host-orchestrated loop (``tests/test_fused_cycle.py`` pins the host-sync
count of both).

SLO-aware serving: every request may carry a TTFT/TPOT
SLO (``data/workload.py``; engine-level ``ttft_slo_s``/``tpot_slo_s``
fill unset ones).  When any SLO is configured the scheduler's objective
switches from raw T_eff to predicted SLO attainment — the engine
publishes a ``LoadSignal`` (run-queue depth, slot occupancy, profiler
cycle-latency EMA) before every cycle, and under pressure the chain
search shrinks speculation windows / flattens trees / drops slots to
target-only so queued requests' first tokens are not starved by deep
speculation.  Admission becomes earliest-TTFT-deadline-first (exact FIFO
for no-SLO populations), and ``shed_policy="ttft"`` drops queued
requests whose deadline is already unmeetable.  With no SLOs configured
everything degenerates to the latency-only scheduler bit-exactly
(``tests/test_slo_scheduling.py`` pins this).

The benchmark drives this engine (``python3 bench/run.py --workload
<cell>``); its numbers are in ``PERF_LEDGER.jsonl``.

Timing semantics: arrivals follow the workload trace on a
simulated clock; service time is the REAL wall time of the host models.
Queueing delay is fully billed to TTFT — a request's first-token clock
starts at ``arrival_s``, and every admission prefill / speculation cycle
that runs before its first commit advances the clock it waits on.  A
retired slot's later cycles bill nothing to it (``finish_s`` is fixed at
retirement).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..core import ChainRouter, LoadSignal, ModelPool, Placement
from ..data.workload import Request


@dataclasses.dataclass
class ServingMetrics:
    goodput_tps: float
    request_throughput_rps: float
    avg_ttft_s: float
    p95_ttft_s: float
    avg_tpot_s: float
    avg_latency_s: float
    p95_latency_s: float
    slo_attainment: float
    total_tokens: int
    num_requests: int
    makespan_s: float
    avg_acceptance_len: float
    avg_queue_s: float = 0.0        # arrival -> slot admission
    # per-request SLO goodput (SpecServe's metric): a request counts iff
    # it finished AND met every SLO it carries (Request.slo_met) — shed
    # or late requests are misses.  Populations with no SLOs configured
    # reduce to plain request throughput / 100% attainment.
    slo_goodput_rps: float = float("nan")   # SLO-met requests per second
    request_slo_attainment: float = float("nan")  # met / ALL offered
    num_shed: int = 0               # dropped by the admission shed policy
    # mean host syncs of the cycles that ran every group as one fused
    # device program (the one-transfer contract: 1 per group); NaN when
    # no cycle was fused (fused=False)
    fused_cycle_host_syncs: float = float("nan")

    def as_dict(self) -> Dict[str, float]:
        return dataclasses.asdict(self)


class ServingEngine:
    def __init__(self, pool: ModelPool, target: str,
                 batch_size: int = 4,
                 slo_latency_s: float = 30.0,
                 router_kwargs: Optional[dict] = None,
                 ttft_slo_s: Optional[float] = None,
                 tpot_slo_s: Optional[float] = None,
                 slo_aware: Optional[bool] = None,
                 shed_policy: str = "none",
                 mesh: Optional[object] = None):
        self.pool = pool
        self.target = target
        # --- mesh placement (``--mesh dxm``) ----------------------------
        # ``mesh`` is a "dxm" spec string ("2x4"), a jax Mesh, or a
        # prebuilt Placement.  The pool's members are placed BEFORE the
        # router exists (params/KV device_put under NamedSharding trees):
        # target tensor-parallel over the "model" axis, drafts replicated
        # (Placement.auto_assign) — pass a Placement with explicit
        # ``assign`` calls to override kinds.  None = trivial placement,
        # byte-identical to the unmeshed engine.
        if mesh is not None:
            placement = Placement.from_spec(mesh)
            if not placement.kinds:
                placement.auto_assign(pool.capability(), target)
            if pool.placement.is_trivial:
                pool.set_placement(placement)
            elif pool.placement.describe() != placement.describe():
                # a pool already serving on one mesh cannot be re-placed
                # under another (members hold device-put params); same
                # spec = reuse (several engines over one placed pool)
                raise ValueError(
                    f"pool is already placed on {pool.placement.describe()}"
                    f", cannot re-place on {placement.describe()}")
        self.batch_size = batch_size       # slot count
        self.slo = slo_latency_s
        # --- SLO-aware serving ------------------------------------------
        # ``ttft_slo_s``/``tpot_slo_s`` fill in for requests that carry no
        # SLO of their own (per-request SLOs always win).  ``slo_aware``
        # switches the scheduler's objective to goodput (None = auto:
        # active iff any request carries an SLO); ``shed_policy="ttft"``
        # drops queued requests whose TTFT deadline is already unmeetable
        # instead of burning slot capacity on guaranteed misses.
        self.ttft_slo_s = ttft_slo_s
        self.tpot_slo_s = tpot_slo_s
        self.slo_aware = slo_aware
        if shed_policy not in ("none", "ttft"):
            raise ValueError(f"unknown shed_policy {shed_policy!r} "
                             "(expected 'none' or 'ttft')")
        self.shed_policy = shed_policy
        self.router_kwargs = dict(router_kwargs or {})
        # one router per engine: jit caches and scheduler state persist
        # across batches (recompiling per batch would bill compilation to
        # every request's latency)
        self._router = ChainRouter(self.pool, self.target,
                                   **self.router_kwargs)
        # host syncs of each all-fused cycle of the current run
        self._fused_syncs: List[int] = []

    def run(self, requests: Sequence[Request]) -> ServingMetrics:
        reqs = sorted(requests, key=lambda r: r.arrival_s)
        # engine-level SLO defaults fill requests that carry none
        if self.ttft_slo_s is not None or self.tpot_slo_s is not None:
            for r in reqs:
                if r.ttft_slo_s is None:
                    r.ttft_slo_s = self.ttft_slo_s
                if r.tpot_slo_s is None:
                    r.tpot_slo_s = self.tpot_slo_s
        has_slo = any(r.ttft_slo_s is not None or r.tpot_slo_s is not None
                      for r in reqs)
        # goodput objective: auto-activates when any request carries an
        # SLO; ``slo_aware=False`` forces the latency-only argmin even
        # then
        self._router.scheduler.slo_aware = (
            self.slo_aware if self.slo_aware is not None else has_slo)
        self._fused_syncs = []
        acc_lens = self._run_continuous(reqs)
        return self._metrics(reqs, acc_lens)

    # ------------------------------------------------------------------
    # slot-level admission / retirement
    # ------------------------------------------------------------------
    def _run_continuous(self, reqs: List[Request]) -> List[float]:
        B = self.batch_size
        router = self._router
        lmax = max(len(r.prompt) + 2 * r.max_new_tokens + 2 for r in reqs)
        # max_block covers the widest per-cycle append: a linear window or
        # a whole token tree (tree mode appends all N nodes per cycle)
        margin = router.gcap + \
            (router.max_block + router.scheduler.max_chain_len) * 4
        # per-row sizing is only safe when EVERY pool member actually runs
        # the paged state: SSM/hybrid archs silently keep the contiguous
        # shared-pointer layout (ModelConfig.supports_paged), which still
        # burns cross-slot capacity under churn and needs the old headroom
        all_paged = router.paged and all(
            self.pool.cfg(m).supports_paged for m in self.pool.names())
        if all_paged:
            # block accounting: capacity is PER ROW — a slot only needs the
            # longest single request's own footprint (plus per-cycle
            # speculation margin); churn costs nothing because retirement
            # returns the row's blocks to the pool.  Tree shapes leave
            # masked dead-branch holes INSIDE a row (only trailing slots
            # are reclaimed; paged rows have no compaction path), so a
            # tree-configured router gets the hole-inclusive worst case:
            # a cycle commits >= 1 token but can strand up to the whole
            # N-node block, i.e. footprint <= prompt + budget·(N + gap).
            trees = router.tree_shapes + (
                (router.fixed_tree,) if router.fixed_tree is not None else ())
            if trees:
                n_max = max(t.num_nodes for t in trees)
                lmax = max(lmax,
                           max(len(r.prompt) + r.max_new_tokens * (n_max + 2)
                               for r in reqs))
            max_len = lmax + margin
        else:
            # contiguous shared-pointer state: double for cross-slot
            # fragmentation headroom (the router force-defrags and, as a
            # last resort, rebuilds states under capacity pressure)
            max_len = 2 * lmax + margin
        # pow-2 capacity buckets: session state shapes (and thus every
        # jitted program) are shared across workloads of similar size
        # instead of recompiling per run
        cap = 64
        while cap < max_len:
            cap *= 2
        sess = router.start_session(B, cap, session_id="serve")
        prof = router.profiler

        slot_req: List[Optional[Request]] = [None] * B
        clock = 0.0
        i = 0
        queue: List[Request] = []   # arrived, waiting for a free slot
        acc_lens: List[float] = []
        # each cycle commits >= 1 token per active slot, so total cycles is
        # bounded by the total token budget; the cap is a corruption guard
        cycle_cap = sum(r.max_new_tokens for r in reqs) * 4 + 16 * len(reqs)
        cycles = 0
        while (i < len(reqs) or queue
               or any(r is not None for r in slot_req)):
            with prof.span("serve.queue"):
                clock, i, queue = self._refill_and_admit(
                    sess, reqs, slot_req, queue, clock, i)
            rep = sess.run_cycle()
            clock += rep.wall_s
            cycles += 1
            if rep.fused:
                self._fused_syncs.append(rep.host_syncs)
            if rep.commits.any():
                acc_lens.append(rep.acc_mean)
            with prof.span("serve.collect"):
                for s in range(B):
                    r = slot_req[s]
                    if r is None:
                        continue
                    if rep.commits[s] > 0 and r.first_token_s < 0:
                        r.first_token_s = clock
                    if not sess.active[s]:
                        r.finish_s = clock
                        with prof.span("serve.retire",
                                       request_id=r.request_id):
                            r.output_tokens = sess.retire(s)
                        r.generated = len(r.output_tokens)
                        slot_req[s] = None
            if cycles > cycle_cap:
                raise RuntimeError("continuous engine exceeded cycle cap "
                                   "(stuck slot?)")
        sess.close()
        # the load signal is scoped to this run — a later run (or a bare
        # scheduler user) must not inherit a stale pressure reading
        self._router.scheduler.set_load(None)
        return acc_lens

    def _refill_and_admit(self, sess, reqs: List[Request],
                          slot_req: List[Optional[Request]],
                          queue: List[Request], clock: float, i: int):
        """Between two cycles: move arrivals into the run queue, shed,
        order it, admit into free slots and publish the load signal.
        Returns the new (clock, next arrival index, queue)."""
        B = self.batch_size
        busy = any(r is not None for r in slot_req)
        if not busy and not queue and reqs[i].arrival_s > clock:
            clock = reqs[i].arrival_s          # idle: jump to arrival
        # run-queue refill: every arrival up to the current clock
        while i < len(reqs) and reqs[i].arrival_s <= clock:
            queue.append(reqs[i])
            i += 1
        # shed policy: a queued request whose TTFT deadline is already
        # unmeetable — it cannot commit a first token before at least
        # one more cycle elapses (cycle-latency EMA) — is dropped NOW,
        # so slot capacity goes to requests that can still meet SLO
        if self.shed_policy == "ttft" and queue:
            est = self._router.profiler.cycle_time()
            kept = []
            for q in queue:
                if clock + est >= q.ttft_deadline_s:
                    q.shed = True
                else:
                    kept.append(q)
            queue = kept
        # SLO-aware admission order: earliest TTFT deadline first.
        # Requests without a TTFT SLO have an infinite deadline, and
        # the arrival-time tie-break keeps them (and whole no-SLO
        # populations) in exact FIFO order — today's behaviour.
        queue.sort(key=lambda q: (q.ttft_deadline_s, q.arrival_s))
        for s in range(B):
            if slot_req[s] is None and queue:
                r = queue.pop(0)
                r.start_s = clock   # queueing ends, service begins
                with self._router.profiler.span("serve.admit",
                                                request_id=r.request_id):
                    clock += sess.admit(s, r.prompt, r.max_new_tokens,
                                        ttft_slo_s=r.ttft_slo_s,
                                        tpot_slo_s=r.tpot_slo_s)
                slot_req[s] = r
        # publish the load signal the goodput-aware chain search
        # reads: residual run-queue depth, slot occupancy, and the
        # profiler's cycle-latency EMA
        busy_n = sum(r is not None for r in slot_req)
        self._router.scheduler.set_load(LoadSignal(
            queue_depth=len(queue), occupancy=busy_n / B,
            cycle_ema_s=self._router.profiler.cycle_time(),
            num_slots=B))
        return clock, i, queue

    # ------------------------------------------------------------------
    def _metrics(self, reqs: List[Request],
                 acc_lens: List[float]) -> ServingMetrics:
        done = [r for r in reqs if r.finish_s >= 0]
        num_shed = sum(1 for r in reqs if r.shed)
        # per-request SLO attainment over the WHOLE offered population:
        # shed and unfinished requests are misses by definition
        attain = (float(np.mean([r.slo_met for r in reqs])) if reqs
                  else float("nan"))
        if not done:
            # degenerate run (nothing finished): NaN-safe metrics instead
            # of max()/mean() raising on empty sequences
            nan = float("nan")
            return ServingMetrics(
                goodput_tps=nan, request_throughput_rps=nan,
                avg_ttft_s=nan, p95_ttft_s=nan, avg_tpot_s=nan,
                avg_latency_s=nan, p95_latency_s=nan, slo_attainment=nan,
                total_tokens=0, num_requests=0, makespan_s=0.0,
                avg_acceptance_len=0.0, avg_queue_s=0.0,
                slo_goodput_rps=nan, request_slo_attainment=attain,
                num_shed=num_shed)
        total_tokens = sum(r.generated for r in done)
        makespan = max(r.finish_s for r in done) - min(r.arrival_s
                                                       for r in done)
        ttfts = np.array([r.ttft for r in done])
        lats = np.array([r.latency for r in done])
        tpots = np.array([r.tpot for r in done if np.isfinite(r.tpot)])
        queues = np.array([r.queue_delay for r in done
                           if np.isfinite(r.queue_delay)])
        # a single instant request gives makespan == 0 — rates are
        # undefined there, not infinite
        rate_denom = makespan if makespan > 0 else float("nan")
        return ServingMetrics(
            goodput_tps=total_tokens / rate_denom,
            request_throughput_rps=len(done) / rate_denom,
            avg_ttft_s=float(ttfts.mean()),
            p95_ttft_s=float(np.percentile(ttfts, 95)),
            avg_tpot_s=float(tpots.mean()) if tpots.size else float("nan"),
            avg_latency_s=float(lats.mean()),
            p95_latency_s=float(np.percentile(lats, 95)),
            slo_attainment=float(np.mean(lats <= self.slo)),
            total_tokens=total_tokens,
            num_requests=len(done),
            makespan_s=makespan,
            avg_acceptance_len=float(np.mean(acc_lens)) if acc_lens else 0.0,
            avg_queue_s=float(queues.mean()) if queues.size else 0.0,
            slo_goodput_rps=sum(r.slo_met for r in done) / rate_denom,
            request_slo_attainment=attain,
            num_shed=num_shed,
            fused_cycle_host_syncs=(float(np.mean(self._fused_syncs))
                                    if self._fused_syncs else float("nan")),
        )
