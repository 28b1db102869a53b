"""ChainRouter (paper §4.1): central coordination of the multi-level
speculative generation loop (Listing 1).

Per cycle:
  1. get the optimal chain + window from the ModelChainScheduler;
  2. DraftRequest to M_1 (with per-model gap catch-up prefix);
  3. VerifyRequest to M_2 … M_t, splicing corrected candidates between
     levels (§4.3);
  4. consensus rollback: model at level j rolls back to
     min(k_j, …, k_N) — the prefix of ITS cached candidate that survived
     every deeper verifier (the paper's 'rollback length … based on
     consensus');
  5. commit target-accepted tokens + bonus/correction, update termination.

State sync invariant: a model's cache holds exactly ``seq[:seq_len-1]`` for
each row once its gap is caught up; gaps (from consensus < k_N) are
re-fed as the masked prefix of its next block (DESIGN §4).

Slot-level continuous batching (paper §4 "asynchronous batch processing"):
the generation loop is exposed as a step/cycle API via ``RouterSession`` —
``admit`` (catch-up prefill of a request into a free slot), ``run_cycle``
(one speculative cycle over every active slot), ``retire`` (free a finished
slot without stalling live ones).  ``ChainRouter.generate`` is a bulk
wrapper over the same session machinery: admit all rows, cycle until every
row terminates.  Slots are batch rows of ONE per-model session state
(key ``model/session_id``), so admission/retirement is per-row state
surgery (Executor.insert / Executor.retire), not state re-creation.

Per-slot chain routing with LAZY chain membership: every slot
carries its own ``ChainChoice`` — the admission-time similarity probe and
the slot's per-row verify feedback drive ``get_optimal_chain(slot)`` with
the global Eq. 7 memo as the shared prior — and a slot materializes state
ONLY in the models of its assigned chain.  Admission therefore prefills
O(chain) models, not O(pool); retirement frees only those rows; a model
joining a slot's chain later catches up through the ``_insert_row`` path
(priced by the scheduler's switch penalty).  ``run_cycle`` groups active
slots by assigned (chain, window, tree) and runs one active-masked
sub-cycle per group, so every jitted shape stays static and greedy output
remains bit-exact to target-only decoding per slot regardless of
grouping (``test_admission_touches_only_chain_members`` and
``test_two_slots_different_chains_bit_exact`` pin both).

Device-resident cycles (default, ``fused=True``): each sub-cycle group
runs as ONE jitted program (``Executor.fused_cycle``) that keeps the
session buffers (seq / seq_len / active / budgets) and every chain
member's model state on device; only a small per-cycle ``FusedSummary``
(commit slab, accept counts, DTV rows, cache cursors) crosses to host in
one transfer, and the host mirror of ``seq``/``seq_len``/``active`` is
rebuilt from it exactly (``generated``/``retire`` read the mirror).
Because fusing hides per-op timings, every ``profile_every``-th cycle
(default 16, cycle 0 included) runs the per-op path instead,
refreshing the scheduler's ``T_i`` EMAs; capacity pressure or an
oversized catch-up gap also falls back to the per-op path for that cycle
(it owns the defrag/re-prefill escapes).  ``fused=False`` runs the
host-orchestrated loop everywhere, bit-exact to the fused one
(``tests/test_fused_cycle.py`` pins the output and the host syncs).

The serving engine (``serving/engine.py``) drives one session through
``admit`` / ``run_cycle`` / ``retire``; the benchmark measures that path
(``python3 bench/run.py --workload <cell>``, numbers in
``PERF_LEDGER.jsonl``).
"""
from __future__ import annotations

import collections
import dataclasses
import time as _time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import verification as ver
from .executor import (DraftRequest, DraftTreeRequest, Executor,
                       FusedCycleRequest, InsertRequest, PrefillRequest,
                       ResolveTreeRequest, RollbackRequest, VerifyRequest,
                       VerifyTreeRequest)
from .model_pool import ModelPool
from .profiler import PerformanceProfiler
from .scheduler import ChainChoice, ModelChainScheduler
from .similarity import SimilarityStore, pairwise_dtv, pairwise_dtv_rows
from .state_manager import StateManager
from .token_tree import TokenTree


@dataclasses.dataclass
class GenerationResult:
    sequences: List[np.ndarray]      # per row: prompt + generated (trimmed)
    generated: List[np.ndarray]      # per row: generated only
    steps: int                       # speculative cycles executed
    committed_tokens: int
    chain_history: List[Tuple[Tuple[str, ...], int]]
    acceptance_lengths: List[float]  # mean accepted per cycle (diagnostics)


@dataclasses.dataclass
class CycleReport:
    """One speculative cycle of a RouterSession.  ``chain``/``window``
    describe the first sub-cycle group (the only group when all slots
    share a chain); ``groups`` lists every (chain, window, num_slots)
    sub-cycle the cycle ran."""
    commits: np.ndarray           # (B,) tokens committed per slot
    wall_s: float                 # measured cycle wall time
    chain: Tuple[str, ...]
    window: int
    acc_mean: float               # mean committed over pre-cycle active slots
    groups: List[Tuple[Tuple[str, ...], int, int]] = \
        dataclasses.field(default_factory=list)
    fused: bool = False           # every group ran as one device program
    host_syncs: int = 0           # profiler host_sync count of this cycle
    wait_s: float = 0.0           # seconds of wall_s spent in cycle.wait
    per_op_groups: int = 0        # groups that ran the per-op path


class ChainRouter:
    def __init__(self, pool: ModelPool, target: str,
                 eos_token: int = -1,
                 greedy: bool = True,
                 temperature: float = 1.0,
                 adaptive: bool = True,
                 fixed_chain: Optional[Sequence[str]] = None,
                 fixed_window: Optional[int] = None,
                 windows: Sequence[int] = (2, 3, 4, 6),
                 max_chain_len: int = 3,
                 reschedule_every: int = 1,
                 tree_shapes: Sequence = (),
                 fixed_tree=None,
                 seed: int = 0,
                 paged: bool = True,
                 fused: bool = True,
                 profile_every: int = 16,
                 scheduler_kwargs: Optional[dict] = None,
                 profiler: Optional[PerformanceProfiler] = None):
        self.pool = pool
        self.target = target
        # device-resident cycles: run each sub-cycle group as one jitted
        # program, with periodic unfused profiling cycles every
        # ``profile_every`` steps (0 = never; when enabled, cycle 0 is a
        # profiling cycle so the scheduler starts with real per-op
        # timings).  ``fused=False`` runs the host-orchestrated per-op
        # loop everywhere.
        self.fused = fused
        self.profile_every = int(profile_every)
        # paged KV cache (per-slot block tables) is the default serving
        # state; ``paged=False`` keeps the contiguous shared-pointer
        # state.  Archs without a per-position cache (SSM/hybrid) fall
        # back to contiguous automatically either way.
        self.paged = paged
        self.eos = eos_token
        self.greedy = greedy
        self.temperature = temperature
        self.adaptive = adaptive
        self.fixed_chain = tuple(fixed_chain) if fixed_chain else None
        if self.fixed_chain is not None:
            assert len(set(self.fixed_chain)) == len(self.fixed_chain), \
                "chains cannot repeat a model (states are keyed by name)"
            assert self.fixed_chain[-1] == target
        self.fixed_window = fixed_window
        # token-tree speculation (off unless shapes are configured): the
        # scheduler may pick a tree draft for tree-capable chains, or a
        # fixed_tree forces one.  branching-factor-1 shapes run through the
        # same tree code path and are bit-identical to linear greedy.
        tree_ok = {m: pool.cfg(m).supports_tree for m in pool.names()}
        self.tree_shapes = tuple(TokenTree.parse(t) for t in tree_shapes)
        self.fixed_tree = (TokenTree.parse(fixed_tree)
                           if fixed_tree is not None else None)
        if self.fixed_tree is not None:
            assert self.fixed_chain is not None, \
                "fixed_tree requires fixed_chain (give the adaptive " \
                "scheduler tree_shapes instead)"
            bad = [m for m in self.fixed_chain if not tree_ok[m]]
            assert not bad, f"models {bad} cannot decode token trees"
            assert len(self.fixed_chain) > 1, \
                "tree speculation needs a draft model in the chain"
        self.reschedule_every = reschedule_every
        self.profiler = profiler or PerformanceProfiler()
        # the pool's placement (Placement.single() unless the pool was
        # built with a mesh): threads the per-member NamedSharding trees
        # through the executor and makes every profiling/scheduler key
        # placement-qualified.  Trivial placement = identity everywhere.
        self.placement = pool.placement
        self.states = StateManager()
        self.executor = Executor(pool, self.states, self.profiler)
        self.sims = SimilarityStore()
        self.scheduler = ModelChainScheduler(
            pool.names(), target, self.profiler, self.sims,
            pool.capability(), max_chain_len=max_chain_len, windows=windows,
            tree_shapes=self.tree_shapes, tree_capable=tree_ok,
            qualify=self.placement.qualify,
            **(scheduler_kwargs or {}))
        self.rng = jax.random.PRNGKey(seed)
        # static gap-prefix width: one jit shape per (model, Tc).  Tree
        # cycles can leave laggard levels up to depth D behind, so D joins
        # the bound; max_block bounds the per-cycle appended block for
        # capacity sizing (a tree appends all N nodes in one cycle).
        trees = self.tree_shapes + ((self.fixed_tree,)
                                    if self.fixed_tree else ())
        depth_max = max((t.depth_levels for t in trees), default=0)
        self.gcap = max(max(windows), depth_max) + max_chain_len + 2
        self.max_block = max(max(windows),
                             max((t.num_nodes for t in trees), default=0))
        # the widest block any one forward appends (the widest gap bucket
        # plus a window): what a recurrent member keeps for rollback
        self.rollback_span = self.gcap + 1 + self.max_block

    # ------------------------------------------------------------------
    def _next_rng(self):
        self.rng, k = jax.random.split(self.rng)
        return k

    def _prefill_model(self, m: str, request_id: str, seq: np.ndarray,
                       seq_len: np.ndarray, max_len: int,
                       rows: Optional[np.ndarray] = None):
        """(Re-)create model m's state holding seq[:seq_len-1] per row.
        ``rows`` (B,) restricts materialization to those slots (lazy chain
        membership) — other rows stay empty, zero-length, zero-block, and
        the forward runs over ``rows`` alone.  Returns the fed rows' (k, V)
        next-token distributions in row order (k = B for ``rows=None``)."""
        eff_len = (seq_len if rows is None
                   else np.where(np.asarray(rows, bool), seq_len, 0))
        S = max(int(eff_len.max()), 1)
        seq = seq[:, :S]
        B = seq.shape[0]
        idx = np.arange(S)[None, :]
        valid = idx < (eff_len - 1)[:, None]
        cfg = self.pool.cfg(m)
        extras = self.pool.model(m).extras_for(B)
        probs, _sid = self.executor.prefill(PrefillRequest(
            model=m, request_id=request_id, tokens=seq.astype(np.int32),
            valid=valid, max_len=max_len,
            rollback_span=self.rollback_span if cfg.recurrent else 0,
            paged=self.paged, extras=extras, rows=rows))
        return probs

    def _gap_prefix(self, m: str, request_id: str, seq, seq_len, active):
        """Build [pads…, gap tokens…, t_last] (B, w) + valid mask, with w
        the smallest width bucket covering the largest row gap (buckets keep
        the jit-shape count bounded while avoiding gcap-wide pad waste).

        Returns (None, None, gap) if a gap exceeds gcap (caller re-prefills).
        """
        B = seq.shape[0]
        sid = StateManager.key(m, request_id)
        cache_len = self.states.lengths(sid)          # (B,)
        gap = (seq_len - 1) - cache_len               # tokens missing
        gap = np.where(active, gap, 0)
        if gap.min() < 0 or gap.max() > self.gcap:
            return None, None, gap
        w = 1
        for bucket in (1, 2, 4, 8, self.gcap + 1):
            if bucket >= int(gap.max()) + 1:
                w = bucket
                break
        # vectorized right-aligned gather (hot decode path — the per-row
        # Python loop was O(B·w) interpreter work per model per cycle):
        # column c of row b holds seq[b, cache_len[b] + c - (w-1-gap[b])]
        # for the gap span, then t_last in the final column.
        cols = np.arange(w)[None, :]                       # (1, w)
        off = cols - (w - 1 - gap[:, None])                # idx into gap run
        gmask = (off >= 0) & (cols < w - 1)                # (B, w)
        src = np.where(gmask, cache_len[:, None] + off, 0)
        prefix = np.where(
            gmask, seq[np.arange(B)[:, None], src], 0).astype(np.int32)
        pvalid = gmask.copy()
        last = np.maximum(seq_len - 1, 0)
        prefix[:, -1] = np.where(active, seq[np.arange(B), last], 0)
        pvalid[:, -1] = active.astype(bool)
        return prefix, pvalid, gap

    def _ensure_capacity(self, m: str, request_id: str, needed: int,
                         seq, seq_len, max_len,
                         rows: Optional[np.ndarray] = None,
                         state_rows: Optional[np.ndarray] = None) -> None:
        """Guard against physical buffer exhaustion.  Paged states use
        BLOCK accounting: every row that will append (``rows`` mask; None =
        all — paged appends only consume capacity for writing rows, so the
        caller should scope the check to them) must fit ``needed`` more
        entries inside its per-row capacity and the pool must hold enough
        free blocks for the worst case — with default full provisioning
        this never trips, because retirement returns blocks instead of
        burning shared-pointer headroom (the churn regression test pins the
        counters at zero).  Contiguous states keep the legacy escalation:
        force-defragment masked holes, then rebuild from the committed
        stream as a last resort (their shared pointer advances for every
        row, so ``rows`` does not apply).  Without this, out-of-range
        appends would be CLAMPED (contiguous) or DROPPED (paged), silently
        corrupting the cache."""
        from ..models.kv_cache import PagedModelState
        sid = StateManager.key(m, request_id)
        st = self.states.get(sid)
        if isinstance(st, PagedModelState):
            sel = (np.ones(st.batch, bool) if rows is None
                   else np.asarray(rows, bool))
            if not sel.any():
                return
            wp = np.asarray(st.write_ptr)[sel]
            nb = np.asarray(st.num_blocks)[sel]
            high = wp + needed
            new_blocks = np.maximum(-(-high // st.block_size) - nb, 0)
            if (high.max() <= st.capacity
                    and int(new_blocks.sum()) <= int(st.free_top)):
                return
            # no defragment to run — paged rows cannot leak holes into each
            # other; a genuine overflow means the session was undersized.
            # ``state_rows`` keeps the rebuild scoped to the rows this
            # model actually holds (lazy chain membership).
            self.states.release(sid)
            self._prefill_model(m, request_id, seq, seq_len, max_len,
                                rows=state_rows)
            self.profiler.count(f"reprefill.{m}")
            return
        if int(st.write_ptr) + needed <= st.capacity:
            return
        self.states.maybe_defragment(sid, force=True)
        self.profiler.count(f"defrag.{m}")
        st = self.states.get(sid)
        if int(st.write_ptr) + needed <= st.capacity:
            return
        self.states.release(sid)
        self._prefill_model(m, request_id, seq, seq_len, max_len,
                            rows=state_rows)
        self.profiler.count(f"reprefill.{m}")

    def _insert_rows(self, m: str, session_id: str, rows: np.ndarray,
                     seq: np.ndarray, seq_len: np.ndarray, max_len: int,
                     state_rows: Optional[np.ndarray] = None
                     ) -> Optional[np.ndarray]:
        """Catch-up prefill of one or more freed rows into a live session
        state: ONE forward over those rows alone feeds every row in
        ``rows`` its ``seq[b, :seq_len[b]-1]`` (occupied rows are not
        computed) — a group of slots joining the same model in one cycle
        costs one insert, not one per row.

        Precondition: each row is already free (retire wiped it, or it
        has been masked-empty since the state was created).

        Returns the (k, V) next-token distributions of the rows fed, in
        row order, or None when there was nothing to feed (1-token
        prompts, or the capacity guard rebuilt the state — which prefills
        the new rows too)."""
        B = seq.shape[0]
        sid = StateManager.key(m, session_id)
        rows = np.asarray(rows, bool)
        n = np.where(rows, seq_len - 1, 0)  # cache invariant: seq[:len-1]
        if int(n.max()) <= 0:
            return None
        w_max = 1                      # reserve for the BUCKETED width: the
        while w_max < int(n.max()):    # append is w wide, and an under-
            w_max *= 2                 # reservation would let the slice
        srows = (rows if state_rows is None      # clamp onto live rows
                 else (np.asarray(state_rows, bool) | rows))
        self._ensure_capacity(m, session_id, w_max + 2, seq, seq_len,
                              max_len, rows=rows, state_rows=srows)
        done = self.states.lengths(sid)     # re-prefill may have run
        need = np.where(rows, n - done, 0)
        if int(need.max()) <= 0:
            return None
        w = 1
        while w < int(need.max()):     # pow-2 width buckets bound jit
            w *= 2                     # shapes (w <= w_max)
        tokens = np.zeros((B, w), np.int32)
        valid = np.zeros((B, w), bool)
        for b in np.where(need > 0)[0]:
            tokens[b, :need[b]] = seq[b, done[b]:n[b]]
            valid[b, :need[b]] = True
        probs = self.executor.insert(InsertRequest(
            model=m, request_id=session_id, tokens=tokens, valid=valid,
            rows=need > 0))
        self.profiler.count(f"admit.{m}", float(rows.sum()))
        return probs

    def _insert_row(self, m: str, session_id: str, row: int,
                    seq: np.ndarray, seq_len: np.ndarray,
                    max_len: int,
                    state_rows: Optional[np.ndarray] = None
                    ) -> Optional[np.ndarray]:
        """Single-row ``_insert_rows`` (admission): returns the admitted
        row's (1, V) distribution for the similarity probe, or None."""
        rows = np.zeros(seq.shape[0], bool)
        rows[row] = True
        return self._insert_rows(m, session_id, rows, seq, seq_len,
                                 max_len, state_rows=state_rows)

    def _sync_chain(self, chain: Tuple[str, ...], request_id: str,
                    needed: int, seq: np.ndarray, seq_len: np.ndarray,
                    active: np.ndarray, max_len: int,
                    members: Optional[Dict[str, np.ndarray]] = None
                    ) -> Dict:
        """Catch every chain member up to the committed stream before a
        cycle: capacity guard, gap prefix per model, and a full catch-up
        re-prefill for models that fell beyond the gap bound.  ``members``
        (model -> (B,) bool, lazy membership) scopes any rebuild to the
        rows the model actually holds.  Returns
        {model: (prefix_tokens, prefix_valid)}."""
        prefixes = {}
        for m in chain:
            srows = members.get(m) if members is not None else None
            self._ensure_capacity(m, request_id, needed, seq, seq_len,
                                  max_len, rows=active, state_rows=srows)
            pfx, pval, _gap = self._gap_prefix(m, request_id, seq, seq_len,
                                               active)
            if pfx is None:   # fell too far behind -> catch-up prefill
                self.states.release(StateManager.key(m, request_id))
                self._prefill_model(m, request_id, seq, seq_len, max_len,
                                    rows=srows)
                pfx, pval, _gap = self._gap_prefix(m, request_id, seq,
                                                   seq_len, active)
            prefixes[m] = (pfx, pval)
        return prefixes

    def _apply_termination(self, seq: np.ndarray, seq_len: np.ndarray,
                           prompt_lens: np.ndarray, budget: np.ndarray,
                           active: np.ndarray,
                           scan_from: Optional[np.ndarray] = None) -> None:
        """Per-row termination: budget exhaustion (over-committed tokens in
        the final cycle are truncated — the prefix still equals target-only
        output, so equivalence is preserved) and EOS.

        ``scan_from`` (B,) bounds the EOS scan to tokens committed THIS
        cycle (everything before it was already scanned when it was
        committed) — without it a long generation re-scans its whole output
        every cycle, O(n²) per request."""
        B = seq.shape[0]
        for b in range(B):
            if not active[b]:
                continue
            if seq_len[b] - prompt_lens[b] >= budget[b]:
                seq_len[b] = prompt_lens[b] + budget[b]
                active[b] = False
            if self.eos >= 0:
                start = prompt_lens[b] if scan_from is None else \
                    max(int(scan_from[b]), int(prompt_lens[b]))
                row = seq[b, start:seq_len[b]]
                hits = np.where(row == self.eos)[0]
                if hits.size:
                    seq_len[b] = start + hits[0] + 1
                    active[b] = False

    @staticmethod
    def _commit_rows(seq: np.ndarray, seq_len: np.ndarray,
                     active: np.ndarray, cand: np.ndarray,
                     k: np.ndarray, next_token: np.ndarray) -> None:
        """Vectorized commit (hot decode path): for each active row b,
        ``seq[b, len:len+k[b]] = cand[b, :k[b]]``, then the
        correction/bonus token, then ``seq_len += k+1``.  Fancy-indexed
        scatter replaces the per-row Python loop; outputs are bit-equal
        (the equivalence suite pins this end to end)."""
        rows = np.where(active)[0]
        if rows.size == 0:
            return
        kr = np.asarray(k, np.int64)[rows]
        base = np.asarray(seq_len[rows], np.int64)
        if cand.shape[1]:
            keep = np.arange(cand.shape[1])[None, :] < kr[:, None]
            rr, cc = np.nonzero(keep)
            seq[rows[rr], base[rr] + cc] = cand[rows[rr], cc]
        seq[rows, base + kr] = np.asarray(next_token)[rows]
        seq_len[rows] += kr + 1

    def _observe_slots(self, slot_keys: Sequence[str],
                       producer: str, verifier: str, dtv: np.ndarray,
                       active: np.ndarray) -> None:
        """Per-slot acceptance feedback: each active row's verify DTV
        updates that slot's similarity view (the per-slot scheduler's
        evidence), alongside the pool-global EMA."""
        if not self.adaptive:
            return
        for b in np.where(active)[0]:
            self.scheduler.observe_slot(slot_keys[b], producer, verifier,
                                        float(dtv[b]))

    # ------------------------------------------------------------------
    def start_session(self, num_slots: int, max_len: int,
                      session_id: str = "sess0") -> "RouterSession":
        """Open a slot-level continuous-batching session (the serving
        engine's entry point; ``generate`` wraps the same machinery)."""
        return RouterSession(self, num_slots, max_len, session_id)

    def generate(self, prompt: np.ndarray, prompt_lens: np.ndarray,
                 max_new_tokens, request_id: str = "req0",
                 capacity_margin: int = 4) -> GenerationResult:
        """Batch generate-to-completion: a bulk wrapper over the slot
        session — every row is admitted up front (one batched prefill,
        identical cost profile to the pre-session code path), then cycles
        run until all rows terminate."""
        B, Tp = prompt.shape
        budget = (np.full(B, max_new_tokens, np.int64)
                  if np.isscalar(max_new_tokens)
                  else np.asarray(max_new_tokens, np.int64))
        max_new = int(budget.max())
        # physical capacity: prompt + worst-case appended blocks (max_block
        # covers the widest linear window or tree node count per cycle)
        max_len = Tp + (max_new + 2) * 2 + self.gcap + \
            (self.max_block + self.scheduler.max_chain_len) * capacity_margin

        sess = self.start_session(B, max_len, session_id=request_id)
        sess.seq[:, :Tp] = prompt
        sess.seq_len[:] = prompt_lens.astype(np.int64)
        sess.prompt_len[:] = sess.seq_len
        sess.budget[:] = budget
        sess.occupied[:] = True
        sess.active[:] = True
        sess.boot()

        acc_lens = []
        while sess.active.any() and sess.committed < max_new * B:
            rep = sess.run_cycle()
            acc_lens.append(rep.acc_mean)
            if sess.steps > max_new * 4 + 16:   # safety net
                break

        seq, seq_len, prompt_len = sess.seq, sess.seq_len, sess.prompt_len
        seqs = [seq[b, :seq_len[b]].copy() for b in range(B)]
        gens = [seq[b, prompt_len[b]:seq_len[b]].copy() for b in range(B)]
        hist = list(sess.chain_history)
        steps = sess.steps
        sess.close()
        return GenerationResult(seqs, gens, steps,
                                int(sum(len(g) for g in gens)),
                                hist, acc_lens)

    # ------------------------------------------------------------------
    def _one_cycle(self, chain: Tuple[str, ...], W: int, request_id: str,
                   seq: np.ndarray, seq_len: np.ndarray,
                   active: np.ndarray, slot_keys: Sequence[str],
                   tree: Optional[TokenTree] = None,
                   members: Optional[Dict[str, np.ndarray]] = None
                   ) -> np.ndarray:
        """Execute one speculative cycle; mutates seq/seq_len in place.
        Returns per-row committed token count.  A non-None ``tree`` routes
        the cycle through tree-structured speculation (draft a token tree,
        prune per level, one merged target verify).  ``members`` carries
        the session's lazy chain membership (rebuild scoping);
        ``slot_keys`` routes per-row verify DTV into the per-slot
        scheduler views."""
        if tree is not None and len(chain) > 1:
            return self._one_tree_cycle(chain, tree, request_id, seq,
                                        seq_len, active, slot_keys,
                                        members=members)
        B = seq.shape[0]
        max_len = self.states.get(
            StateManager.key(self.target, request_id)).capacity

        # --- ensure chain members are synced (or re-prefill laggards) ----
        prefixes = self._sync_chain(chain, request_id,
                                    self.gcap + 2 + W + len(chain),
                                    seq, seq_len, active, max_len,
                                    members=members)

        # --- target-only chain: plain autoregressive step -----------------
        if len(chain) == 1:
            pfx, pval = prefixes[self.target]
            toks, _probs = self.executor.draft(DraftRequest(
                model=self.target, request_id=request_id,
                prefix_tokens=pfx, prefix_valid=pval, window=1,
                active=active, greedy=self.greedy,
                temperature=self.temperature, rng=self._next_rng()))
            nxt = toks[:, 0]
            n_committed = np.where(active, 1, 0)
            self._commit_rows(seq, seq_len, active,
                              np.zeros((B, 0), np.int32),
                              np.zeros(B, np.int64), nxt)
            return n_committed

        # --- draft --------------------------------------------------------
        m1 = chain[0]
        pfx, pval = prefixes[m1]
        cand, cprobs = self.executor.draft(DraftRequest(
            model=m1, request_id=request_id, prefix_tokens=pfx,
            prefix_valid=pval, window=W, active=active, greedy=self.greedy,
            temperature=self.temperature, rng=self._next_rng()))
        valid_len = np.full((B,), W, np.int32)

        # --- staged verification (levels 2..N) -----------------------------
        ks: List[np.ndarray] = []
        producer = m1
        res = None
        for j, m in enumerate(chain[1:], start=2):
            pfx, pval = prefixes[m]
            res = self.executor.verify(VerifyRequest(
                model=m, request_id=request_id, prefix_tokens=pfx,
                prefix_valid=pval, candidates=cand,
                candidate_probs=cprobs, valid_len=valid_len, active=active,
                greedy=self.greedy, temperature=self.temperature,
                rng=self._next_rng()))
            ks.append(np.asarray(res.num_accepted))
            # similarity feedback (Eq. 5/6) between adjacent chain levels:
            # pool-global EMA + per-slot views (slot-level routing)
            if active.any():
                self.sims.update(producer, m,
                                 float(np.mean(res.dtv[active])))
                self._observe_slots(slot_keys, producer, m,
                                    np.asarray(res.dtv), active)
            self.profiler.count(f"accept.{producer}->{m}",
                                float(np.sum(res.num_accepted[active])))
            if m != chain[-1]:
                cand_j, cprobs_j, vlen = ver.splice_candidates(
                    jax.numpy.asarray(cand),
                    jax.numpy.asarray(cprobs) if cprobs is not None else None,
                    jax.tree.map(jax.numpy.asarray, res))
                cand = np.asarray(cand_j)
                cprobs = np.asarray(cprobs_j) if cprobs_j is not None else None
                valid_len = np.asarray(vlen)
            producer = m

        k_N = np.asarray(res.num_accepted)          # target acceptance
        next_token = np.asarray(res.next_token)

        # --- consensus rollback (paper §4.3 RollbackProcessor) -------------
        # level j in [1..N-1] holds a candidate of length W + (j-1) and
        # rolls back to min(k_j, ..., k_N) — the shared pure function also
        # runs inside the fused cycle program, so both paths settle states
        # identically.
        ks_arr = np.stack(ks, axis=0)               # (N-1, B)
        rbs = np.asarray(ver.consensus_rollbacks(
            jnp.asarray(ks_arr), W, jnp.asarray(active)))
        for j, m in enumerate(chain[:-1], start=1):
            self.executor.rollback(RollbackRequest(
                model=m, request_id=request_id,
                r=rbs[j - 1].astype(np.int32)))
        # target rolls back its own rejects
        self.executor.rollback(RollbackRequest(
            model=chain[-1], request_id=request_id,
            r=np.asarray(res.rollback, np.int32)))

        # --- commit ---------------------------------------------------------
        n_committed = np.where(active, k_N + 1, 0)
        self._commit_rows(seq, seq_len, active, cand, k_N, next_token)
        self.profiler.count("cycles")
        self.profiler.count("committed", float(n_committed.sum()))
        return n_committed

    # ------------------------------------------------------------------
    def _one_tree_cycle(self, chain: Tuple[str, ...], tree: TokenTree,
                        request_id: str, seq: np.ndarray,
                        seq_len: np.ndarray,
                        active: np.ndarray, slot_keys: Sequence[str],
                        members: Optional[Dict[str, np.ndarray]] = None
                        ) -> np.ndarray:
        """One tree-structured speculative cycle (SpecInfer-style):

          1. the draft model emits a token tree (static shape, level by
             level, ancestor-masked attention);
          2. every intermediate chain model verifies the WHOLE tree in one
             pass and prunes the sub-trees it rejects (multi-level
             collaboration: the target only considers surviving nodes);
          3. the target's single merged pass accepts the deepest surviving
             root-to-leaf prefix and yields the correction/bonus token;
          4. every model settles its tree block by consensus: keep the
             winning-path nodes all deeper levels also accepted, mask the
             dead branches (ResolveTree = the tree RollbackProcessor).

        Greedy mode commits exactly the target-only greedy stream (at most
        one child per node can match the target argmax).  Pruning can only
        drop candidates, never add them, so bit-equality survives any
        intermediate pruning decisions."""
        B = seq.shape[0]
        N, D = tree.num_nodes, tree.depth_levels
        max_len = self.states.get(
            StateManager.key(self.target, request_id)).capacity

        for m in chain:
            assert self.pool.cfg(m).supports_tree, \
                f"{m} cannot decode token trees"
        prefixes = self._sync_chain(chain, request_id, self.gcap + 2 + N,
                                    seq, seq_len, active, max_len,
                                    members=members)

        # --- draft the tree ------------------------------------------------
        m1 = chain[0]
        pfx, pval = prefixes[m1]
        cand, cprobs = self.executor.draft_tree(DraftTreeRequest(
            model=m1, request_id=request_id, prefix_tokens=pfx,
            prefix_valid=pval, tree=tree, active=active, greedy=self.greedy,
            temperature=self.temperature, rng=self._next_rng()))

        # --- per-level prune, then the target's merged verify --------------
        node_valid = np.broadcast_to(active[:, None], (B, N)).copy()
        accepts: List[np.ndarray] = []
        producer = m1
        res = None
        for m in chain[1:]:
            final = m == chain[-1]
            pfx, pval = prefixes[m]
            res = self.executor.verify_tree(VerifyTreeRequest(
                model=m, request_id=request_id, prefix_tokens=pfx,
                prefix_valid=pval, tree=tree, candidates=cand,
                candidate_probs=cprobs, node_valid=node_valid,
                active=active, greedy=self.greedy,
                temperature=self.temperature, final=final,
                rng=self._next_rng()))
            accepts.append(np.asarray(res.accept))
            if active.any():
                # every tree level verifies the DRAFT's candidate_probs
                # (no per-level re-splicing), so res.dtv measures the
                # draft-vs-this-verifier divergence — attribute it to that
                # pair, not to the adjacent chain edge
                self.sims.update(m1, m, float(np.mean(res.dtv[active])))
                self._observe_slots(slot_keys, m1, m,
                                    np.asarray(res.dtv), active)
            self.profiler.count(f"accept.{producer}->{m}",
                                float(np.sum(res.num_accepted[active])))
            if not final:   # prune: mask the sub-trees this level rejected
                node_valid = node_valid & np.asarray(res.accept)
            producer = m

        k_N = np.asarray(res.num_accepted)
        path = np.asarray(res.path_nodes)
        next_token = np.asarray(res.next_token)

        # --- consensus resolve (tree analogue of RollbackProcessor) --------
        # level j keeps the winning-path prefix that IT and every deeper
        # level accepted: min over the per-level accepted depths along the
        # target's winning path (the draft keeps the min over all levels);
        # the shared pure function also runs inside the fused tree program.
        keeps = np.asarray(ver.tree_consensus_keep(
            [jnp.asarray(a) for a in accepts], jnp.asarray(path),
            jnp.asarray(k_N), jnp.asarray(active)))
        for j, m in enumerate(chain):
            self.executor.resolve_tree(ResolveTreeRequest(
                model=m, request_id=request_id, tree=tree,
                path_nodes=path, keep_len=keeps[j], active=active))

        # --- commit the winning path + correction/bonus --------------------
        path_tokens = np.take_along_axis(cand, path, axis=1)   # (B, D)
        n_committed = np.where(active, k_N + 1, 0)
        self._commit_rows(seq, seq_len, active, path_tokens, k_N,
                          next_token)
        self.profiler.count("cycles")
        self.profiler.count("committed", float(n_committed.sum()))
        return n_committed


class RouterSession:
    """Slot-level continuous-batching handle (§4 asynchronous batching).

    A session owns a fixed pool of ``num_slots`` slots backed by one
    batch-sized ModelState per CHAIN-MEMBER model (state key
    ``model/session_id``).  Request lifecycle per slot:

        QUEUED --admit()--> PREFILL --> DECODING --retire()--> DONE
                 (chain assigned;       (run_cycle() groups
                  catch-up prefill       active slots by chain
                  of the CHAIN's         and advances each
                  models only, over      group in one masked
                  the admitted row;      sub-cycle)
                  live rows untouched)

    Chain membership is per-slot and LAZY: ``admit`` assigns the slot a
    chain (``get_optimal_chain(slot)`` seeded by the global prior, or an
    explicit ``chain=`` override) and materializes its row only in that
    chain's models — O(chain) prefill work, not O(pool).  Rescheduling may
    reassign the chain later: leaving models free the slot's row
    immediately, joining models catch up through ``_insert_row`` (priced
    by the scheduler's switch penalty).  ``retire`` frees exactly the
    member rows.
    """

    def __init__(self, router: ChainRouter, num_slots: int, max_len: int,
                 session_id: str = "sess0"):
        self.router = router
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.session_id = session_id
        B = self.num_slots
        self.seq = np.zeros((B, self.max_len + 8), np.int32)
        self.seq_len = np.zeros(B, np.int64)
        self.prompt_len = np.zeros(B, np.int64)
        self.budget = np.zeros(B, np.int64)
        self.occupied = np.zeros(B, bool)   # slot holds a live request
        self.active = np.zeros(B, bool)     # still generating
        self.steps = 0
        self.committed = 0
        # diagnostics ring: one (chain, window) entry per sub-cycle group
        # — bounded, or an indefinite serving session leaks it at
        # O(groups · cycles)
        self.chain_history: collections.deque = \
            collections.deque(maxlen=4096)
        # lazy chain membership: model -> (B,) bool, True where the
        # slot's row is materialized in that model's session state
        self._members: Dict[str, np.ndarray] = {}
        self._slot_choice: List[Optional[ChainChoice]] = [None] * B
        self._forced: np.ndarray = np.zeros(B, bool)  # admit(chain=...)
        # device-resident session buffers (fused cycles): the numpy arrays
        # above are the HOST MIRROR, rebuilt exactly from each fused
        # cycle's summary slab; ``_dev`` holds the authoritative device
        # copies between fused cycles and is re-uploaded whenever a host
        # path (admission, retirement, an unfused profiling cycle) has
        # mutated the mirror (``_dev_stale``).
        self._dev: Optional[Dict[str, jax.Array]] = None
        self._dev_stale = True
        # summary-fed host views of per-model cache cursors, so the fused
        # path's gap/capacity preflight costs no device sync; cleared by
        # any host-path state op (prefill/insert/free/unfused cycle)
        self._len_cache: Dict[str, np.ndarray] = {}
        self._wp_cache: Dict[str, tuple] = {}

    # ---- scheduling helpers -------------------------------------------
    def _skey(self, slot: int) -> str:
        """Per-slot scheduler key, namespaced so concurrent sessions on
        one router cannot collide on physical slot indices."""
        return f"{self.session_id}:{slot}"

    def _fixed_choice(self) -> ChainChoice:
        r = self.router
        w = (r.fixed_tree.depth_levels if r.fixed_tree is not None
             else (r.fixed_window or 4))
        return ChainChoice(r.fixed_chain, w, 0.0, tree=r.fixed_tree)

    def _choose(self, slot: int) -> ChainChoice:
        r = self.router
        if r.fixed_chain is not None:
            return self._fixed_choice()
        return r.scheduler.get_optimal_chain(slot=self._skey(slot))

    # ---- membership surgery -------------------------------------------
    def _invalidate_state_caches(self) -> None:
        """A host-path state op ran (prefill/insert/free/unfused cycle):
        the summary-fed cursor views are stale — drop them; the next fused
        preflight re-reads from the live states (that path just synced
        anyway, so the extra read is free)."""
        self._len_cache.clear()
        self._wp_cache.clear()

    def _materialize_row(self, m: str, slot: int) -> Optional[np.ndarray]:
        """Ensure model ``m`` holds slot ``slot``'s committed stream:
        create the session state (row-scoped prefill) if this is the
        model's first member, else catch the row up via ``_insert_row``.
        Returns the row's (1, V) next-token distribution when a forward
        ran (the admission similarity probe), else None."""
        r = self.router
        B = self.num_slots
        mem = self._members.setdefault(m, np.zeros(B, bool))
        if mem[slot]:
            return None
        self._invalidate_state_caches()
        sid = StateManager.key(m, self.session_id)
        if not r.states.exists(sid):
            rows = np.zeros(B, bool)
            rows[slot] = True
            probs = r._prefill_model(m, self.session_id, self.seq,
                                     self.seq_len, self.max_len, rows=rows)
            mem[slot] = True
            r.profiler.count(f"admit.{m}")
            return probs
        p = r._insert_row(m, self.session_id, slot, self.seq,
                          self.seq_len, self.max_len, state_rows=mem)
        mem[slot] = True
        return p

    def _release_member(self, m: str, slot: int) -> None:
        """Free one slot's row in one model (chain reassignment dropped
        the model, or the slot retired).  When the model's last member
        leaves, the whole session state is released — a pool model no
        slot routes through holds nothing at all."""
        mem = self._members.get(m)
        if mem is None or not mem[slot]:
            return
        self._invalidate_state_caches()
        rows = np.zeros(self.num_slots, bool)
        rows[slot] = True
        self.router.executor.retire(m, self.session_id, rows)
        mem[slot] = False
        if not mem.any():
            self.router.states.release(
                StateManager.key(m, self.session_id))
            self._members.pop(m, None)

    def _ensure_members(self, chain: Tuple[str, ...],
                        rows: np.ndarray) -> None:
        """Lazy join: materialize any (model, row) of the group that is
        not yet a member (a model that entered the slot's chain after
        admission catches up through the insert path).  All of a model's
        joining rows share ONE batched prefill/insert forward."""
        r = self.router
        for m in chain:
            mem = self._members.setdefault(
                m, np.zeros(self.num_slots, bool))
            missing = rows & ~mem
            if not missing.any():
                continue
            self._invalidate_state_caches()
            sid = StateManager.key(m, self.session_id)
            if not r.states.exists(sid):
                r._prefill_model(m, self.session_id, self.seq,
                                 self.seq_len, self.max_len, rows=missing)
                r.profiler.count(f"admit.{m}", float(missing.sum()))
            else:
                self.router._insert_rows(m, self.session_id, missing,
                                         self.seq, self.seq_len,
                                         self.max_len, state_rows=mem)
            mem |= missing

    # ---- lifecycle ----------------------------------------------------
    def free_slots(self) -> List[int]:
        return [s for s in range(self.num_slots) if not self.occupied[s]]

    def admit(self, slot: int, prompt: np.ndarray,
              max_new_tokens: int,
              chain: Optional[Sequence[str]] = None,
              window: Optional[int] = None,
              tree=None,
              ttft_slo_s: Optional[float] = None,
              tpot_slo_s: Optional[float] = None) -> float:
        """Admit a request into a free slot (QUEUED -> PREFILL): assign
        the slot a chain, write its prompt into the slot row, and
        catch-up-prefill the CHAIN members only.  An explicit
        ``chain``/``window``/``tree`` pins the slot's routing (bypassing
        the scheduler).
        ``ttft_slo_s``/``tpot_slo_s`` attach the request's SLOs to the
        slot's chain search (the goodput objective's per-slot inputs;
        cleared at retirement).
        Returns the measured admission wall time in seconds.

        Raises ValueError — before any slot state is touched — when the
        prompt plus generation budget cannot fit the slot row."""
        assert not self.occupied[slot], f"slot {slot} is occupied"
        prompt = np.asarray(prompt)
        Lp = int(len(prompt))
        assert Lp >= 1, "empty prompt"
        r = self.router
        # validate capacity BEFORE mutating occupied/active/seq: a
        # mid-admission failure must not leave the session inconsistent
        need = Lp + int(max_new_tokens) + r.max_block + 2
        if need > self.max_len:
            raise ValueError(
                f"request needs {need} slots (prompt {Lp} + budget "
                f"{int(max_new_tokens)} + speculation margin) but the "
                f"session rows hold {self.max_len}; admit rejected")
        if chain is not None:
            chain = tuple(chain)
            assert chain[-1] == r.target, \
                f"explicit chain must end with the target {r.target!r}"
            assert len(set(chain)) == len(chain), \
                "chains cannot repeat a model"
            unknown = [m for m in chain if m not in r.pool.names()]
            if unknown:   # must reject BEFORE mutating slot state — a
                raise ValueError(   # KeyError mid-admission leaks the slot
                    f"chain names models not in the pool: {unknown}")
            choice = ChainChoice(
                chain, (window or (r.fixed_window or 4)), 0.0,
                tree=TokenTree.parse(tree) if tree is not None else None)
        else:
            choice = None
        t0 = _time.perf_counter()
        self._dev_stale = True      # host mirror mutates: re-upload before
        self.seq[slot, :] = 0       # the next fused cycle
        self.seq[slot, :Lp] = prompt
        self.seq_len[slot] = Lp
        self.prompt_len[slot] = Lp
        self.budget[slot] = int(max_new_tokens)
        self.occupied[slot] = True
        self.active[slot] = True
        # SLOs must be attached BEFORE the chain choice: the goodput
        # objective's TPOT-feasibility term reads them
        r.scheduler.set_slot_slo(self._skey(slot), ttft_slo_s, tpot_slo_s)
        if choice is None:
            choice = self._choose(slot)
        self._slot_choice[slot] = choice
        self._forced[slot] = chain is not None
        probe: Dict[str, np.ndarray] = {}
        for m in choice.chain:
            p = self._materialize_row(m, slot)
            if p is not None:
                probe[m] = p
        if len(probe) >= 2:   # admission doubles as a similarity probe
            dtvs = pairwise_dtv(probe)
            r.sims.update_many(dtvs)
            if r.adaptive:
                for (a, b), v in dtvs.items():
                    r.scheduler.observe_slot(self._skey(slot), a, b, v)
        return _time.perf_counter() - t0

    def boot(self) -> None:
        """Bulk admission (``ChainRouter.generate``): assign every
        occupied slot its chain, then materialize each model once with a
        BATCHED row-scoped prefill over the union of rows routed through
        it, seeding global + per-slot similarity from the probe."""
        r = self.router
        B = self.num_slots
        self._dev_stale = True
        self._invalidate_state_caches()
        occ = np.where(self.occupied)[0]
        for s in occ:
            if self._slot_choice[s] is None:
                self._slot_choice[s] = self._choose(int(s))
        want: Dict[str, np.ndarray] = {}
        for s in occ:
            for m in self._slot_choice[s].chain:
                want.setdefault(m, np.zeros(B, bool))[s] = True
        probes: Dict[str, np.ndarray] = {}
        for m, rows in want.items():
            p = r._prefill_model(m, self.session_id, self.seq,
                                 self.seq_len, self.max_len, rows=rows)
            probes[m] = np.zeros((B, p.shape[-1]), p.dtype)
            probes[m][rows] = p
            mem = self._members.setdefault(m, np.zeros(B, bool))
            mem |= rows
            r.profiler.count(f"admit.{m}", float(rows.sum()))
        for (a, b), v in pairwise_dtv_rows(probes).items():
            rows = want[a] & want[b]
            if not rows.any():
                continue
            r.sims.update(a, b, float(np.mean(v[rows])))
            if r.adaptive:
                for s in np.where(rows)[0]:
                    r.scheduler.observe_slot(self._skey(int(s)), a, b,
                                             float(v[s]))

    def _reschedule(self) -> None:
        """Refresh per-slot choices; on a chain change, free the leaving
        models' rows (joiners materialize lazily at the next sub-cycle)."""
        r = self.router
        if r.fixed_chain is not None:
            for s in np.where(self.active)[0]:
                if self._slot_choice[s] is None:
                    self._slot_choice[s] = self._fixed_choice()
            return
        resched = r.adaptive and self.steps % r.reschedule_every == 0
        for s in np.where(self.active)[0]:
            cur = self._slot_choice[s]
            if cur is not None and (self._forced[s] or not resched):
                continue
            new = self._choose(int(s))
            if cur is not None and new.chain != cur.chain:
                for m in set(cur.chain) - set(new.chain):
                    self._release_member(m, int(s))
            self._slot_choice[s] = new

    # ---- device-resident fused cycles ---------------------------------
    def _sync_device(self) -> None:
        """Upload the host mirror into the device session buffers if a
        host path mutated it since the last fused cycle."""
        if self._dev is not None and not self._dev_stale:
            return
        # under a real mesh the session buffers are explicitly replicated
        # (every member's slice reads them); trivial placement keeps the
        # plain single-device upload
        rep = self.router.placement.replicated_sharding()

        def up(x):
            a = jnp.asarray(x)
            return a if rep is None else jax.device_put(a, rep)

        self._dev = {
            "seq": up(self.seq),
            "seq_len": up(self.seq_len.astype(np.int32)),
            "prompt_len": up(self.prompt_len.astype(np.int32)),
            "budget": up(self.budget.astype(np.int32)),
            "active": up(self.active),
        }
        self._dev_stale = False

    def _cached_lengths(self, m: str) -> np.ndarray:
        """Per-row cache lengths for model ``m`` — the summary-fed view
        when fresh, else one read from the live state."""
        v = self._len_cache.get(m)
        if v is None:
            v = self.router.states.lengths(
                StateManager.key(m, self.session_id))
            self._len_cache[m] = v
        return v

    def _chain_timed(self, chain: Tuple[str, ...], tree) -> bool:
        """True when every chain member has per-op timing evidence (the
        scheduler's Eq. 7 inputs): draft decode (decode_level for the
        tree's shape) and a verify EMA per verifier level."""
        emas = self.router.profiler.emas
        pq = self.router.placement.qualify
        draft_key = (("decode_level", pq(chain[0]), tree.branching)
                     if tree is not None else ("decode1", pq(chain[0])))
        e = emas.get(draft_key)
        if e is None or e.count == 0:
            return False
        for m in chain[1:]:
            qm = pq(m)
            if not any(k[0] == "verify" and k[1] == qm and e.count
                       for k, e in emas.items() if len(k) == 3):
                return False
        return True

    def _fused_capacity_ok(self, m: str, needed: int,
                           rows: np.ndarray) -> bool:
        """Non-mutating mirror of ``_ensure_capacity``: True when model
        ``m`` can absorb ``needed`` more entries for every row in ``rows``
        without a defrag/rebuild escape (which only the per-op path runs)."""
        from ..models.kv_cache import PagedModelState
        r = self.router
        st = r.states.get(StateManager.key(m, self.session_id))
        info = self._wp_cache.get(m)
        if isinstance(st, PagedModelState):
            if info is None:
                info = (np.asarray(st.write_ptr), int(st.free_top),
                        np.asarray(st.num_blocks))
                self._wp_cache[m] = info
            wp, free_top, nb = info
            sel = np.asarray(rows, bool)
            if not sel.any():
                return True
            high = wp[sel] + needed
            new_blocks = np.maximum(
                -(-high // st.block_size) - nb[sel], 0)
            return bool(high.max() <= st.capacity
                        and int(new_blocks.sum()) <= int(free_top))
        if info is None:
            info = (np.asarray(st.write_ptr), None, None)
            self._wp_cache[m] = info
        return bool(int(np.max(info[0])) + needed <= st.capacity)

    def _prepare_fused(self, choice: ChainChoice, gmask: np.ndarray
                       ) -> Tuple[Optional[FusedCycleRequest],
                                  Optional[str]]:
        """Preflight and inputs of one sub-cycle group as a single device
        program: (the program's request, None), or (None, why the group
        falls back to the per-op path this cycle: ``untimed``, ``gap`` —
        a catch-up gap wider than the program's static prefix — or
        ``capacity``; the last two are the per-op path's escape
        hatches)."""
        r = self.router
        chain = choice.chain
        tree = choice.tree if (choice.tree is not None
                               and len(chain) > 1) else None
        # a chain member with NO per-op timing evidence yet (a freshly
        # explored model) runs per-op this cycle: fused cycles produce no
        # T_i measurements, so without this the scheduler could keep
        # exploring a slow chain forever between profiling cycles — the
        # first cycle of any new chain doubles as its profiling cycle
        # (tests/test_profiler_spans.py::test_fallback_counters_by_reason)
        if not self._chain_timed(chain, tree):
            return None, "untimed"
        depth = tree.depth_levels if tree is not None else choice.window
        # prefix-width bound: the worst-case consensus gap is the target's
        # max accepted length (W + N - 2 linear, D tree); +1 for t_last,
        # +1 slack.  target-only chains never lag by more than 1.
        p_max = (depth + len(chain)) if len(chain) > 1 else 2
        gmax = 0
        for m in chain:
            lens = self._cached_lengths(m)
            gap = np.where(gmask, (self.seq_len - 1) - lens, 0)
            if gap.min() < 0 or gap.max() > p_max - 1:
                return None, "gap"   # needs the re-prefill escape
            gmax = max(gmax, int(gap.max()))
        # pow-2 prefix-width buckets (min 2 = [t_last] + 1 gap slot), like
        # the per-op path's gap buckets: the steady-state cycle (gap 0)
        # runs the narrow program; wide variants compile only when a real
        # catch-up gap appears, instead of every cycle paying p_max-wide
        # draft/verify blocks
        P = 2
        while P - 1 < gmax:
            P *= 2
        P = min(P, p_max)
        block = tree.num_nodes if tree is not None else choice.window
        needed = P + block + len(chain)
        for m in chain:
            if not self._fused_capacity_ok(m, needed, gmask):
                return None, "capacity"  # needs the defrag/rebuild escape
        self._sync_device()
        rngs = tuple(r._next_rng() for _ in chain)
        return FusedCycleRequest(
            chain=chain, request_id=self.session_id,
            window=choice.window, tree=tree, prefix_width=P, eos=r.eos,
            seq=self._dev["seq"], seq_len=self._dev["seq_len"],
            prompt_len=self._dev["prompt_len"],
            budget=self._dev["budget"], active=self._dev["active"],
            gmask=jnp.asarray(gmask), rngs=rngs, greedy=r.greedy,
            temperature=r.temperature), None

    def _run_fused_group(self, req: FusedCycleRequest, choice: ChainChoice,
                         gmask: np.ndarray,
                         slot_keys: Sequence[str]) -> np.ndarray:
        """Run one sub-cycle group as a single device program (``req``
        from ``_prepare_fused``).  Returns per-row raw commits."""
        r = self.router
        ok = False
        try:
            bufs, s = r.executor.fused_cycle(req)
            ok = True
        finally:
            # on ANY failure (including KeyboardInterrupt) the donated
            # device buffers may have been consumed: drop them so a caller
            # that survives the error re-uploads the (still-exact) host
            # mirror instead of passing deleted arrays into the next
            # program.  try/finally, not a broad except: nothing is
            # swallowed, cleanup runs for every exception type
            if not ok:
                self._dev = None
                self._dev_stale = True
        with r.profiler.span("cycle.mirror"):
            return self._mirror_summary(choice, gmask, slot_keys, bufs, s)

    def _count_restores(self, choice: ChainChoice, run: np.ndarray,
                        accepts: np.ndarray) -> None:
        """Count the rows whose recurrent state a linear cycle's rollback
        restored (``restore_rows``), from the accepted counts the summary
        carries: chain member ``i`` rolls back ``W + i - min(k_i, ...,
        k_N)``, the target ``W + N - 2 - k_N`` (``consensus_rollbacks``)."""
        chain = choice.chain
        if len(chain) < 2 or choice.tree is not None:
            return
        r = self.router
        for i, m in enumerate(chain):
            if not r.pool.cfg(m).recurrent:
                continue
            lvl = min(i, len(chain) - 2)
            tc = choice.window + lvl
            kept = np.min(accepts[lvl:], axis=0)
            r.profiler.count("restore_rows",
                             float(np.sum(run & (kept < tc))))

    def _mirror_summary(self, choice: ChainChoice, gmask: np.ndarray,
                        slot_keys: Sequence[str], bufs,
                        s) -> np.ndarray:
        """Host mirror of one fused group's summary, and the similarity
        and acceptance feedback; returns per-row raw commits."""
        r = self.router
        chain = choice.chain
        tree = choice.tree if (choice.tree is not None
                               and len(chain) > 1) else None
        self._dev.update(bufs)
        self._count_restores(choice, gmask & self.active, s.accepts)
        # --- mirror the one-transfer summary onto the host ----------------
        cnum = s.n_committed.astype(np.int64)
        rows = np.where(cnum > 0)[0]
        if rows.size:
            keep = (np.arange(s.slab.shape[1])[None, :]
                    < cnum[rows][:, None])
            rr, cc = np.nonzero(keep)
            self.seq[rows[rr], self.seq_len[rows][rr] + cc] = \
                s.slab[rows[rr], cc]
        self.seq_len[:] = np.where(gmask, s.new_seq_len, self.seq_len)
        self.active[:] = np.where(gmask, s.new_active, self.active)
        for i, m in enumerate(chain):
            self._len_cache[m] = s.lengths[i]
            self._wp_cache[m] = (s.write_ptr[i], int(s.free_top[i]),
                                 s.num_blocks[i])
        # --- feedback loops (same signals/keys the per-op cycle emits) ----
        # tree cycles verify the DRAFT's candidate probs at every level,
        # so DTV is attributed to the (draft, verifier) pair; the accept
        # counters bill adjacent chain edges on both paths
        any_run = bool(gmask.any())
        for lvl in range(s.accepts.shape[0]):
            sim_prod = chain[0] if tree is not None else chain[lvl]
            verif = chain[lvl + 1]
            if any_run:
                r.sims.update(sim_prod, verif,
                              float(np.mean(s.dtv[lvl][gmask])))
                r._observe_slots(slot_keys, sim_prod, verif, s.dtv[lvl],
                                 gmask)
            r.profiler.count(f"accept.{chain[lvl]}->{verif}",
                             float(np.sum(s.accepts[lvl][gmask])))
        if len(chain) > 1:
            r.profiler.count("cycles")
            r.profiler.count("committed", float(cnum.sum()))
        return cnum

    def run_cycle(self) -> CycleReport:
        """One speculative cycle over every active slot (DECODING step).
        Active slots are grouped by their assigned (chain, window, tree)
        and each group runs one masked sub-cycle — batched kernels keep
        their static shapes, rows outside the group ride along as no-ops,
        and per-slot greedy output is bit-exact to target-only decoding
        regardless of the grouping.  Per-slot budget/EOS termination is
        applied after the cycle.

        With ``router.fused`` (default) each group is one device program
        and one host transfer; every ``profile_every``-th cycle instead
        runs the per-op path to refresh the scheduler's timings.

        The call is one ``cycle`` span whose phases are child spans:
        ``cycle.schedule``, then per group ``cycle.prepare``,
        ``cycle.dispatch``, ``cycle.wait``, ``cycle.mirror`` (or
        ``cycle.per_op``), then ``cycle.finish``.  A group that falls
        back to the per-op path counts ``fallback.<reason>``."""
        r = self.router
        B = self.num_slots
        if not self.active.any():
            return CycleReport(np.zeros(B, np.int64), 0.0, (), 0, 0.0)
        prof = r.profiler
        with prof.span("cycle"):
            with prof.span("cycle.schedule"):
                self._reschedule()
                # group slots by assigned (chain, window, tree shape)
                groups: Dict[tuple, np.ndarray] = {}
                order: List[tuple] = []
                for s in np.where(self.active)[0]:
                    c = self._slot_choice[s]
                    key = (c.chain, c.window,
                           c.tree.branching if c.tree is not None else None)
                    if key not in groups:
                        groups[key] = np.zeros(B, bool)
                        order.append(key)
                    groups[key][s] = True
                slot_keys = [self._skey(s) for s in range(B)]
            pre_active = self.active.copy()
            gen_before = (self.seq_len - self.prompt_len).copy()
            n_acc = np.zeros(B, np.int64)
            ginfo: List[Tuple[Tuple[str, ...], int, int]] = []
            profiling = r.fused and (r.profile_every > 0
                                     and self.steps % r.profile_every == 0)
            per_op = 0
            syncs0 = prof.counters["host_sync"]
            wait0 = prof.spans.get("cycle.wait", (0, 0.0))[1]
            t0 = _time.perf_counter()
            for key in order:
                gmask = groups[key] & self.active
                if not gmask.any():
                    continue
                first = int(np.where(gmask)[0][0])
                choice = self._slot_choice[first]
                chain_name = "+".join(choice.chain)
                req, fallback = None, None
                with prof.span("cycle.prepare", chain=chain_name):
                    self._ensure_members(choice.chain, gmask)
                    if profiling:
                        fallback = "profiling"
                    elif r.fused:
                        req, fallback = self._prepare_fused(choice, gmask)
                prof.count("groups")
                if req is not None:
                    acc = self._run_fused_group(req, choice, gmask,
                                                slot_keys)
                else:                # per-op path or a fused fallback
                    if fallback is not None:
                        prof.count(f"fallback.{fallback}")
                    per_op += 1
                    with prof.span("cycle.per_op", chain=chain_name):
                        acc = r._one_cycle(choice.chain, choice.window,
                                           self.session_id, self.seq,
                                           self.seq_len, gmask, slot_keys,
                                           tree=choice.tree,
                                           members=self._members)
                    # the per-op path mutated host state directly: device
                    # buffers and summary-fed cursor views are stale (a
                    # later fused group this cycle must re-upload)
                    self._dev_stale = True
                    self._invalidate_state_caches()
                n_acc += np.asarray(acc, np.int64)  # groups are row-disjoint
                self.chain_history.append((choice.chain, choice.window))
                ginfo.append((choice.chain, choice.window, int(gmask.sum())))
            wall = _time.perf_counter() - t0
            with prof.span("cycle.finish"):
                return self._finish_cycle(
                    n_acc, wall, pre_active, gen_before, ginfo, per_op,
                    host_syncs=int(prof.counters["host_sync"] - syncs0),
                    wait_s=prof.spans.get("cycle.wait", (0, 0.0))[1] - wait0)

    def _finish_cycle(self, n_acc: np.ndarray, wall: float,
                      pre_active: np.ndarray, gen_before: np.ndarray,
                      ginfo: List[Tuple[Tuple[str, ...], int, int]],
                      per_op: int, host_syncs: int,
                      wait_s: float) -> CycleReport:
        """Load signal, termination and the report of one cycle."""
        r = self.router
        # cycle-latency EMA: the load signal's "seconds a queued request
        # waits per cycle boundary" (admission runs between cycles)
        r.profiler.record("cycle_wall", "session", wall)
        acc_mean = float(np.mean(n_acc[pre_active]))
        self.steps += 1
        # EOS scan covers only this cycle's commits (earlier tokens were
        # scanned the cycle they landed) — O(commits), not O(generated)
        scan_from = np.maximum(gen_before + self.prompt_len,
                               self.prompt_len)
        r._apply_termination(self.seq, self.seq_len, self.prompt_len,
                             self.budget, self.active, scan_from=scan_from)
        # acceptance diagnostics report the RAW speculative commit, but the
        # session's committed counter only advances by tokens that SURVIVED
        # termination (budget truncation / EOS cut): tree cycles commit
        # several tokens at once, and counting the truncated overshoot let
        # bulk generate's budget loop exit while rows were still active
        survived = np.where(pre_active,
                            (self.seq_len - self.prompt_len) - gen_before,
                            0).astype(np.int64)
        self.committed += int(survived.sum())
        lead = ginfo[0] if ginfo else ((), 0, 0)
        return CycleReport(n_acc, wall, lead[0], lead[1], acc_mean,
                           groups=ginfo, fused=per_op == 0 and bool(ginfo),
                           host_syncs=host_syncs, wait_s=wait_s,
                           per_op_groups=per_op)

    def generated(self, slot: int) -> np.ndarray:
        """The slot's committed output tokens so far (prompt excluded)."""
        return self.seq[slot,
                        self.prompt_len[slot]:self.seq_len[slot]].copy()

    def retire(self, slot: int) -> np.ndarray:
        """Free a finished slot (DECODING -> DONE) and return its output.
        Only the slot's CHAIN-MEMBER rows are released (recurrent carries
        wiped); pool models outside its chain never held anything.  Live
        slots are untouched."""
        out = self.generated(slot)
        for m in list(self._members):
            self._release_member(m, slot)
        self._dev_stale = True
        self.occupied[slot] = False
        self.active[slot] = False
        self.seq_len[slot] = 0
        self.prompt_len[slot] = 0
        self._slot_choice[slot] = None
        self._forced[slot] = False
        self.router.scheduler.release_slot(self._skey(slot))
        return out

    def close(self) -> None:
        """Release every model state owned by this session, plus the
        scheduler's per-slot views."""
        self.router.states.release_request(self.session_id)
        for s in range(self.num_slots):
            self.router.scheduler.release_slot(self._skey(s))
        self._members.clear()
        self._slot_choice = [None] * self.num_slots
        self._forced[:] = False
        self._dev = None
        self._dev_stale = True
        self._invalidate_state_caches()
