"""Executor + stateless Processors (paper §3.2, §4.3).

The Executor is the data-plane dispatcher: it receives operation requests
from the ChainRouter, routes them to the specialized processors
(Prefill/Draft/Verify/Rollback, plus Insert/Retire for slot-level
continuous batching and DraftTree/VerifyTree/ResolveTree for
tree-structured speculation), resolves models via the ModelPool and state
via the StateManager, and wraps every call with PerformanceProfiler timing
(the feedback loop of §4.6).

All device computation goes through per-(model, op, shape) jitted callables
cached here; tree programs additionally specialize on the static tree
shape (one compile per (model, branching)).

Fused cycle executor (device-resident speculative cycles): one jitted
program per (chain, window | tree) group runs the ENTIRE cycle on device —
gap catch-up prefixes, the draft scan, every intermediate level's
verify + prune, the final target verify, consensus rollback/resolve, the
commit into device-resident session buffers (seq / seq_len / active), and
per-row budget/EOS termination — with the chain members' model states and
the session buffers donated through ``jax.jit``.  Probabilities never
leave the device; a single small ``FusedSummary`` (the newly committed
token slab, per-level accept counts and DTV rows, per-model cache cursors)
crosses to host in ONE transfer per group per cycle.  The per-op
processors above stay as the bit-exact A/B baseline and as the periodic
profiling path that refreshes the scheduler's per-op timings.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import verification as ver
from ..kernels import ops as kops
from ..models import kv_cache as kvc
from .model_pool import ModelPool
from .profiler import PerformanceProfiler
from .state_manager import StateManager
from .token_tree import TokenTree


# ---------------------------------------------------------------------------
# Request messages (paper §4.1 "constructs PrefillRequest messages…")
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class PrefillRequest:
    model: str
    request_id: str
    tokens: np.ndarray            # (B, Tp) int32
    valid: np.ndarray             # (B, Tp) bool
    max_len: int
    rollback_span: int = 0        # widest block a rollback may undo
    paged: bool = True            # paged KV state (archs that support it)
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)
    rows: Optional[np.ndarray] = None     # (B,) bool rows to feed; None=all


@dataclasses.dataclass
class DraftRequest:
    model: str
    request_id: str
    prefix_tokens: np.ndarray     # (B, G+1) gap catch-up ++ t_last
    prefix_valid: np.ndarray      # (B, G+1) bool
    window: int
    active: np.ndarray            # (B,) bool
    greedy: bool = True
    temperature: float = 1.0
    rng: Optional[jax.Array] = None


@dataclasses.dataclass
class VerifyRequest:
    model: str
    request_id: str
    prefix_tokens: np.ndarray     # (B, G+1)
    prefix_valid: np.ndarray      # (B, G+1)
    candidates: np.ndarray        # (B, Tc)
    candidate_probs: Optional[np.ndarray]  # (B, Tc, V) producer dists
    valid_len: Optional[np.ndarray]        # (B,) legit candidate length
    active: np.ndarray            # (B,)
    greedy: bool = True
    temperature: float = 1.0
    rng: Optional[jax.Array] = None


@dataclasses.dataclass
class RollbackRequest:
    model: str
    request_id: str
    r: np.ndarray                 # (B,) int32


@dataclasses.dataclass
class DraftTreeRequest:
    """Tree-structured speculation: draft one token tree (static shape)
    from the last committed token, level by level."""
    model: str
    request_id: str
    prefix_tokens: np.ndarray     # (B, G+1) gap catch-up ++ t_last
    prefix_valid: np.ndarray      # (B, G+1) bool
    tree: TokenTree
    active: np.ndarray            # (B,) bool
    greedy: bool = True
    temperature: float = 1.0
    rng: Optional[jax.Array] = None


@dataclasses.dataclass
class VerifyTreeRequest:
    """One merged verify pass over a drafted token tree.  ``node_valid``
    carries upstream pruning (chain levels before this one); ``final``
    marks the target level (sampling mode runs the multi-branch rejection
    walk there instead of per-node prune coins)."""
    model: str
    request_id: str
    prefix_tokens: np.ndarray     # (B, G+1)
    prefix_valid: np.ndarray      # (B, G+1)
    tree: TokenTree
    candidates: np.ndarray        # (B, N) node tokens
    candidate_probs: np.ndarray   # (B, N, V) producer dists
    node_valid: np.ndarray        # (B, N) bool
    active: np.ndarray            # (B,)
    greedy: bool = True
    temperature: float = 1.0
    final: bool = True
    rng: Optional[jax.Array] = None


@dataclasses.dataclass
class ResolveTreeRequest:
    """Settle a model's speculative tree block: commit the winning path's
    first ``keep_len`` nodes, mask every dead branch (consensus semantics
    identical to the linear RollbackProcessor)."""
    model: str
    request_id: str
    tree: TokenTree
    path_nodes: np.ndarray        # (B, D) winning root->leaf node ids
    keep_len: np.ndarray          # (B,) int32 — consensus depth to keep
    active: Optional[np.ndarray] = None   # (B,) bool — rows that appended
                                  # a tree block this cycle (paged states
                                  # must not touch the trailing slots of
                                  # rows that sat the cycle out)


@dataclasses.dataclass
class InsertRequest:
    """Slot-level continuous batching: catch-up prefill of newly admitted
    rows into an EXISTING batch state.  ``rows`` names the admitted rows
    and ``valid`` their real tokens; the forward runs over those rows
    alone, and every other row is untouched."""
    model: str
    request_id: str               # session id (state key namespace)
    tokens: np.ndarray            # (B, T) int32, left-aligned per row
    valid: np.ndarray             # (B, T) bool
    rows: np.ndarray              # (B,) bool rows to feed


@dataclasses.dataclass
class FusedCycleRequest:
    """One whole speculative cycle for a (chain, window | tree) group,
    executed as a single jitted program over DEVICE-RESIDENT session
    buffers.  ``gmask`` is the group's slot mask (rows outside ride along
    as no-ops); ``rngs`` carries one key per chain position (draft +
    each verify level) so the session RNG stream advances exactly as the
    per-op path would."""
    chain: Tuple[str, ...]
    request_id: str               # session id (state key namespace)
    window: int
    tree: Optional[TokenTree]     # None = linear window draft
    prefix_width: int             # static gap-prefix width (incl. t_last)
    eos: int                      # EOS token id, -1 = none
    seq: jax.Array                # (B, S) int32 device session buffer
    seq_len: jax.Array            # (B,) int32
    prompt_len: jax.Array         # (B,) int32
    budget: jax.Array             # (B,) int32
    active: jax.Array             # (B,) bool — session-wide live mask
    gmask: jax.Array              # (B,) bool — this group's slots
    rngs: Tuple[jax.Array, ...]   # len(chain) keys
    greedy: bool = True
    temperature: float = 1.0


class FusedSummary(NamedTuple):
    """The ONE device→host transfer of a fused cycle (everything the host
    needs to mirror the device buffers and feed the feedback loops)."""
    slab: jnp.ndarray             # (B, C) newly committed tokens (raw)
    n_committed: jnp.ndarray      # (B,) int32 raw commits (pre-termination)
    new_seq_len: jnp.ndarray      # (B,) int32 post-termination
    new_active: jnp.ndarray       # (B,) bool post-termination
    accepts: jnp.ndarray          # (L-1, B) int32 per-level accepted counts
    dtv: jnp.ndarray              # (L-1, B) f32 per-level DTV rows
    lengths: jnp.ndarray          # (M, B) int32 per-model cache lengths
    write_ptr: jnp.ndarray        # (M, B) int32 per-model append cursors
    free_top: jnp.ndarray         # (M,) int32 paged free blocks (or big)
    num_blocks: jnp.ndarray       # (M, B) int32 paged blocks (contig: 0)


# ---------------------------------------------------------------------------
# Fused-cycle device helpers (pure jnp, traced inside the fused program)
# ---------------------------------------------------------------------------
_BIG = np.int32(2 ** 30)      # OOB sentinel for mode="drop" scatters
_NO_POOL = 2 ** 30            # free_top sentinel for contiguous states


def _draft_scan_body(lm, window: int, greedy: bool, temperature: float):
    """The whole-window draft program body (prefix pass + (W-1)-step
    lax.scan).  Shared verbatim by the standalone jitted DraftProcessor and
    the fused cycle program, so both paths run the same math."""
    def sample(logits, k):
        lt = logits.astype(jnp.float32) / temperature
        probs = jax.nn.softmax(lt, -1)
        if greedy:
            return jnp.argmax(logits, -1).astype(jnp.int32), probs
        return jax.random.categorical(k, lt).astype(jnp.int32), probs

    def body(params, state, prefix_tokens, prefix_valid, active, rng):
        logits, state = lm.decode(params, state, prefix_tokens,
                                  valid=prefix_valid & active[:, None],
                                  logits_mode="all")
        rng, k0 = jax.random.split(rng)
        tok0, probs0 = sample(logits[:, -1], k0)

        def step(carry, k):
            state, tok = carry
            lg, state = lm.decode(params, state, tok[:, None],
                                  valid=active[:, None],
                                  logits_mode="all")
            nxt, probs = sample(lg[:, -1], k)
            return (state, nxt), (tok, probs)

        keys = jax.random.split(rng, max(window - 1, 1))
        if window > 1:
            (state, last), (toks, probs) = jax.lax.scan(
                step, (state, tok0), keys[:window - 1])
            all_toks = jnp.concatenate(
                [jnp.swapaxes(toks, 0, 1), last[:, None]], axis=1)
            all_probs = jnp.concatenate(
                [probs0[:, None], jnp.swapaxes(probs, 0, 1)], axis=1)
        else:
            all_toks = tok0[:, None]
            all_probs = probs0[:, None]
        return all_toks, all_probs, state

    return body


def _draft_tree_body(lm, tree: TokenTree, greedy: bool, temperature: float):
    """Whole-tree draft program body (prefix pass + D level expansions),
    shared by the DraftTreeProcessor jit and the fused tree program."""
    D = tree.depth_levels
    sizes = tree.level_sizes

    def body(params, state, prefix_tokens, prefix_valid, active, rng):
        B = prefix_tokens.shape[0]
        logits, state = lm.decode(params, state, prefix_tokens,
                                  valid=prefix_valid & active[:, None],
                                  logits_mode="all")
        par_logits = logits[:, -1:]                  # (B, 1, V)
        toks_all, probs_all = [], []
        for d in range(D):
            n_par = par_logits.shape[1]
            bd = tree.branching[d]
            V = par_logits.shape[-1]
            lt = par_logits.astype(jnp.float32) / temperature
            par_probs = jax.nn.softmax(lt, axis=-1)
            if greedy:
                _, idx = kops.draft_topk(lt.reshape(B * n_par, V), bd)
                toks_d = idx.reshape(B, n_par * bd).astype(jnp.int32)
            else:
                rng, kd = jax.random.split(rng)
                lt_rep = jnp.repeat(lt, bd, axis=1)  # (B, n_par*bd, V)
                toks_d = jax.random.categorical(
                    kd, lt_rep, axis=-1).astype(jnp.int32)
            probs_d = jnp.repeat(par_probs, bd, axis=1)
            lg, state = lm.decode(
                params, state, toks_d,
                valid=jnp.broadcast_to(active[:, None], toks_d.shape),
                logits_mode="all",
                spec_depth=jnp.full((sizes[d],), d, jnp.int32),
                spec_attend=jnp.asarray(tree.level_attend(d)))
            par_logits = lg
            toks_all.append(toks_d)
            probs_all.append(probs_d)
        return (jnp.concatenate(toks_all, axis=1),
                jnp.concatenate(probs_all, axis=1), state)

    return body


def _gap_prefix_dev(state, seq, seq_len, run, width: int):
    """Device analogue of ``ChainRouter._gap_prefix`` with a STATIC width:
    [pads…, gap tokens…, t_last] per row, valid-masked.  Identical valid
    content to the host version (which buckets the width), so the decode
    appends the same logical entries."""
    S = seq.shape[1]
    cache_len = state.length.astype(jnp.int32)
    gap = jnp.where(run, (seq_len - 1) - cache_len, 0)
    cols = jnp.arange(width, dtype=jnp.int32)[None, :]
    off = cols - (width - 1 - gap[:, None])
    gmask = (off >= 0) & (cols < width - 1)
    src = jnp.clip(jnp.where(gmask, cache_len[:, None] + off, 0), 0, S - 1)
    pfx = jnp.where(gmask, jnp.take_along_axis(seq, src, axis=1), 0)
    last = jnp.clip(seq_len - 1, 0, S - 1)
    t_last = jnp.take_along_axis(seq, last[:, None], axis=1)[:, 0]
    pfx = pfx.at[:, -1].set(jnp.where(run, t_last, 0))
    pval = gmask.at[:, -1].set(run)
    return pfx.astype(jnp.int32), pval


def _commit_dev(seq, seq_len, run, cand, k, next_token, slab_width: int):
    """Device analogue of ``ChainRouter._commit_rows``: scatter the
    accepted prefix + correction/bonus into the device ``seq`` buffer.
    Returns (seq, new_seq_len, slab (B, C), n_committed (B,))."""
    B = seq.shape[0]
    j = jnp.arange(slab_width, dtype=jnp.int32)[None, :]
    pad = slab_width - cand.shape[1]
    cand_pad = jnp.concatenate(
        [cand.astype(jnp.int32), jnp.zeros((B, pad), jnp.int32)], axis=1)
    k = k.astype(jnp.int32)
    slab = jnp.where(j < k[:, None], cand_pad, 0)
    slab = jnp.where(j == k[:, None],
                     next_token.astype(jnp.int32)[:, None], slab)
    cnum = jnp.where(run, k + 1, 0).astype(jnp.int32)
    tgt = jnp.where(j < cnum[:, None], seq_len[:, None] + j, _BIG)
    seq = seq.at[jnp.arange(B)[:, None], tgt].set(slab, mode="drop")
    return seq, seq_len + cnum, slab, cnum


def _terminate_dev(slab, run, seq_len_old, new_len, prompt_len,
                   budget, active, eos: int):
    """Device analogue of ``ChainRouter._apply_termination``, bounded to
    this cycle's commit slab: budget clamp first, then the EOS scan up to
    the (possibly clamped) new length.  Rows outside ``run`` keep their
    session values."""
    cap = prompt_len + budget
    over = run & ((new_len - prompt_len) >= budget)
    len1 = jnp.minimum(new_len, cap)
    alive = run & ~over
    if eos >= 0:
        C = slab.shape[1]
        jj = jnp.arange(C, dtype=jnp.int32)[None, :]
        within = jj < (len1 - seq_len_old)[:, None]
        hit = (slab == eos) & within & run[:, None]
        has = jnp.any(hit, axis=1)
        first = jnp.argmax(hit, axis=1).astype(jnp.int32)
        len1 = jnp.where(has, seq_len_old + first + 1, len1)
        alive = alive & ~has
    new_seq_len = jnp.where(run, len1, seq_len_old)
    new_active = jnp.where(run, alive, active)
    return new_seq_len.astype(jnp.int32), new_active


def _wp_rows(st) -> jnp.ndarray:
    wp = st.write_ptr.astype(jnp.int32)
    if wp.ndim == 0:            # contiguous: shared pointer, broadcast
        wp = jnp.broadcast_to(wp[None], (st.batch,))
    return wp


def _free_top_of(st) -> jnp.ndarray:
    ft = getattr(st, "free_top", None)
    if ft is None:
        return jnp.asarray(_NO_POOL, jnp.int32)
    return ft.astype(jnp.int32)


def _num_blocks_of(st) -> jnp.ndarray:
    nb = getattr(st, "num_blocks", None)
    if nb is None:
        return jnp.zeros((st.batch,), jnp.int32)
    return nb.astype(jnp.int32)


def _state_summary(states) -> Tuple[jnp.ndarray, ...]:
    return (jnp.stack([st.length.astype(jnp.int32) for st in states]),
            jnp.stack([_wp_rows(st) for st in states]),
            jnp.stack([_free_top_of(st) for st in states]),
            jnp.stack([_num_blocks_of(st) for st in states]))


class Executor:
    def __init__(self, pool: ModelPool, states: StateManager,
                 profiler: PerformanceProfiler):
        self.pool = pool
        self.states = states
        self.profiler = profiler
        self.placement = pool.placement
        # placement-qualified profiling keys: the scheduler's T_i model is
        # keyed by (model, slice) — the same model on a different slice is
        # a different cost.  Identity on the trivial placement, so every
        # pre-placement EMA key is unchanged.
        self._pq = self.placement.qualify
        # trace-time mesh scope: every jitted program is CALLED (and so
        # first traced) inside this context — the Pallas wrappers in
        # kernels/ops.py replicate their operands only when a mesh is
        # active.  nullcontext on the trivial placement and 1x1 meshes.
        self._mctx = self.placement.mesh_context
        self._jit_cache: Dict[tuple, Any] = {}

    # ---- jitted primitive builders ------------------------------------
    def _fwd(self, model: str, logits_mode: str):
        key = ("fwd", model, logits_mode)
        if key not in self._jit_cache:
            lm = self.pool.model(model)

            @partial(jax.jit, static_argnames=())
            def f(params, state, tokens, valid, extras):
                return lm.decode(params, state, tokens, valid=valid,
                                 logits_mode=logits_mode, **extras)
            self._jit_cache[key] = f
        return self._jit_cache[key]

    def _rollback(self, model: str):
        key = ("rb", model)
        if key not in self._jit_cache:
            lm = self.pool.model(model)
            self._jit_cache[key] = jax.jit(lm.rollback)
        return self._jit_cache[key]

    def _sample(self, greedy: bool, temperature: float):
        key = ("sample", greedy, temperature)
        if key not in self._jit_cache:
            if greedy:
                def s(logits, rng):
                    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
                    return jnp.argmax(logits, -1).astype(jnp.int32), probs
            else:
                def s(logits, rng):
                    lt = logits.astype(jnp.float32) / temperature
                    probs = jax.nn.softmax(lt, -1)
                    return (jax.random.categorical(rng, lt).astype(jnp.int32),
                            probs)
            self._jit_cache[key] = jax.jit(s)
        return self._jit_cache[key]

    # ---- admission: forwards over the admitted rows ---------------------
    def _admission(self, model: str, kind: str):
        """The jitted admission forward of ``model`` (``kind`` "prefill" or
        "insert"): gather the per-row leaves of rows ``idx`` (k,) into a
        k-row state, run the forward there, scatter them back, and return
        the k rows' next-token distributions.  Shared leaves (the paged KV
        pool, the free stack) go in and come out whole.  Entries of ``idx``
        equal to B pad k to its bucket: they read the first row with an
        all-false mask and write nothing.  ``bax`` (static) is each leaf's
        batch axis (``kvc.batch_axes``); None runs every row as one batch
        and ignores ``idx``.  The state is donated, so the scatter and the
        forward's cache writes update it in place."""
        key = ("admission", model, kind)
        if key not in self._jit_cache:
            lm = self.pool.model(model)
            fwd = lm.prefill if kind == "prefill" else lm.decode

            def f(params, state, idx, tokens, valid, extras, bax):
                sub = state
                if bax is not None:
                    take = jnp.where(idx < state.batch, idx, idx[0])
                    sub = kvc.take_rows(state, take, bax)
                    extras = jax.tree.map(lambda x: x[take], extras)
                logits, sub = fwd(params, sub, tokens, valid=valid,
                                  logits_mode="last", **extras)
                if bax is not None:
                    sub = kvc.put_rows(state, sub, idx, bax)
                return jax.nn.softmax(logits.astype(jnp.float32), -1), sub

            f.__name__ = f.__qualname__ = f"admission_{kind}"
            self._jit_cache[key] = jax.jit(f, static_argnames=("bax",),
                                           donate_argnames=("state",))
        return self._jit_cache[key]

    def _admit(self, req, kind: str, state, axes, extras=None):
        """Run ``req``'s admission forward (``kind`` as ``_admission``) over
        the rows ``req.rows`` marks (None: every row), their count bucketed
        to a power of two.  A state whose rows cannot be told apart
        (``kvc.batch_axes`` is None), or a bucket as large as the batch,
        runs every row.  Returns the fed rows' (k, V) distributions in row
        order and the new state."""
        B, T = req.tokens.shape
        sel = (np.arange(B) if req.rows is None
               else np.flatnonzero(req.rows))
        kb = 1
        while kb < len(sel):          # pow-2 row buckets bound jit shapes
            kb *= 2
        bax = kvc.batch_axes(state, axes) if axes is not None else None
        tokens, valid = req.tokens, req.valid
        if bax is None or kb >= B:
            bax, kb, idx = None, B, np.arange(B, dtype=np.int32)
        else:
            idx = np.full(kb, B, np.int32)
            idx[:len(sel)] = sel
            tokens = np.zeros((kb, T), np.int32)
            valid = np.zeros((kb, T), bool)
            tokens[:len(sel)] = req.tokens[sel]
            valid[:len(sel)] = req.valid[sel]
        self.profiler.count("admission_rows", float(kb))
        prog = self._admission(req.model, kind)
        with self.profiler.timed(kind, self._pq(req.model),
                                 tokens=int(req.valid.sum())), self._mctx():
            probs, state = prog(self.pool.params(req.model), state,
                                jnp.asarray(idx), jnp.asarray(tokens),
                                jnp.asarray(valid), extras or {}, bax=bax)
            with self.profiler.wait():
                probs = np.asarray(jax.block_until_ready(probs))
        return (probs[sel] if bax is None else probs[:len(sel)]), state

    def prefill(self, req: PrefillRequest):
        """PrefillProcessor: create the session's B-row state and feed the
        rows ``req.rows`` marks.  Returns their (k, V) last-token probs
        (similarity probes) and the state id."""
        lm = self.pool.model(req.model)
        sid = StateManager.key(req.model, req.request_id)
        B = req.tokens.shape[0]
        state, state_axes = lm.make_state(B, req.max_len,
                                          rollback_span=req.rollback_span,
                                          paged=req.paged)
        # allocate the fresh KV state under the member's placement (the
        # same sharding.py rules that placed the params shard the KV block
        # pools); None on the trivial placement — no movement, the legacy
        # single-device path
        sharding = self.placement.state_sharding(req.model, state_axes,
                                                 state)
        if sharding is not None:
            state = jax.device_put(state, sharding)
        probs, state = self._admit(req, "prefill", state, state_axes,
                                   req.extras)
        self.states.create(sid, state, axes=state_axes, sharding=sharding)
        return probs, sid

    def insert(self, req: InsertRequest):
        """InsertProcessor (continuous batching): feed the admitted rows'
        prompt tokens through the model against the live session state,
        appending their KV/recurrent entries; the forward runs over those
        rows alone, so no other slot is touched.  Returns the admitted
        rows' (k, V) probs at each one's last valid position — an admitted
        row's distribution doubles as a similarity probe."""
        sid = StateManager.key(req.model, req.request_id)
        axes = self.states.axes(sid)
        with self._checked_out([sid]) as (state,):
            probs, state = self._admit(req, "insert", state, axes)
            self.states.commit([sid], [state])
        return probs

    @contextlib.contextmanager
    def _checked_out(self, sids):
        """Check states out of the registry for a program they are donated
        to; the body commits the program's outputs.  If the body raises:
        at trace time nothing executed and the states are still valid, so
        they go back; after dispatch (e.g. device OOM) the donated buffers
        are gone, and committing deleted arrays would poison every later
        op with "Array has been deleted" errors, so the entries are
        dropped and the next access fails cleanly.  try/finally, not a
        broad except: nothing is swallowed, and KeyboardInterrupt /
        SystemExit are covered too."""
        states = self.states.checkout(sids)
        ok = False
        try:
            yield states
            ok = True
        finally:
            if not ok:
                donated = any(
                    getattr(leaf, "is_deleted", lambda: False)()
                    for st in states for leaf in jax.tree.leaves(st))
                if donated:
                    for sid in sids:
                        self.states.release(sid)
                else:
                    self.states.commit(sids, states)

    def retire(self, model: str, request_id: str, rows: np.ndarray):
        """RetireProcessor (continuous batching): free finished slot rows of
        a session state (logical release + recurrent-carry wipe)."""
        self.states.free_rows(StateManager.key(model, request_id), rows)

    def _req_rng(self, rng: Optional[jax.Array], greedy: bool, op: str):
        """Sampling without an explicit rng is a silent-nondeterminism
        footgun: the old ``PRNGKey(0)`` fallback repeated IDENTICAL draws
        every cycle.  Greedy ops never read the key (a constant stand-in
        is fine); sampling ops must be given the session RNG."""
        if rng is not None:
            return rng
        if not greedy:
            raise ValueError(
                f"{op}: sampling requested without an rng — thread the "
                "session RNG (ChainRouter._next_rng) through the request")
        # speclint: disable=rng-literal-key -- greedy ops never read the
        # key; this constant is a traced-signature stand-in, not a stream
        return jax.random.PRNGKey(0)

    def _draft_scan(self, model: str, window: int, greedy: bool,
                    temperature: float):
        """Whole-window drafting fused into ONE jitted program: the prefix
        pass + (W-1) decode steps run as a lax.scan, eliminating W host
        round-trips per cycle (§Perf serving-path iteration 1).  The body
        is shared with the fused cycle program (``_draft_scan_body``)."""
        key = ("draftscan", model, window, greedy, temperature)
        if key in self._jit_cache:
            return self._jit_cache[key]
        f = jax.jit(_draft_scan_body(self.pool.model(model), window,
                                     greedy, temperature))
        self._jit_cache[key] = f
        return f

    def draft(self, req: DraftRequest):
        """DraftProcessor: W speculative tokens from the draft model.

        Returns (draft_tokens (B, W), draft_probs (B, W, V))."""
        params = self.pool.params(req.model)
        sid = StateManager.key(req.model, req.request_id)
        state = self.states.get(sid)
        rng = self._req_rng(req.rng, req.greedy, "draft")
        f = self._draft_scan(req.model, req.window, req.greedy,
                             req.temperature)
        with self.profiler.span("op.draft", model=self._pq(req.model)):
            t0 = time.perf_counter()
            with self._mctx():
                toks, probs, state = f(params, state,
                                       jnp.asarray(req.prefix_tokens),
                                       jnp.asarray(req.prefix_valid),
                                       jnp.asarray(req.active), rng)
            with self.profiler.wait():
                toks = jax.block_until_ready(toks)
            dt = time.perf_counter() - t0
        # amortized per-token draft time feeds the scheduler's T_i
        self.profiler.record("decode1", self._pq(req.model),
                             dt / req.window, tokens=req.window)
        self.states.update(sid, state)
        return np.asarray(toks), np.asarray(probs)

    def verify(self, req: VerifyRequest):
        """VerifyProcessor: one forward pass over [gap ++ t_last ++ cand],
        acceptance rule, returns VerifyResult (numpy)."""
        lm = self.pool.model(req.model)
        params = self.pool.params(req.model)
        sid = StateManager.key(req.model, req.request_id)
        state = self.states.get(sid)
        fwd_all = self._fwd(req.model, "all")
        G1 = req.prefix_tokens.shape[1]          # gap + 1 (t_last)
        Tc = req.candidates.shape[1]
        active = jnp.asarray(req.active)
        block = np.concatenate([req.prefix_tokens, req.candidates], axis=1)
        bvalid = np.concatenate(
            [req.prefix_valid, np.ones_like(req.candidates, bool)], axis=1)
        bvalid = jnp.asarray(bvalid) & active[:, None]

        with self.profiler.span("op.verify", model=self._pq(req.model)):
            t0 = time.perf_counter()
            with self._mctx():
                logits, state = fwd_all(params, state, jnp.asarray(block),
                                        bvalid, {})
            with self.profiler.wait():
                logits = jax.block_until_ready(logits)
            dt = time.perf_counter() - t0
        self.profiler.record("verify", self._pq(req.model), dt, tokens=Tc,
                             block=Tc + 1)
        # amortized per-token verify time (the decode1 analogue)
        self.profiler.record("verify1", self._pq(req.model), dt / (Tc + 1))
        self.states.update(sid, state)

        vlogits = logits[:, G1 - 1:]             # (B, Tc+1, V)
        cands = jnp.asarray(req.candidates)
        cprobs = (jnp.asarray(req.candidate_probs)
                  if req.candidate_probs is not None else None)
        key = ("verifymath", req.greedy, vlogits.shape, req.temperature,
               req.valid_len is not None)
        if key not in self._jit_cache:
            if req.greedy:
                self._jit_cache[key] = jax.jit(ver.verify_greedy)
            else:
                self._jit_cache[key] = jax.jit(partial(
                    ver.verify_sampling, temperature=req.temperature))
        with self._mctx():
            if req.greedy:
                res = self._jit_cache[key](cands, vlogits, cprobs, active)
            else:
                res = self._jit_cache[key](
                    cands, vlogits, cprobs,
                    self._req_rng(req.rng, req.greedy, "verify"),
                    active=active,
                    valid_len=(jnp.asarray(req.valid_len)
                               if req.valid_len is not None else None))
        return jax.tree.map(np.asarray, res)

    def rollback(self, req: RollbackRequest):
        """RollbackProcessor: consensus rollback via StateManager (Eq. 8/9;
        SSM archs restore snapshots first — model.rollback handles both)."""
        sid = StateManager.key(req.model, req.request_id)
        state = self.states.get(sid)
        if self.pool.cfg(req.model).recurrent:
            self.profiler.count("restore_rows", float(np.sum(req.r > 0)))
        with self.profiler.timed("rollback", self._pq(req.model),
                                 tokens=int(req.r.sum())), self._mctx():
            state = self._rollback(req.model)(state, jnp.asarray(req.r))
            with self.profiler.wait():
                jax.block_until_ready(state.write_ptr)
        self.states.update(sid, state)

    # ------------------------------------------------------------------
    # Tree-structured speculation processors
    # ------------------------------------------------------------------
    def _draft_tree(self, model: str, tree: TokenTree, greedy: bool,
                    temperature: float):
        """One jitted program drafting the whole tree: the prefix pass plus
        D level expansions (each level decodes all its nodes as one block
        under the static ancestor mask).  Greedy expansion takes every
        parent's top-b children via the fused vocab-tile kernel
        (ops.draft_topk, argmax tie-compatible — branching-factor 1 is
        bit-identical to the linear draft scan); sampling draws children
        i.i.d. from the parent distribution (the multi-branch rejection
        rule assumes independent draws)."""
        key = ("drafttree", model, tree.branching, greedy, temperature)
        if key in self._jit_cache:
            return self._jit_cache[key]
        f = jax.jit(_draft_tree_body(self.pool.model(model), tree,
                                     greedy, temperature))
        self._jit_cache[key] = f
        return f

    def draft_tree(self, req: DraftTreeRequest):
        """DraftTreeProcessor: returns (node tokens (B, N), producer dists
        (B, N, V)) in tree-node order."""
        params = self.pool.params(req.model)
        sid = StateManager.key(req.model, req.request_id)
        state = self.states.get(sid)
        rng = self._req_rng(req.rng, req.greedy, "draft_tree")
        f = self._draft_tree(req.model, req.tree, req.greedy,
                             req.temperature)
        with self.profiler.span("op.draft_tree",
                                model=self._pq(req.model)):
            t0 = time.perf_counter()
            with self._mctx():
                toks, probs, state = f(params, state,
                                       jnp.asarray(req.prefix_tokens),
                                       jnp.asarray(req.prefix_valid),
                                       jnp.asarray(req.active), rng)
            with self.profiler.wait():
                toks = jax.block_until_ready(toks)
            dt = time.perf_counter() - t0
        # per-LEVEL wall time keyed by the full branching profile (meta
        # block -> EMA key): a level forward decodes several sibling
        # nodes, so feeding it into the per-token decode1 EMA would
        # contaminate the linear cost model, and distinct shapes (even
        # with equal node counts) must not share an EMA
        self.profiler.record("decode_level", self._pq(req.model),
                             dt / req.tree.depth_levels,
                             tokens=req.tree.num_nodes,
                             block=req.tree.branching)
        # amortized per-node draft time (the decode1 analogue for trees)
        self.profiler.record("decode1_tree", self._pq(req.model),
                             dt / req.tree.num_nodes)
        self.states.update(sid, state)
        return np.asarray(toks), np.asarray(probs)

    def _fwd_tree(self, model: str, tree: TokenTree, prefix_width: int):
        """Jitted verify forward over [gap ++ t_last ++ tree nodes]: the
        prefix part appends linearly, the node part carries depth
        positions and the static ancestor-mask override."""
        key = ("fwdtree", model, tree.branching, prefix_width)
        if key in self._jit_cache:
            return self._jit_cache[key]
        lm = self.pool.model(model)
        N = tree.num_nodes
        spec_depth = jnp.asarray(np.concatenate(
            [np.full(prefix_width, -1, np.int32), tree.depth]))
        spec_attend = jnp.asarray(np.concatenate(
            [np.zeros((prefix_width, N), bool), tree.attend], axis=0))

        @jax.jit
        def f(params, state, tokens, valid):
            return lm.decode(params, state, tokens, valid=valid,
                             logits_mode="all", spec_depth=spec_depth,
                             spec_attend=spec_attend)

        self._jit_cache[key] = f
        return f

    def _verify_tree_math(self, tree: TokenTree, greedy: bool,
                          temperature: float, final: bool):
        key = ("treemath", tree.branching, greedy, temperature, final)
        if key not in self._jit_cache:
            def f(cands, vlogits, node_valid, cprobs, rng, active):
                return ver.verify_tree(
                    tree, cands, vlogits, node_valid,
                    candidate_probs=cprobs, key=rng, greedy=greedy,
                    temperature=temperature, active=active, final=final)
            self._jit_cache[key] = jax.jit(f)
        return self._jit_cache[key]

    def verify_tree(self, req: VerifyTreeRequest):
        """VerifyTreeProcessor: one forward over [gap ++ t_last ++ nodes],
        tree acceptance rule, returns TreeVerifyResult (numpy)."""
        params = self.pool.params(req.model)
        sid = StateManager.key(req.model, req.request_id)
        state = self.states.get(sid)
        G1 = req.prefix_tokens.shape[1]
        N = req.tree.num_nodes
        active = jnp.asarray(req.active)
        block = np.concatenate([req.prefix_tokens, req.candidates], axis=1)
        bvalid = np.concatenate(
            [req.prefix_valid, np.ones_like(req.candidates, bool)], axis=1)
        bvalid = jnp.asarray(bvalid) & active[:, None]
        fwd = self._fwd_tree(req.model, req.tree, G1)
        with self.profiler.span("op.verify_tree",
                                model=self._pq(req.model)):
            t0 = time.perf_counter()
            with self._mctx():
                logits, state = fwd(params, state, jnp.asarray(block),
                                    bvalid)
            with self.profiler.wait():
                logits = jax.block_until_ready(logits)
            dt = time.perf_counter() - t0
        self.profiler.record("verify", self._pq(req.model), dt, tokens=N,
                             block=N + 1)
        # amortized per-node verify time (the decode1 analogue)
        self.profiler.record("verify1", self._pq(req.model), dt / (N + 1))
        self.states.update(sid, state)

        vlogits = logits[:, G1 - 1:]                 # (B, N+1, V)
        rng = self._req_rng(req.rng, req.greedy, "verify_tree")
        fmath = self._verify_tree_math(req.tree, req.greedy,
                                       req.temperature, req.final)
        with self._mctx():
            res = fmath(jnp.asarray(req.candidates), vlogits,
                        jnp.asarray(req.node_valid),
                        jnp.asarray(req.candidate_probs), rng, active)
        return jax.tree.map(np.asarray, res)

    def _resolve_tree(self, model: str, tree: TokenTree):
        key = ("resolvetree", model, tree.branching)
        if key not in self._jit_cache:
            N, D = tree.num_nodes, tree.depth_levels

            @jax.jit
            def f(state, path_nodes, keep_len, active):
                keep = kvc.path_keep_matrix(path_nodes, keep_len, N, D)
                return kvc.resolve_tree(state, N, keep, keep_len,
                                        active=active)

            self._jit_cache[key] = f
        return self._jit_cache[key]

    # ------------------------------------------------------------------
    # Fused cycle executor (device-resident speculative cycles)
    # ------------------------------------------------------------------
    def _build_fused_linear(self, lms, window: int, greedy: bool,
                            temperature: float, P: int, eos: int,
                            reshard=None):
        """One program = one whole LINEAR cycle: gap prefixes for every
        chain member, the draft scan, each level's verify (+ splice), the
        consensus rollback, the commit into the device seq buffer, and
        budget/EOS termination.  Mirrors ``ChainRouter._one_cycle`` op for
        op (the math is the same shared functions), so greedy output is
        bit-exact across paths.

        ``reshard`` (Placement.reshard_between_levels) constrains the
        candidate slab back to replicated at every level boundary, so a
        slab produced on the draft's slice reaches a tensor-parallel
        verifier via XLA collectives INSIDE this one program — never a
        host hop.  None on the trivial placement (identical lowering to
        the unmeshed program); a sharding constraint never changes
        values, so meshed output stays bit-exact where the arithmetic
        itself is unchanged (any mesh, 1x1 guaranteed)."""
        N = len(lms)
        W = window
        C = (W + N - 1) if N >= 2 else 1        # commit slab width
        draft_body = _draft_scan_body(lms[0], W if N >= 2 else 1,
                                      greedy, temperature)
        rs = reshard if reshard is not None else (lambda x: x)

        def f(params, states, seq, seq_len, prompt_len, budget, active,
              gmask, rngs):
            states = list(states)
            B = seq.shape[0]
            run = active & gmask
            sl32 = seq_len.astype(jnp.int32)
            with jax.named_scope("gap_prefix"):
                prefixes = [_gap_prefix_dev(st, seq, sl32, run, P)
                            for st in states]
            if N == 1:
                pfx, pval = prefixes[0]
                with jax.named_scope("decode"):
                    toks, _probs, st = draft_body(params[0], states[0], pfx,
                                                  pval, run, rngs[0])
                states[0] = st
                with jax.named_scope("commit"):
                    seq, new_len, slab, cnum = _commit_dev(
                        seq, sl32, run, jnp.zeros((B, 0), jnp.int32),
                        jnp.zeros((B,), jnp.int32), toks[:, 0], C)
                accepts = jnp.zeros((0, B), jnp.int32)
                dtvs = jnp.zeros((0, B), jnp.float32)
            else:
                pfx, pval = prefixes[0]
                with jax.named_scope("draft"):
                    cand, cprobs, st = draft_body(params[0], states[0], pfx,
                                                  pval, run, rngs[0])
                states[0] = st
                cand, cprobs = rs(cand), rs(cprobs)
                valid_len = jnp.full((B,), W, jnp.int32)
                ks, dts = [], []
                res = None
                for j in range(1, N):
                    with jax.named_scope(f"verify.{j}"):
                        vpfx, vpval = prefixes[j]
                        block = jnp.concatenate([vpfx, cand], axis=1)
                        bvalid = jnp.concatenate(
                            [vpval, jnp.ones(cand.shape, bool)],
                            axis=1) & run[:, None]
                        logits, st = lms[j].decode(params[j], states[j],
                                                   block, valid=bvalid,
                                                   logits_mode="all")
                        states[j] = st
                        vlogits = logits[:, P - 1:]
                        if greedy:
                            res = ver.verify_greedy(cand, vlogits, cprobs,
                                                    run)
                        else:
                            res = ver.verify_sampling(
                                cand, vlogits, cprobs, rngs[j],
                                temperature=temperature, active=run,
                                valid_len=valid_len)
                        ks.append(res.num_accepted)
                        dts.append(res.dtv)
                        if j < N - 1:
                            cand, cprobs, valid_len = ver.splice_candidates(
                                cand, cprobs, res)
                            cand, cprobs = rs(cand), rs(cprobs)
                k_n = ks[-1]
                ks_arr = jnp.stack(ks)                   # (N-1, B)
                with jax.named_scope("rollback"):
                    rbs = ver.consensus_rollbacks(ks_arr, W, run)
                    for j in range(N - 1):
                        states[j] = lms[j].rollback(states[j], rbs[j])
                    states[N - 1] = lms[N - 1].rollback(
                        states[N - 1], res.rollback.astype(jnp.int32))
                with jax.named_scope("commit"):
                    seq, new_len, slab, cnum = _commit_dev(
                        seq, sl32, run, cand, k_n, res.next_token, C)
                accepts = ks_arr.astype(jnp.int32)
                dtvs = jnp.stack(dts).astype(jnp.float32)
            with jax.named_scope("commit"):
                new_seq_len, new_active = _terminate_dev(
                    slab, run, sl32, new_len,
                    prompt_len.astype(jnp.int32), budget.astype(jnp.int32),
                    active, eos)
                lengths, wps, fts, nbs = _state_summary(states)
            summary = FusedSummary(slab, cnum, new_seq_len, new_active,
                                   accepts, dtvs, lengths, wps, fts, nbs)
            return tuple(states), seq, new_seq_len, new_active, summary

        return f

    def _build_fused_tree(self, lms, tree: TokenTree, greedy: bool,
                          temperature: float, P: int, eos: int,
                          reshard=None):
        """One program = one whole TREE cycle (draft tree, per-level prune,
        merged target verify, consensus resolve, commit, termination) —
        mirrors ``ChainRouter._one_tree_cycle``.  ``reshard`` as in
        ``_build_fused_linear``: the node slab is constrained back to
        replicated at level boundaries under a real mesh."""
        N = len(lms)
        NT, D = tree.num_nodes, tree.depth_levels
        C = D + 1
        draft_body = _draft_tree_body(lms[0], tree, greedy, temperature)
        rs = reshard if reshard is not None else (lambda x: x)
        spec_depth = jnp.asarray(np.concatenate(
            [np.full(P, -1, np.int32), tree.depth]))
        spec_attend = jnp.asarray(np.concatenate(
            [np.zeros((P, NT), bool), tree.attend], axis=0))

        def f(params, states, seq, seq_len, prompt_len, budget, active,
              gmask, rngs):
            states = list(states)
            B = seq.shape[0]
            run = active & gmask
            sl32 = seq_len.astype(jnp.int32)
            with jax.named_scope("gap_prefix"):
                prefixes = [_gap_prefix_dev(st, seq, sl32, run, P)
                            for st in states]
            pfx, pval = prefixes[0]
            with jax.named_scope("draft"):
                cand, cprobs, st = draft_body(params[0], states[0], pfx,
                                              pval, run, rngs[0])
            states[0] = st
            cand, cprobs = rs(cand), rs(cprobs)
            node_valid = jnp.broadcast_to(run[:, None], (B, NT))
            acc_mats, ks, dts = [], [], []
            res = None
            for j in range(1, N):
                final = j == N - 1
                with jax.named_scope(f"verify.{j}"):
                    vpfx, vpval = prefixes[j]
                    block = jnp.concatenate([vpfx, cand], axis=1)
                    bvalid = jnp.concatenate(
                        [vpval, jnp.ones(cand.shape, bool)],
                        axis=1) & run[:, None]
                    logits, st = lms[j].decode(params[j], states[j], block,
                                               valid=bvalid,
                                               logits_mode="all",
                                               spec_depth=spec_depth,
                                               spec_attend=spec_attend)
                    states[j] = st
                    vlogits = logits[:, P - 1:]
                    res = ver.verify_tree(tree, cand, vlogits, node_valid,
                                          candidate_probs=cprobs,
                                          key=rngs[j], greedy=greedy,
                                          temperature=temperature,
                                          active=run, final=final)
                    acc_mats.append(res.accept)
                    ks.append(res.num_accepted)
                    dts.append(res.dtv)
                    if not final:
                        node_valid = node_valid & res.accept
            k_n = res.num_accepted
            path = res.path_nodes
            with jax.named_scope("rollback"):
                keeps = ver.tree_consensus_keep(acc_mats, path, k_n, run)
                for j in range(N):
                    keep = kvc.path_keep_matrix(path, keeps[j], NT, D)
                    states[j] = kvc.resolve_tree(states[j], NT, keep,
                                                 keeps[j], active=run)
            with jax.named_scope("commit"):
                path_tokens = jnp.take_along_axis(cand, path, axis=1)
                seq, new_len, slab, cnum = _commit_dev(
                    seq, sl32, run, path_tokens, k_n, res.next_token, C)
                new_seq_len, new_active = _terminate_dev(
                    slab, run, sl32, new_len,
                    prompt_len.astype(jnp.int32), budget.astype(jnp.int32),
                    active, eos)
                lengths, wps, fts, nbs = _state_summary(states)
            summary = FusedSummary(slab, cnum, new_seq_len, new_active,
                                   jnp.stack(ks).astype(jnp.int32),
                                   jnp.stack(dts).astype(jnp.float32),
                                   lengths, wps, fts, nbs)
            return tuple(states), seq, new_seq_len, new_active, summary

        return f

    def _fused_program(self, chain: Tuple[str, ...], window: int,
                       tree: Optional[TokenTree], greedy: bool,
                       temperature: float, prefix_width: int, eos: int):
        tkey = tree.branching if tree is not None else None
        key = ("fusedcycle", chain, window, tkey, greedy, temperature,
               prefix_width, eos)
        if key in self._jit_cache:
            return self._jit_cache[key]
        lms = [self.pool.model(m) for m in chain]
        # level-boundary reshard (None on the trivial placement): the
        # candidate slab crosses between member slices on DEVICE, inside
        # this one program — the one-transfer-per-cycle contract holds
        # under meshes
        reshard = self.placement.reshard_between_levels()
        if tree is not None:
            body = self._build_fused_tree(lms, tree, greedy, temperature,
                                          prefix_width, eos,
                                          reshard=reshard)
        else:
            body = self._build_fused_linear(lms, window, greedy,
                                            temperature, prefix_width, eos,
                                            reshard=reshard)
        # the program's name carries its key, so a compile log or a trace
        # says which (levels, window or tree, prefix width) it is
        shape = (f"t{'x'.join(map(str, tkey))}" if tree is not None
                 else f"w{window}")
        body.__name__ = body.__qualname__ = \
            f"fused_{len(chain)}L_{shape}_p{prefix_width}"
        # donate the model states + the seq/seq_len/active session buffers:
        # the cycle replaces them wholesale, so XLA can update in place
        prog = jax.jit(body, donate_argnums=(1, 2, 3, 6))
        self._jit_cache[key] = prog
        return prog

    def fused_cycle(self, req: FusedCycleRequest):
        """FusedCycleProcessor: run one whole speculative cycle for a
        (chain, window | tree) group on device.  Checkout → run (states and
        session buffers donated) → commit; exactly ONE host sync — the
        ``FusedSummary`` device_get — per call.  Returns
        ({seq, seq_len, active} new device buffers, numpy FusedSummary)."""
        with self.profiler.span("cycle.dispatch", chain="+".join(req.chain)):
            sids = [StateManager.key(m, req.request_id) for m in req.chain]
            params = tuple(self.pool.params(m) for m in req.chain)
            prog = self._fused_program(req.chain, req.window, req.tree,
                                       req.greedy, req.temperature,
                                       req.prefix_width, req.eos)
            t0 = time.perf_counter()
            with self._checked_out(sids) as states, self._mctx():
                new_states, seq, seq_len, active, summary = prog(
                    params, tuple(states), req.seq, req.seq_len,
                    req.prompt_len, req.budget, req.active, req.gmask,
                    tuple(req.rngs))
                self.states.commit(sids, list(new_states))
        with self.profiler.wait():
            # speclint: disable=host-sync -- THE sanctioned one-transfer-
            # per-cycle FusedSummary device_get (counted by profiler.wait)
            summary = jax.device_get(summary)
        dt = time.perf_counter() - t0
        self.profiler.record("fused_cycle",
                             "+".join(self._pq(m) for m in req.chain), dt,
                             tokens=int(summary.n_committed.sum()))
        return {"seq": seq, "seq_len": seq_len, "active": active}, summary

    def resolve_tree(self, req: ResolveTreeRequest):
        """ResolveTreeProcessor: consensus settle of the model's tree block
        (the tree analogue of RollbackProcessor — mask/table arithmetic
        plus the write-pointer rewind, no data movement)."""
        sid = StateManager.key(req.model, req.request_id)
        state = self.states.get(sid)
        # no fallback mask: a paged resolve WITHOUT the active gate would
        # re-mask committed trailing slots of rows that sat the cycle out,
        # so kvc.resolve_tree asserts instead (contiguous states ignore it)
        active = (jnp.asarray(req.active, bool)
                  if req.active is not None else None)
        with self.profiler.timed("rollback", self._pq(req.model),
                                 tokens=int(req.keep_len.sum())), \
                self._mctx():
            state = self._resolve_tree(req.model, req.tree)(
                state, jnp.asarray(req.path_nodes, jnp.int32),
                jnp.asarray(req.keep_len, jnp.int32), active)
            with self.profiler.wait():
                jax.block_until_ready(state.write_ptr)
        self.states.update(sid, state)
