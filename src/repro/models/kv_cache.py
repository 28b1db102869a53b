"""ModelState: the paper's synchronized state abstraction (§4.4, Fig. 3).

A ModelState bundles the physical per-layer caches with the *logical* buffers
that make multi-level speculation consistent:

  token_buf  (B, S) int32  — cache_tokens in the paper
  pos_buf    (B, S) int32  — logical position stored in each physical slot
  mask       (B, S) bool   — cache_mask: logical validity (paper Eq. 8)
  length     (B,)   int32  — logical sequence length per row
  write_ptr  ()     int32  — shared physical append pointer

TPU adaptation of Eq. 9 (physical truncation): XLA needs static shapes, so
instead of slicing tensors we *rewind the shared write pointer* to the end of
the last physically-used slot that is still valid in any row.  This reclaims
exactly the common suffix (r_min) with zero data movement — strictly cheaper
than the paper's tensor copy.  Holes left by divergent per-row acceptance
stay masked; ``defragment`` (beyond-paper) compacts them when fragmentation
exceeds a threshold.

Paged variant (``PagedModelState``): the shared write pointer keys every
batch row to the SAME physical slots, so under slot-level continuous
batching each appended block consumes capacity for *every* slot — one
long-lived request plus admission churn burns the buffer at O(cycles) and
trips force-defragment (a full O(L·B·S·H·hd) cache copy) or a full state
rebuild on the hot path.  The paged state splits the physical cache into
fixed-size blocks drawn from a shared pool:

  write_ptr    (B,)   int32  — PER-ROW append cursor (row-local slot)
  block_table  (B, R) int32  — row-local block index -> pool block id (-1 free)
  num_blocks   (B,)   int32  — allocated blocks per row
  free_stack   (P,)   int32  — LIFO free list of pool block ids
  free_top     ()     int32  — number of free blocks (stack height)

Appends allocate blocks per row (only rows that write consume capacity),
``free_rows`` returns a retired row's blocks to the pool in O(1) (no
defragment, no masked-hole leak across slots), and rollback/``resolve_tree``
stay pure block-table + mask edits — the same zero-copy guarantees as the
pointer rewind.  Per-layer attention caches are pool-shaped
``(L, P·bs, Hkv, hd)``; rows address them through the block table
(``physical_slots`` / ``physical_view_index``).  Per-row recurrent
leaves (the linear-attention mixer's conv windows and delta states) ride
in the same paged state beside the pool; SSM/hybrid carries keep the
contiguous state + snapshot rings.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ModelState:
    token_buf: jnp.ndarray          # (B, S) int32
    pos_buf: jnp.ndarray            # (B, S) int32
    mask: jnp.ndarray               # (B, S) bool
    length: jnp.ndarray             # (B,) int32
    write_ptr: jnp.ndarray          # () int32
    layers: Dict[str, Any]          # model-specific per-layer caches

    @property
    def batch(self) -> int:
        return self.token_buf.shape[0]

    @property
    def capacity(self) -> int:
        return self.token_buf.shape[1]


def make_state(batch: int, max_len: int, layers: Dict[str, Any]) -> ModelState:
    return ModelState(
        token_buf=jnp.zeros((batch, max_len), jnp.int32),
        pos_buf=jnp.zeros((batch, max_len), jnp.int32),
        mask=jnp.zeros((batch, max_len), jnp.bool_),
        length=jnp.zeros((batch,), jnp.int32),
        write_ptr=jnp.zeros((), jnp.int32),
        layers=layers,
    )


_BIG = np.int32(2 ** 30)      # numpy: no device array at import


def _append_positions(state, valid, spec_depth):
    """Shared logical-position arithmetic for both state layouts.

    Returns (q_pos (B, T) with invalid -> far-future, adv (B,) length
    advance).  ``spec_depth`` semantics documented on ``append_tokens``."""
    if spec_depth is None:
        q_pos = (state.length[:, None]
                 + jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1)
        adv = jnp.sum(valid, axis=1, dtype=jnp.int32)
    else:
        is_lin = (spec_depth < 0)[None, :]                       # (1, T)
        lin_valid = valid & is_lin
        lin_pos = (state.length[:, None]
                   + jnp.cumsum(lin_valid.astype(jnp.int32), axis=1) - 1)
        adv = jnp.sum(lin_valid, axis=1, dtype=jnp.int32)
        base = state.length + adv                                # (B,)
        spec_pos = base[:, None] + jnp.maximum(spec_depth, 0)[None, :]
        q_pos = jnp.where(is_lin, lin_pos, spec_pos)
    return jnp.where(valid, q_pos, _BIG), adv


# ---------------------------------------------------------------------------
# Logical append (all rows write the same physical slots [P, P+T))
# ---------------------------------------------------------------------------
def append_tokens(state, tokens: jnp.ndarray,
                  valid: Optional[jnp.ndarray] = None,
                  spec_depth: Optional[jnp.ndarray] = None):
    """Append T tokens per row; returns (new_state, q_positions (B,T), slot).

    Contiguous ``ModelState``: all rows write the shared physical slots
    [P, P+T) and ``slot`` is the scalar slot start.  ``PagedModelState``:
    each row writes only its own VALID entries at its per-row cursor
    (allocating pool blocks as needed) and ``slot`` is the (B, T) array of
    row-local slots (invalid entries -> far-future sentinel).

    ``valid`` (B, T) bool marks which appended entries are logically valid
    (used when a batch row has already finished but the batch step still runs).

    ``spec_depth`` (T,) int32 marks *speculative tree* entries: ``-1`` is a
    normal committed-stream token (linear cumsum position, advances
    ``length``), ``d >= 0`` is a tree node at depth ``d`` — its logical
    position is ``post-linear length + d`` (siblings share a position) and
    it does NOT advance ``length``; the block is later settled by
    ``resolve_tree`` (commit the winning path, mask dead branches).  With
    ``spec_depth=None`` the behaviour is bit-identical to the pre-tree code.
    """
    B, T = tokens.shape
    if valid is None:
        valid = jnp.ones((B, T), jnp.bool_)
    if isinstance(state, PagedModelState):
        return paged_append_tokens(state, tokens, valid, spec_depth)
    P = state.write_ptr
    q_pos, adv = _append_positions(state, valid, spec_depth)
    upd = lambda buf, new: jax.lax.dynamic_update_slice_in_dim(buf, new, P, axis=1)
    new = dataclasses.replace(
        state,
        token_buf=upd(state.token_buf, tokens.astype(jnp.int32)),
        pos_buf=upd(state.pos_buf, q_pos.astype(jnp.int32)),
        mask=upd(state.mask, valid),
        length=state.length + adv,
        write_ptr=P + T,
    )
    return new, q_pos, P


# ---------------------------------------------------------------------------
# Rollback: Eq. 8 (logical) + Eq. 9 TPU analogue (pointer rewind)
# ---------------------------------------------------------------------------
def logical_rollback(state: ModelState, r: jnp.ndarray) -> ModelState:
    """Invalidate the last ``r[b]`` logically-valid entries of each row.

    Pure mask arithmetic — no data movement (paper step 1, Eq. 8)."""
    new_len = jnp.maximum(state.length - r.astype(jnp.int32), 0)
    keep = state.pos_buf < new_len[:, None]
    return dataclasses.replace(
        state, mask=state.mask & keep, length=new_len)


def physical_reclaim(state: ModelState) -> ModelState:
    """Rewind the shared write pointer past the common invalid suffix.

    TPU-native Eq. 9: reclaims the r_min common suffix without copying."""
    S = state.capacity
    slot_ids = jnp.arange(S, dtype=jnp.int32)[None, :]
    # highest still-valid physical slot across the whole batch
    last_valid = jnp.max(jnp.where(state.mask, slot_ids, -1))
    new_ptr = jnp.minimum(state.write_ptr, last_valid + 1)
    return dataclasses.replace(state, write_ptr=new_ptr.astype(jnp.int32))


def rollback(state, r: jnp.ndarray):
    """Full paper rollback: logical mask update then physical reclaim.

    Paged states rewind each row's OWN cursor (reclaiming even non-common
    suffixes) and return now-empty trailing blocks to the pool."""
    if isinstance(state, PagedModelState):
        return paged_rollback(state, r)
    return physical_reclaim(logical_rollback(state, r))


def resolve_tree(state, num_nodes: int, keep: jnp.ndarray,
                 add_len: jnp.ndarray,
                 active: Optional[jnp.ndarray] = None):
    """Settle a speculative tree block (the LAST ``num_nodes`` physical
    slots, appended with ``spec_depth``): keep the winning-path nodes, mask
    every dead branch, and advance each row's logical length by the number
    of kept nodes.

    Same machinery as logical rollback — pure mask arithmetic plus the
    write-pointer rewind, zero data movement.  Dead-branch holes inside the
    block stay masked and are reclaimed by ``defragment`` under capacity
    pressure, exactly like divergent-acceptance holes in linear mode.

    keep:    (B, N) bool — True for nodes on the row's committed path
    add_len: (B,) int32  — kept-path length (0 for inactive rows)
    active:  (B,) bool   — rows that actually appended a tree block this
             cycle.  Contiguous states can ignore it (inactive rows' block
             region holds freshly-written masked junk), but paged rows that
             sat out the cycle never advanced their cursor — their trailing
             slots hold COMMITTED data that must not be re-masked.
    """
    if isinstance(state, PagedModelState):
        assert active is not None, "paged resolve_tree needs the active mask"
        return paged_resolve_tree(state, num_nodes, keep, add_len, active)
    B, S = state.token_buf.shape
    start = state.write_ptr - num_nodes
    slot_ids = jnp.arange(S, dtype=jnp.int32)[None, :]
    in_block = (slot_ids >= start) & (slot_ids < state.write_ptr)
    keep_full = jnp.zeros((B, S), jnp.bool_)
    keep_full = jax.lax.dynamic_update_slice(keep_full, keep, (0, start))
    new = dataclasses.replace(
        state,
        mask=jnp.where(in_block, state.mask & keep_full, state.mask),
        length=state.length + add_len.astype(jnp.int32),
    )
    return physical_reclaim(new)


def path_keep_matrix(path_nodes: jnp.ndarray, keep_len: jnp.ndarray,
                     num_nodes: int, depth_levels: int) -> jnp.ndarray:
    """(B, D) winning-path node ids + (B,) consensus depth -> (B, N) bool
    keep matrix for ``resolve_tree`` (True for the first ``keep_len`` nodes
    along the path).  Pure index arithmetic, used in-program by both the
    per-op ResolveTreeProcessor and the fused cycle executor."""
    depth_ok = (jnp.arange(depth_levels, dtype=jnp.int32)[None, :]
                < keep_len[:, None])                            # (B, D)
    onehot = ((path_nodes[..., None]
               == jnp.arange(num_nodes, dtype=jnp.int32)[None, None, :])
              & depth_ok[..., None])                            # (B, D, N)
    return jnp.any(onehot, axis=1)                              # (B, N)


def free_rows(state, rows, layer_axes=None):
    """Retire a subset of batch rows so their slots can host new requests
    (slot-level continuous batching).

    Paged states return every block of the freed rows to the pool in O(1)
    (block-table + free-stack edits, no cache-tensor movement at all).

    Logical release is pure mask arithmetic: the rows' cache entries become
    dead (mask False, length 0) and are reclaimed by ``defragment`` under
    capacity pressure.  Per-position caches (named ``"seq"`` axis —
    attention KV and quant scales) need nothing more: masked slots are
    never attended, and rewriting them per retirement would be an
    O(L·B·S·H·hd) copy on the serving hot path.  Positionless recurrent
    carries (SSM / hybrid) WOULD leak the old request into the next
    occupant, so when ``layer_axes`` (the axes pytree from ``make_state``)
    is provided, every seq-less layer leaf with a named ``"batch"`` axis is
    zeroed along that axis for the freed rows.  Snapshot rings keep stale
    entries: they are keyed by physical slot, and a freshly admitted row
    only ever rolls back to slots written after its admission.
    """
    rows = jnp.asarray(rows, bool)                # (B,) True = free this row
    keep = ~rows
    if isinstance(state, PagedModelState):
        return paged_free_rows(state, rows, layer_axes)
    new = dataclasses.replace(
        state,
        mask=state.mask & keep[:, None],
        length=jnp.where(rows, 0, state.length).astype(jnp.int32),
    )
    if layer_axes is None:
        return new

    leaves, treedef = jax.tree.flatten(state.layers)
    ax_leaves = treedef.flatten_up_to(layer_axes)

    def wipe(x, ax):
        if not isinstance(ax, tuple) or "batch" not in ax or "seq" in ax:
            return x
        bi = ax.index("batch")
        shape = [1] * x.ndim
        shape[bi] = keep.shape[0]
        return x * keep.reshape(shape).astype(x.dtype)

    new_leaves = [wipe(x, ax) for x, ax in zip(leaves, ax_leaves)]
    return dataclasses.replace(
        new, layers=jax.tree.unflatten(treedef, new_leaves))


# ---------------------------------------------------------------------------
# Row-scoped views: a forward over some rows of a state
# ---------------------------------------------------------------------------
def batch_axes(state, state_axes) -> Optional[tuple]:
    """Each leaf's ``"batch"`` axis, in ``jax.tree.leaves(state)`` order,
    from the axes ``make_state`` returned with it; -1 for a leaf every row
    shares (the paged block pool and its free stack, a contiguous state's
    write pointer).  None when some leaf declares no axes at all (snapshot
    rings), so its rows cannot be told apart."""
    leaves, treedef = jax.tree.flatten(state)
    out = []
    for ax in treedef.flatten_up_to(state_axes):
        if not isinstance(ax, tuple):
            return None
        out.append(ax.index("batch") if "batch" in ax else -1)
    return tuple(out)


def take_rows(state, rows: jnp.ndarray, bax: tuple):
    """The state of rows ``rows`` (k,) alone: every per-row leaf gathered
    along its batch axis (``bax``, from ``batch_axes``), every shared leaf
    whole.  Out-of-range rows read the last row."""
    leaves, treedef = jax.tree.flatten(state)
    return jax.tree.unflatten(treedef, [
        x if a < 0 else jnp.take(x, rows, axis=a, mode="clip")
        for x, a in zip(leaves, bax)])


def put_rows(state, sub, rows: jnp.ndarray, bax: tuple):
    """Write ``sub`` (a ``take_rows`` state, run forward) back into rows
    ``rows`` of ``state``; its shared leaves replace the state's whole.
    Out-of-range rows (padding) write nothing."""
    leaves, treedef = jax.tree.flatten(state)
    new = jax.tree.leaves(sub)

    def put(x, y, a):
        if a < 0:
            return y
        return x.at[(slice(None),) * a + (rows,)].set(y, mode="drop")
    return jax.tree.unflatten(treedef, [
        put(x, y, a) for x, y, a in zip(leaves, new, bax)])


def fragmentation(state) -> jnp.ndarray:
    """Fraction of physically-used slots that are logically dead."""
    if isinstance(state, PagedModelState):
        return paged_fragmentation(state)
    S = state.capacity
    used = jnp.maximum(state.write_ptr, 1).astype(jnp.float32)
    slot_ids = jnp.arange(S, dtype=jnp.int32)[None, :]
    in_use = slot_ids < state.write_ptr
    dead = jnp.sum((~state.mask) & in_use, axis=1).astype(jnp.float32)
    return jnp.mean(dead) / used


def defragment(state: ModelState) -> ModelState:
    """Beyond-paper: compact every row's valid entries to the buffer front.

    Gathers each row's valid slots (stable order by logical position) and
    rewrites all buffers + every per-layer cache along the S axis.  O(S·cache)
    data movement — call only when ``fragmentation`` exceeds a threshold.
    """
    B, S = state.token_buf.shape
    big = jnp.int32(2**30)
    sort_key = jnp.where(state.mask, state.pos_buf, big)
    order = jnp.argsort(sort_key, axis=1)                       # (B, S)
    take = lambda buf: jnp.take_along_axis(buf, order, axis=1)
    n_valid = jnp.sum(state.mask, axis=1).astype(jnp.int32)
    new_mask = jnp.arange(S, dtype=jnp.int32)[None, :] < n_valid[:, None]

    def gather_cache(x):
        # per-layer caches are (L, B, S, ...): gather along axis=2
        if x.ndim >= 3 and x.shape[1] == B and x.shape[2] == S:
            idx = order.reshape((1, B, S) + (1,) * (x.ndim - 3))
            return jnp.take_along_axis(x, idx, axis=2)
        return x

    return dataclasses.replace(
        state,
        token_buf=take(state.token_buf),
        pos_buf=jnp.where(new_mask, take(state.pos_buf), 0),
        mask=new_mask,
        write_ptr=jnp.max(n_valid),
        layers=jax.tree.map(gather_cache, state.layers),
    )


# ---------------------------------------------------------------------------
# Attention KV cache helpers (stacked layers: (L, B, S, Hkv, hd))
# ---------------------------------------------------------------------------
def make_attn_cache(num_layers, batch, max_len, num_kv_heads, head_dim,
                    dtype, quant: bool = False):
    shape = (num_layers, batch, max_len, num_kv_heads, head_dim)
    if quant:
        # §Perf G2: int8 cache + per-(token, head) scales — halves the
        # dominant serving memory/traffic; dequant fuses into the dots
        sshape = (num_layers, batch, max_len, num_kv_heads)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.bfloat16),
                "v_scale": jnp.zeros(sshape, jnp.bfloat16)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attn_cache_axes(prefix=(), quant: bool = False):
    ax = ("layers", "batch", "seq", "kv_heads", "head_dim")
    d = {"k": prefix + ax, "v": prefix + ax}
    if quant:
        sx = ("layers", "batch", "seq", "kv_heads")
        d["k_scale"] = prefix + sx
        d["v_scale"] = prefix + sx
    return d


def kv_quantize(x: jnp.ndarray):
    """(B, T, Hkv, hd) -> (int8 codes, (B, T, Hkv) bf16 scales)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    return codes, scale.astype(jnp.bfloat16)


def kv_dequantize(codes: jnp.ndarray, scale: jnp.ndarray, dtype):
    return (codes.astype(dtype) * scale[..., None].astype(dtype))


def write_kv(cache_k: jnp.ndarray, cache_v: jnp.ndarray,
             k_new: jnp.ndarray, v_new: jnp.ndarray, slot_start):
    """Write (B,T,Hkv,hd) into a single layer's (B,S,Hkv,hd) cache views."""
    ck = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new, slot_start, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new, slot_start, axis=1)
    return ck, cv


def write_layer(cache: jnp.ndarray, layer, new: jnp.ndarray, slot_start):
    """Write (B, T, Hkv, ...) entries into layer ``layer`` of a stacked
    (L, B, S, Hkv, ...) cache at the slots ``write_kv`` writes (its
    ``dynamic_update_slice`` clamps the start so the block fits) — in
    place when ``cache`` is a scan carry.  A scatter of (row, slot, head)
    entries, like ``paged_scatter_layer``: a block update would make the
    TPU copy the cache out of its head-major layout and back, and the CPU
    widen a bf16 cache to f32 around the whole stack."""
    B, T, H = new.shape[:3]
    start = jnp.clip(slot_start, 0, cache.shape[2] - T)
    return cache.at[layer, jnp.arange(B)[:, None, None],
                    (start + jnp.arange(T))[None, :, None],
                    jnp.arange(H)].set(new.astype(cache.dtype))


# ---------------------------------------------------------------------------
# SSM snapshot buffers (rollback support for recurrent archs — DESIGN §5)
# ---------------------------------------------------------------------------
# Recurrent state has no per-position cache; rollback restores a snapshot.
# Snapshots are only materialized in the speculative serving path (small
# models); the dry-run decode step carries ``snaps=None``.
def snap_write(snaps: jnp.ndarray, current: jnp.ndarray, pos: jnp.ndarray):
    """snaps: (K, ...) ring buffer; store ``current`` at slot pos % K."""
    K = snaps.shape[0]
    return jax.lax.dynamic_update_index_in_dim(
        snaps, current, pos % K, axis=0)


def snap_read(snaps: jnp.ndarray, pos: jnp.ndarray):
    K = snaps.shape[0]
    return jax.lax.dynamic_index_in_dim(snaps, pos % K, axis=0, keepdims=False)


# ===========================================================================
# Paged KV cache: per-row block tables over a shared pool of fixed blocks
# ===========================================================================
PAGE_BLOCK = 32   # default tokens per KV block (TPU path wants >= 8)


def _ceil_div(a, b):
    return -(-a // b)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedModelState:
    """Paged analogue of ModelState (module docstring has the layout).

    The logical buffers (token/pos/mask/length) keep the exact (B, S)
    row-major addressing of the contiguous state — S is the per-row
    capacity ``blocks_per_row * block_size`` — so every mask consumer
    (``build_attention_mask``, overlays, verification) is unchanged.  Only
    the *physical* KV tensors move to the pool layout; rows translate
    row-local slots to pool slots through ``block_table``.
    """
    token_buf: jnp.ndarray          # (B, S) int32
    pos_buf: jnp.ndarray            # (B, S) int32
    mask: jnp.ndarray               # (B, S) bool
    length: jnp.ndarray             # (B,) int32
    write_ptr: jnp.ndarray          # (B,) int32 per-row append cursor
    block_table: jnp.ndarray        # (B, R) int32 pool block id or -1
    num_blocks: jnp.ndarray         # (B,) int32 allocated blocks per row
    free_stack: jnp.ndarray         # (P,) int32 LIFO of free pool block ids
    free_top: jnp.ndarray           # () int32 stack height (# free blocks)
    layers: Dict[str, Any]          # per-layer caches (attention: pool flat)
    block_size: int = dataclasses.field(
        metadata=dict(static=True), default=PAGE_BLOCK)

    @property
    def batch(self) -> int:
        return self.token_buf.shape[0]

    @property
    def capacity(self) -> int:
        """Per-row logical capacity (R * block_size)."""
        return self.token_buf.shape[1]

    @property
    def blocks_per_row(self) -> int:
        return self.block_table.shape[1]

    @property
    def pool_blocks(self) -> int:
        return self.free_stack.shape[0]


def make_paged_state(batch: int, max_len: int, layers: Dict[str, Any],
                     block_size: int = PAGE_BLOCK,
                     pool_blocks: Optional[int] = None) -> PagedModelState:
    """Per-row capacity rounds ``max_len`` up to whole blocks; the pool
    defaults to full provisioning (batch * blocks_per_row) so a session can
    never exhaust it while every row stays within its own budget —
    admission churn returns retired rows' blocks instead of burning new
    capacity."""
    R = _ceil_div(max_len, block_size)
    P = pool_blocks if pool_blocks is not None else batch * R
    S = R * block_size
    return PagedModelState(
        token_buf=jnp.zeros((batch, S), jnp.int32),
        pos_buf=jnp.zeros((batch, S), jnp.int32),
        mask=jnp.zeros((batch, S), jnp.bool_),
        length=jnp.zeros((batch,), jnp.int32),
        write_ptr=jnp.zeros((batch,), jnp.int32),
        block_table=jnp.full((batch, R), -1, jnp.int32),
        num_blocks=jnp.zeros((batch,), jnp.int32),
        free_stack=jnp.arange(P, dtype=jnp.int32),
        free_top=jnp.asarray(P, jnp.int32),
        layers=layers,
        block_size=int(block_size),
    )


def paged_state_axes(layer_axes: Dict[str, Any],
                     block_size: int) -> PagedModelState:
    """Logical-axis mirror of a PagedModelState (for sharding / free_rows)."""
    return PagedModelState(
        token_buf=("batch", "seq"), pos_buf=("batch", "seq"),
        mask=("batch", "seq"), length=("batch",), write_ptr=("batch",),
        block_table=("batch", None), num_blocks=("batch",),
        free_stack=(None,), free_top=(), layers=layer_axes,
        block_size=block_size)


def _alloc_blocks(state: PagedModelState, n_new_tokens: jnp.ndarray,
                  k_max: int):
    """Pop enough pool blocks for each row to hold ``n_new_tokens`` more
    entries past its cursor.  ``k_max`` is the static per-row bound on new
    blocks (ceil(T/bs) + 1).  Pure index arithmetic: pops only move
    ``free_top``; the stack array itself is untouched.

    Exhaustion (free_top underflow) leaves the rows' new table entries at
    -1 — writes to them are dropped, attention reads masked garbage for the
    affected row only.  The host-side capacity guard
    (``ChainRouter._ensure_capacity``) prevents this by block accounting.
    """
    B, R = state.block_table.shape
    bs = state.block_size
    high = state.write_ptr + n_new_tokens                       # (B,)
    need = jnp.maximum(_ceil_div(high, bs) - state.num_blocks, 0)
    offs = jnp.cumsum(need) - need                              # exclusive
    j = jnp.arange(k_max, dtype=jnp.int32)[None, :]             # (1, k_max)
    take = state.free_top - 1 - (offs[:, None] + j)             # (B, k_max)
    ok = (j < need[:, None]) & (take >= 0)
    pid = jnp.where(
        ok, state.free_stack[jnp.clip(take, 0, state.pool_blocks - 1)], -1)
    cols = jnp.where(ok, state.num_blocks[:, None] + j, R)      # R -> dropped
    bt = state.block_table.at[
        jnp.arange(B)[:, None], cols].set(pid, mode="drop")
    # account only the pops that SUCCEEDED (take >= 0 fails are a prefix
    # loss under exhaustion): inflating num_blocks with phantom blocks
    # would make the host-side block accounting pass while writes to the
    # -1 entries silently drop
    got = jnp.sum(ok, axis=1, dtype=jnp.int32)                  # (B,)
    return dataclasses.replace(
        state, block_table=bt, num_blocks=state.num_blocks + got,
        free_top=state.free_top - jnp.sum(got))


def _push_free_blocks(state: PagedModelState,
                      to_free: jnp.ndarray) -> PagedModelState:
    """Return the table entries flagged in ``to_free`` (B, R) to the pool:
    compact the freed ids, push them on the stack, null the table entries.
    O(B·R) int32 index work — never touches the cache tensors."""
    B, R = state.block_table.shape
    to_free = to_free & (state.block_table >= 0)
    flat_free = to_free.reshape(-1)
    ids = jnp.where(flat_free, state.block_table.reshape(-1), -1)
    order = jnp.argsort(jnp.where(flat_free, 0, 1), stable=True)
    ids_sorted = ids[order]                                    # freed first
    cnt = jnp.sum(flat_free, dtype=jnp.int32)
    pos = jnp.where(jnp.arange(B * R) < cnt,
                    state.free_top + jnp.arange(B * R),
                    state.pool_blocks)                          # OOB -> drop
    return dataclasses.replace(
        state,
        block_table=jnp.where(to_free, -1, state.block_table),
        free_stack=state.free_stack.at[pos].set(ids_sorted, mode="drop"),
        free_top=state.free_top + cnt)


def paged_append_tokens(state: PagedModelState, tokens: jnp.ndarray,
                        valid: jnp.ndarray,
                        spec_depth: Optional[jnp.ndarray] = None):
    """Per-row append: each row writes ONLY its valid entries, contiguously
    at its own cursor.  Rows with nothing valid (retired slots, masked
    no-op rows of a batched step) consume zero capacity — the structural
    fix for the shared-pointer churn blowup.  Returns
    (new_state, q_pos (B, T), slots (B, T) row-local, invalid -> sentinel).
    """
    B, T = tokens.shape
    q_pos, adv = _append_positions(state, valid, spec_depth)
    cnt = jnp.cumsum(valid.astype(jnp.int32), axis=1)           # (B, T)
    n_valid = cnt[:, -1]
    state = _alloc_blocks(state, n_valid,
                          k_max=_ceil_div(T, state.block_size) + 1)
    slots = jnp.where(valid, state.write_ptr[:, None] + cnt - 1, _BIG)
    bidx = jnp.arange(B)[:, None]
    new = dataclasses.replace(
        state,
        token_buf=state.token_buf.at[bidx, slots].set(
            tokens.astype(jnp.int32), mode="drop"),
        pos_buf=state.pos_buf.at[bidx, slots].set(
            q_pos.astype(jnp.int32), mode="drop"),
        mask=state.mask.at[bidx, slots].set(valid, mode="drop"),
        length=state.length + adv,
        write_ptr=state.write_ptr + n_valid,
    )
    return new, q_pos, slots


def physical_slots(state: PagedModelState,
                   slots: jnp.ndarray) -> jnp.ndarray:
    """Row-local slots (B, T) -> flat pool slot ids (block_table lookup).
    Invalid slots (the append sentinel) map OOB so scatter-writes drop."""
    bs = state.block_size
    R = state.blocks_per_row
    rb = slots // bs
    ok = (slots >= 0) & (rb < R)
    pid = jnp.take_along_axis(state.block_table,
                              jnp.clip(rb, 0, R - 1), axis=1)
    return jnp.where(ok & (pid >= 0), pid * bs + slots % bs, _BIG)


def physical_view_index(state: PagedModelState) -> jnp.ndarray:
    """(B, S) flat pool slot id backing each row-local slot.  Unallocated
    blocks clamp to pool slot 0 — their logical mask is False, so attention
    never consumes the garbage."""
    S = state.capacity
    bs = state.block_size
    s = jnp.arange(S, dtype=jnp.int32)
    pid = state.block_table[:, s // bs]                         # (B, S)
    return jnp.maximum(pid, 0) * bs + (s % bs)[None, :]


def tree_region_cols(state: PagedModelState,
                     num_region: int,
                     appended: jnp.ndarray) -> jnp.ndarray:
    """Row-local slots of the speculative tree region — the last
    ``num_region`` entries each appending row wrote (a draft level's region
    spans slots written by the cycle's EARLIER level appends, so it must be
    derived from the post-append cursor, not from this append's slots).
    Rows that appended nothing get the far-future sentinel (overlay drops
    them)."""
    cols = (state.write_ptr[:, None] - num_region
            + jnp.arange(num_region, dtype=jnp.int32)[None, :])
    return jnp.where(jnp.asarray(appended, bool)[:, None], cols, _BIG)


def paged_scatter(cache_flat: jnp.ndarray, new: jnp.ndarray,
                  phys: jnp.ndarray) -> jnp.ndarray:
    """Write (B, T, ...) entries into a (P·bs, ...) pool cache at flat pool
    slots ``phys`` (B, T); sentinel slots are dropped."""
    flat = new.reshape((-1,) + new.shape[2:]).astype(cache_flat.dtype)
    return cache_flat.at[phys.reshape(-1)].set(flat, mode="drop")


def paged_gather(cache_flat: jnp.ndarray,
                 view_idx: jnp.ndarray) -> jnp.ndarray:
    """(P·bs, ...) pool cache -> (B, S, ...) per-row contiguous view."""
    return cache_flat[view_idx]


def paged_scatter_layer(cache: jnp.ndarray, layer, new: jnp.ndarray,
                        phys: jnp.ndarray) -> jnp.ndarray:
    """``paged_scatter`` into layer ``layer`` of a stacked (L, P·bs, Hkv,
    ...) pool — in place when ``cache`` is a scan carry.

    Each (slot, head) entry is its own update, so only the trailing
    head_dim is a contiguous window: a whole (Hkv, hd) row is not, in the
    head-major layout the TPU gives a pool whose Hkv is not a multiple of
    8, and a row-window scatter would make XLA copy the pool into
    row-major order and back around the layer scan.  (Reads stay row
    gathers from the layer's slice, ``paged_gather(cache[layer], ...)``:
    a gather of single (slot, head) entries is too fine for the TPU.)"""
    flat = new.reshape((-1,) + new.shape[2:]).astype(cache.dtype)
    heads = jnp.arange(cache.shape[2])
    return cache.at[layer, phys.reshape(-1)[:, None], heads].set(
        flat, mode="drop")


def paged_write_kv(cache_k, cache_v, k_new, v_new, phys):
    """Paged analogue of ``write_kv``: scatter (B,T,Hkv,hd) into the flat
    (P·bs,Hkv,hd) pool views of a single layer."""
    return paged_scatter(cache_k, k_new, phys), \
        paged_scatter(cache_v, v_new, phys)


def _paged_reclaim(state: PagedModelState) -> PagedModelState:
    """Per-row Eq. 9: rewind each row's OWN cursor past its invalid suffix
    and return now-empty trailing blocks to the pool.  Strictly stronger
    than the contiguous pointer rewind (which only reclaims the suffix
    common to ALL rows)."""
    S = state.capacity
    slot_ids = jnp.arange(S, dtype=jnp.int32)[None, :]
    last = jnp.max(jnp.where(state.mask, slot_ids, -1), axis=1)  # (B,)
    new_wp = jnp.minimum(state.write_ptr, last + 1)
    keep_b = _ceil_div(new_wp, state.block_size)                 # (B,)
    j = jnp.arange(state.blocks_per_row, dtype=jnp.int32)[None, :]
    to_free = (j >= keep_b[:, None]) & (j < state.num_blocks[:, None])
    state = dataclasses.replace(
        state, write_ptr=new_wp,
        num_blocks=jnp.minimum(state.num_blocks, keep_b))
    return _push_free_blocks(state, to_free)


def paged_rollback(state: PagedModelState, r: jnp.ndarray) -> PagedModelState:
    new_len = jnp.maximum(state.length - r.astype(jnp.int32), 0)
    keep = state.pos_buf < new_len[:, None]
    return _paged_reclaim(dataclasses.replace(
        state, mask=state.mask & keep, length=new_len))


def paged_resolve_tree(state: PagedModelState, num_nodes: int,
                       keep: jnp.ndarray, add_len: jnp.ndarray,
                       active: jnp.ndarray) -> PagedModelState:
    """Settle the tree block of each ACTIVE row — its last ``num_nodes``
    row-local slots.  Inactive rows never appended, so their trailing slots
    hold committed data and stay untouched (gated by ``active``)."""
    B, S = state.token_buf.shape
    active = jnp.asarray(active, bool)
    slot_ids = jnp.arange(S, dtype=jnp.int32)[None, :]
    wp = state.write_ptr[:, None]
    start = wp - num_nodes
    in_block = active[:, None] & (slot_ids >= start) & (slot_ids < wp)
    cols = jnp.where(active[:, None],
                     start + jnp.arange(num_nodes, dtype=jnp.int32)[None, :],
                     _BIG)
    keep_full = jnp.zeros((B, S), jnp.bool_).at[
        jnp.arange(B)[:, None], cols].set(keep, mode="drop")
    new = dataclasses.replace(
        state,
        mask=jnp.where(in_block, state.mask & keep_full, state.mask),
        length=state.length + add_len.astype(jnp.int32),
    )
    return _paged_reclaim(new)


def paged_free_rows(state: PagedModelState, rows: jnp.ndarray,
                    layer_axes=None) -> PagedModelState:
    """O(1) retirement: zero the row's logical buffers, rewind its cursor,
    and push ALL its blocks back on the free stack.  No cache-tensor data
    movement — the next occupant simply allocates fresh blocks.  Per-row
    leaves (``layer_axes`` names a ``"batch"`` axis: recurrent states,
    whisper cross-KV) are zeroed for the freed rows."""
    rows = jnp.asarray(rows, bool)
    keep = ~rows
    j = jnp.arange(state.blocks_per_row, dtype=jnp.int32)[None, :]
    to_free = rows[:, None] & (j < state.num_blocks[:, None])
    state = dataclasses.replace(
        state,
        mask=state.mask & keep[:, None],
        length=jnp.where(rows, 0, state.length).astype(jnp.int32),
        write_ptr=jnp.where(rows, 0, state.write_ptr).astype(jnp.int32),
        num_blocks=jnp.where(rows, 0, state.num_blocks).astype(jnp.int32),
    )
    state = _push_free_blocks(state, to_free)
    if layer_axes is None:
        return state
    # pool-shaped attention caches have no batch axis; per-row leaves that
    # do (recurrent states, whisper cross-KV) get the same exact wipe as
    # the contiguous path so a freed row never leaks into its next occupant
    leaves, treedef = jax.tree.flatten(state.layers)
    ax_leaves = treedef.flatten_up_to(layer_axes)

    def wipe(x, ax):
        if not isinstance(ax, tuple) or "batch" not in ax or "seq" in ax:
            return x
        bi = ax.index("batch")
        shape = [1] * x.ndim
        shape[bi] = keep.shape[0]
        return x * keep.reshape(shape).astype(x.dtype)

    new_leaves = [wipe(x, ax) for x, ax in zip(leaves, ax_leaves)]
    return dataclasses.replace(
        state, layers=jax.tree.unflatten(treedef, new_leaves))


def paged_fragmentation(state: PagedModelState) -> jnp.ndarray:
    """Dead fraction of in-use slots (within-row tree holes only — paged
    rows can never leak holes into each other)."""
    S = state.capacity
    slot_ids = jnp.arange(S, dtype=jnp.int32)[None, :]
    in_use = slot_ids < state.write_ptr[:, None]
    used = jnp.maximum(jnp.sum(in_use), 1).astype(jnp.float32)
    dead = jnp.sum((~state.mask) & in_use).astype(jnp.float32)
    return dead / used


def blocks_in_use(state: PagedModelState) -> jnp.ndarray:
    return jnp.asarray(state.pool_blocks, jnp.int32) - state.free_top


def make_paged_attn_cache(num_layers, pool_blocks, block_size, num_kv_heads,
                          head_dim, dtype, quant: bool = False):
    """Pool-shaped attention cache: flat (L, P·bs, Hkv, hd) — rows address
    it through the block table (``physical_slots``/``physical_view_index``);
    the Pallas paged kernel views it as (P, bs, Hkv, hd) blocks."""
    shape = (num_layers, pool_blocks * block_size, num_kv_heads, head_dim)
    if quant:
        sshape = shape[:-1]
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.bfloat16),
                "v_scale": jnp.zeros(sshape, jnp.bfloat16)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_attn_cache_axes(quant: bool = False):
    ax = ("layers", "kv_pool", "kv_heads", "head_dim")
    d = {"k": ax, "v": ax}
    if quant:
        sx = ("layers", "kv_pool", "kv_heads")
        d["k_scale"] = sx
        d["v_scale"] = sx
    return d
