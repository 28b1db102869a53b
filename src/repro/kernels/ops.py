"""Public jit'd wrappers for the Pallas kernels.

Handle padding to tile boundaries, dtype plumbing, and backend selection:
on TPU the kernels run compiled; on the CPU backend (tests) they run in
interpret mode (same kernel body, Python-executed) — correctness is
validated against the ref.py oracles either way.  The choice is made when
a wrapper is traced, never at import, and any other backend is refused:
a kernel silently interpreted on an accelerator would hide the device.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from . import attention as _attn
from . import dtv as _dtv
from . import verify as _verify
from . import ref
from ..sharding import context_mesh


def _interpret() -> bool:
    """Interpret mode for the backend this trace runs on: compiled on TPU,
    interpreted on CPU, refused anywhere else."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on TPU or interpreted on CPU; the "
        f"{backend!r} backend has neither (pass use_kernel=False for the "
        "jnp reference path)")


def _force_replicated(*arrays):
    """Pallas kernels are OPAQUE to the GSPMD partitioner: given sharded
    operands it can run the kernel per-shard (partial softmax over a split
    head/seq dim — numerically wrong), not insert collectives.  Under an
    active multi-device mesh (the mesh-sharded serving path traces every
    program inside ``placement.mesh_context()`` — see Executor), constrain all
    operands to replicated so the kernel always sees full arrays; XLA then
    places the gather collectives OUTSIDE the kernel.  With no mesh
    context (the trivial placement) this is a no-op and the lowering is
    byte-identical to the unmeshed path."""
    mesh = context_mesh()
    if mesh is None:
        return arrays if len(arrays) > 1 else arrays[0]
    rep = NamedSharding(mesh, PartitionSpec())
    out = tuple(jax.lax.with_sharding_constraint(a, rep) for a in arrays)
    return out if len(out) > 1 else out[0]


def _pad_to(x, mult, axis, value):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("use_kernel",))
def dtv(a_logits: jnp.ndarray, b_logits: jnp.ndarray,
        use_kernel: bool = True) -> jnp.ndarray:
    """(B, V) x2 -> (B,) total variation distance (paper Eq. 5)."""
    if not use_kernel:
        return ref.dtv_ref(a_logits, b_logits)
    B, V = a_logits.shape
    a = _pad_to(_pad_to(a_logits, _dtv.BLK_V, 1, _dtv.NEG),
                _dtv.BLK_R, 0, _dtv.NEG)
    b = _pad_to(_pad_to(b_logits, _dtv.BLK_V, 1, _dtv.NEG),
                _dtv.BLK_R, 0, _dtv.NEG)
    a, b = _force_replicated(a, b)
    return _dtv.dtv_pallas(a, b, interpret=_interpret())[:B]


# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("use_kernel",))
def verify_row_stats(logits: jnp.ndarray, cand: jnp.ndarray,
                     use_kernel: bool = True):
    """logits: (R, V); cand: (R,) -> (argmax, max, sumexp, cand_logit)."""
    if not use_kernel:
        return ref.verify_stats_ref(logits, cand)
    R, V = logits.shape
    x = _pad_to(_pad_to(logits, _verify.BLK_V, 1, _verify.NEG),
                _verify.BLK_R, 0, _verify.NEG)
    c = _pad_to(cand.astype(jnp.int32), _verify.BLK_R, 0, 0)
    x, c = _force_replicated(x, c)
    am, m, s, cl = _verify.verify_stats_pallas(x, c, interpret=_interpret())
    return am[:R], m[:R], s[:R], cl[:R]


@partial(jax.jit, static_argnames=("k", "use_kernel"))
def draft_topk(logits: jnp.ndarray, k: int, use_kernel: bool = True):
    """logits: (R, V) -> (values (R, k), indices (R, k)).

    Greedy tree-draft expansion: every parent node's top-k children in one
    fused pass over vocab tiles.  Tie-breaking matches jnp.argmax (first
    maximal index), so column 0 is bit-identical to linear greedy drafting.
    """
    if not use_kernel:
        return ref.topk_ref(logits, k)
    R, V = logits.shape
    x = _pad_to(_pad_to(logits, _verify.BLK_V, 1, _verify.NEG),
                _verify.BLK_R, 0, _verify.NEG)
    x = _force_replicated(x)
    vals, idx = _verify.topk_pallas(x, k, interpret=_interpret())
    return vals[:R], idx[:R]


def greedy_accept_from_stats(cand, am, m, s, cl):
    """O(R) epilogue: greedy accept mask + p(cand) from the fused stats."""
    match = am == cand.astype(jnp.int32)
    p_cand = jnp.exp(cl - m) / jnp.maximum(s, 1e-30)
    return match, p_cand


# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("use_kernel",))
def masked_decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            mask: jnp.ndarray,
                            use_kernel: bool = True) -> jnp.ndarray:
    """q: (B, H, D); k, v: (B, S, Hkv, D); mask: (B, S) -> (B, H, D)."""
    if not use_kernel:
        return ref.masked_decode_attention_ref(q, k, v, mask)
    D = q.shape[-1]
    scale = 1.0 / (D ** 0.5)     # scale by TRUE head dim before padding
    qp = _pad_to(q, 128, 2, 0.0)
    kp = _pad_to(k, 128, 3, 0.0)
    vp = _pad_to(v, 128, 3, 0.0)
    kp = _pad_to(kp, _attn.BLK_S, 1, 0.0)
    vp = _pad_to(vp, _attn.BLK_S, 1, 0.0)
    mp = _pad_to(mask, _attn.BLK_S, 1, False)
    qp, kp, vp, mp = _force_replicated(qp, kp, vp, mp)
    out = _attn.masked_decode_attention_pallas(
        qp, kp, vp, mp, scale=scale, interpret=_interpret())
    return out[:, :, :D]


# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("use_kernel",))
def masked_tree_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          mask: jnp.ndarray,
                          use_kernel: bool = True) -> jnp.ndarray:
    """Tree-block decode attention: q: (B, T, H, D); k, v: (B, S, Hkv, D);
    mask: (B, T, S) per-query rows (ancestor-or-self over the speculative
    tree slots, validity-causal elsewhere) -> (B, T, H, D).

    The linear decode step is the T=1 special case (same mask path)."""
    if not use_kernel:
        return ref.masked_tree_attention_ref(q, k, v, mask)
    D = q.shape[-1]
    scale = 1.0 / (D ** 0.5)     # scale by TRUE head dim before padding
    qp = _pad_to(q, 128, 3, 0.0)
    kp = _pad_to(k, 128, 3, 0.0)
    vp = _pad_to(v, 128, 3, 0.0)
    kp = _pad_to(kp, _attn.BLK_S, 1, 0.0)
    vp = _pad_to(vp, _attn.BLK_S, 1, 0.0)
    mp = _pad_to(mask, _attn.BLK_S, 2, False)
    qp, kp, vp, mp = _force_replicated(qp, kp, vp, mp)
    out = _attn.masked_tree_attention_pallas(
        qp, kp, vp, mp, scale=scale, interpret=_interpret())
    return out[:, :, :, :D]


# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("block_size", "use_kernel"))
def paged_decode_attention(q: jnp.ndarray, k_flat: jnp.ndarray,
                           v_flat: jnp.ndarray, block_table: jnp.ndarray,
                           mask: jnp.ndarray, block_size: int,
                           use_kernel: bool = True) -> jnp.ndarray:
    """Paged flash-decode over a block pool (the paged-KV serving path).

    q: (B, T, H, D); k_flat, v_flat: (P·bs, Hkv, D) — the flat pool layout
    ``PagedModelState`` stores per layer; block_table: (B, R) int32 with
    -1 marking unallocated row blocks; mask: (B, T, S) per-query validity
    rows, S = R·bs.  T=1 is paged single-token decode; T>1 with
    ancestor-mask rows is the paged tree-block case — one kernel subsumes
    both.  Unallocated table entries are clamped to pool block 0; their
    mask columns are False so they never reach the online softmax.
    """
    if not use_kernel:
        P = k_flat.shape[0] // block_size
        kp = k_flat.reshape(P, block_size, *k_flat.shape[1:])
        vp = v_flat.reshape(P, block_size, *v_flat.shape[1:])
        return ref.paged_attention_ref(q, kp, vp, block_table, mask)
    D = q.shape[-1]
    scale = 1.0 / (D ** 0.5)     # scale by TRUE head dim before padding
    qp = _pad_to(q, 128, 3, 0.0)
    kf = _pad_to(k_flat, 128, 2, 0.0)
    vf = _pad_to(v_flat, 128, 2, 0.0)
    P = kf.shape[0] // block_size
    kp = kf.reshape(P, block_size, *kf.shape[1:])
    vp = vf.reshape(P, block_size, *vf.shape[1:])
    tbl = jnp.clip(block_table, 0, P - 1)
    qp, kp, vp, tbl, mask = _force_replicated(qp, kp, vp, tbl, mask)
    out = _attn.paged_flash_decode_pallas(
        qp, kp, vp, tbl, mask, scale=scale, interpret=_interpret())
    return out[:, :, :, :D]
