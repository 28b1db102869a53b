"""Pallas TPU kernels: masked decode attention (flash-decode) — single
token and tree-block variants.

The paper's cache_mask (Eq. 8) is consumed INSIDE the kernel: invalid KV
slots never contribute to the online softmax, so logical rollback costs
nothing at attention time.  GQA: the g query heads sharing one KV head are
processed together as the (g × BLK_S) MXU tile.

Tree-structured speculation extends the same mask path: a cycle's T tree
nodes decode as one query block with a PER-QUERY mask row (B, T, S) —
ancestor-or-self over the tree slots (siblings share a RoPE position but
must not attend each other), plain validity-causal everywhere else (see
``layers.overlay_block_mask`` for the layout).  The single-token decode
kernel is exactly the T=1 special case.

Grid: (B, Hkv, S/BLK_S) — the minor S axis is sequential on TPU, so the
(m, l, acc) accumulators live in revisited output blocks; the wrapper
normalizes acc/l at the end (no in-kernel finalization step needed).

Paged variant (``paged_flash_decode_pallas``): the KV cache is a POOL of
fixed-size blocks (P, bs, Hkv, D) addressed through a per-row block table.
The block table is a *scalar-prefetch* argument: the grid's minor axis
walks each row's table entries and the K/V BlockSpec index maps read
``table[b, r]`` to DMA exactly that pool block into VMEM — on the TPU
path the gather IS the pipeline, no materialized per-row view.  (The
CPU/jnp forward in models/ materializes the gathered view and runs the
jnp attention instead — the repo-wide staging convention; this kernel is
held to the same oracle, ``ref.paged_attention_ref``, until the TPU
serving path wires it in.)  The kernel body is byte-identical to the tree
kernel's online softmax (T queries, per-query mask rows), so it subsumes
both the single-token (T=1) and tree-block decode cases.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLK_S = 512
NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, mask_ref, acc_ref, m_ref, l_ref,
                  *, scale):
    """One KV block of the online softmax for one (row, kv-head) pair.

    Refs (leading grid dims squeezed): q (R, D) with R = T·g query rows
    (tree node major, GQA group member minor); k, v (BLK, D); mask (R, BLK)
    int32; acc (R, D); m, l (R, 128) lane-replicated running max / sum."""
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32) * scale            # (R, D)
    k = k_ref[...].astype(jnp.float32)                    # (BLK, D)
    v = v_ref[...].astype(jnp.float32)                    # (BLK, D)
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = jnp.where(mask_ref[...] != 0, scores, NEG)   # (R, BLK)

    m_old = m_ref[...][:, :1]                             # (R, 1)
    m_new = jnp.maximum(m_old, jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.where(scores > NEG * 0.5, jnp.exp(scores - m_new), 0.0)
    corr = jnp.where(m_old > NEG * 0.5, jnp.exp(m_old - m_new), 0.0)

    l_new = l_ref[...][:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)


def _head_major(q, mask, Hkv):
    """(B, T, H, D) queries and (B, T, S) mask rows -> (B, Hkv, T·g, D)
    query tiles and the matching (B, T·g, S) int32 mask rows."""
    B, T, H, D = q.shape
    g = H // Hkv
    qh = q.reshape(B, T, Hkv, g, D).transpose(0, 2, 1, 3, 4)
    mask_rows = jnp.repeat(mask.astype(jnp.int32), g, axis=1)
    return qh.reshape(B, Hkv, T * g, D), mask_rows


def _normalize(acc, l, B, T, H, dtype):
    """(B, Hkv, T·g, D) accumulators -> (B, T, H, D) attention output."""
    Hkv, D = acc.shape[1], acc.shape[-1]
    l1 = l[..., :1]
    out = jnp.where(l1 > 0, acc / jnp.maximum(l1, 1e-30), 0.0)
    out = out.reshape(B, Hkv, T, H // Hkv, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, H, D).astype(dtype)


def _acc_shapes(B, Hkv, R, D):
    return [jax.ShapeDtypeStruct((B, Hkv, R, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, R, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, R, 128), jnp.float32)]


def masked_decode_attention_pallas(q: jnp.ndarray, k: jnp.ndarray,
                                   v: jnp.ndarray, mask: jnp.ndarray,
                                   *, interpret: bool,
                                   scale: float | None = None
                                   ) -> jnp.ndarray:
    """q: (B, H, D); k, v: (B, S, Hkv, D); mask: (B, S) -> (B, H, D).

    The T=1 case of ``masked_tree_attention_pallas``."""
    out = masked_tree_attention_pallas(q[:, None], k, v, mask[:, None],
                                       interpret=interpret, scale=scale)
    return out[:, 0]


# ---------------------------------------------------------------------------
# Tree-block decode attention: T queries, per-query ancestor mask
# ---------------------------------------------------------------------------
def masked_tree_attention_pallas(q: jnp.ndarray, k: jnp.ndarray,
                                 v: jnp.ndarray, mask: jnp.ndarray,
                                 *, interpret: bool,
                                 scale: float | None = None
                                 ) -> jnp.ndarray:
    """q: (B, T, H, D); k, v: (B, S, Hkv, D); mask: (B, T, S) per-query
    (tree-ancestor rows over the speculative block, validity-causal rows
    elsewhere).  S must be a BLK_S multiple and D 128-aligned (ops.py
    pads).  T=1 with a (B, 1, S) mask is single-token decode.

    K/V are transposed head-major, (B, Hkv, S, D), so every block is a
    (BLK_S, D) tile: the TPU lowering needs the last two block dims
    tile-aligned or whole."""
    B, T, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    qh, mask_rows = _head_major(q, mask, Hkv)
    R = qh.shape[2]
    kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    acc_spec = pl.BlockSpec((None, None, R, D), lambda b, h, s: (b, h, 0, 0))
    ml_spec = pl.BlockSpec((None, None, R, 128),
                           lambda b, h, s: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((None, None, BLK_S, D),
                           lambda b, h, s: (b, h, s, 0))

    acc, m, l = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale),
        grid=(B, Hkv, S // BLK_S),
        in_specs=[
            acc_spec,
            kv_spec,
            kv_spec,
            pl.BlockSpec((None, R, BLK_S), lambda b, h, s: (b, 0, s)),
        ],
        out_specs=[acc_spec, ml_spec, ml_spec],
        out_shape=_acc_shapes(B, Hkv, R, D),
        interpret=interpret,
    )(qh, kh, vh, mask_rows)
    return _normalize(acc, l, B, T, H, q.dtype)


# ---------------------------------------------------------------------------
# Paged flash-decode: gather K/V block-by-block through the block table
# ---------------------------------------------------------------------------
def _paged_attn_kernel(table_ref, q_ref, k_ref, v_ref, mask_ref,
                       acc_ref, m_ref, l_ref, *, scale):
    # table_ref is consumed by the BlockSpec index maps (scalar prefetch);
    # the body is exactly the tree kernel's online softmax over one block.
    _flash_kernel(q_ref, k_ref, v_ref, mask_ref, acc_ref, m_ref, l_ref,
                  scale=scale)


def paged_flash_decode_pallas(q: jnp.ndarray, k_pool: jnp.ndarray,
                              v_pool: jnp.ndarray,
                              block_table: jnp.ndarray,
                              mask: jnp.ndarray,
                              *, interpret: bool,
                              scale: float | None = None) -> jnp.ndarray:
    """q: (B, T, H, D); k_pool, v_pool: (P, bs, Hkv, D) block pools;
    block_table: (B, R) int32 pool block per row-local block (entries must
    be pre-clamped to [0, P) — unallocated blocks are mask-False anyway);
    mask: (B, T, S) per-query validity rows with S = R * bs.

    Grid (B, Hkv, R): the minor axis walks the row's block table; the K/V
    index maps dereference ``table[b, r]`` so each pool block is DMA'd
    exactly once per (row, kv-head).  T=1 gives paged single-token decode;
    T>1 with ancestor-mask rows gives paged tree-block decode.  bs must be
    a multiple of 8 (sublane) and D 128-aligned (ops.py pads D; bs is a
    build-time choice).  The pools are transposed head-major and the mask
    split per block, so every block is whole in its last two dims.
    """
    B, T, H, D = q.shape
    P, bs, Hkv, _ = k_pool.shape
    Rb = block_table.shape[1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    qh, mask_rows = _head_major(q, mask, Hkv)
    R = qh.shape[2]
    mask_blocks = mask_rows.reshape(B, R, Rb, bs).transpose(0, 2, 1, 3)
    kh, vh = k_pool.transpose(0, 2, 1, 3), v_pool.transpose(0, 2, 1, 3)
    tbl = block_table.reshape(-1).astype(jnp.int32)       # (B*Rb,)
    acc_spec = pl.BlockSpec((None, None, R, D),
                            lambda b, h, r, t: (b, h, 0, 0))
    ml_spec = pl.BlockSpec((None, None, R, 128),
                           lambda b, h, r, t: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((None, None, bs, D),
                           lambda b, h, r, t: (t[b * Rb + r], h, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, Rb),
        in_specs=[
            acc_spec,
            kv_spec,
            kv_spec,
            pl.BlockSpec((None, None, R, bs),
                         lambda b, h, r, t: (b, r, 0, 0)),
        ],
        out_specs=[acc_spec, ml_spec, ml_spec],
    )
    acc, m, l = pl.pallas_call(
        functools.partial(_paged_attn_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=_acc_shapes(B, Hkv, R, D),
        interpret=interpret,
    )(tbl, qh, kh, vh, mask_blocks)
    return _normalize(acc, l, B, T, H, q.dtype)
