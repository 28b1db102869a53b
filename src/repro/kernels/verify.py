"""Pallas TPU kernels: fused verification row statistics + draft top-k.

Each speculative step verifies B·(W+1) rows of |V|-wide logits (|V| up to
262k).  The naive path reads the logits 3×
(argmax, softmax-normalizer, token gather); this kernel fuses all of it in
ONE pass over vocab tiles:

    per row:  argmax, running max, rescaled sumexp, logit[cand]

The acceptance rule itself (greedy match / rejection sampling on p(cand))
is O(B·W) epilogue work done in plain jnp (see ops.verify_row_stats users).

``topk_pallas`` serves tree-structured speculation: greedy tree drafting
expands every parent node into its top-k children, which is a row-wise
top-k over the same |V|-wide logits.  One pass over vocab tiles keeps a
running (value, index) top-k per row (K is tiny and static), with
argmax-compatible tie-breaking (first maximal index wins) so the k=1
column is bit-identical to linear greedy drafting.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLK_R = 8
BLK_V = 2048
NEG = -1e30


def _verify_kernel(x_ref, cand_ref, am_ref, m_ref, s_ref, cl_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        am_ref[...] = jnp.zeros_like(am_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        cl_ref[...] = jnp.full_like(cl_ref, NEG)

    x = x_ref[...].astype(jnp.float32)                  # (BLK_R, BLK_V)
    base = j * BLK_V
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + base

    # running argmax: strictly-greater keeps the FIRST maximal index,
    # matching jnp.argmax tie-breaking (scan left to right over tiles)
    m_old = m_ref[...]                                   # (BLK_R, 1)
    tile_max = jnp.max(x, axis=-1, keepdims=True)
    tile_arg = jnp.argmax(x, axis=-1).astype(jnp.int32)[:, None] + base
    better = tile_max > m_old
    am_ref[...] = jnp.where(better, tile_arg, am_ref[...])

    m_new = jnp.maximum(m_old, tile_max)
    s_ref[...] = (s_ref[...] * jnp.exp(m_old - m_new)
                  + jnp.sum(jnp.exp(x - m_new), axis=-1, keepdims=True))
    m_ref[...] = m_new

    # candidate logit gather: the candidate column lands in exactly one tile
    hit = col == cand_ref[...]                           # (BLK_R, BLK_V)
    cl_tile = jnp.max(jnp.where(hit, x, NEG), axis=-1, keepdims=True)
    cl_ref[...] = jnp.maximum(cl_ref[...], cl_tile)


def verify_stats_pallas(logits: jnp.ndarray, cand: jnp.ndarray,
                        *, interpret: bool):
    """logits: (R, V) padded; cand: (R,) int32.

    Returns (argmax (R,), max (R,), sumexp (R,), cand_logit (R,))."""
    R, V = logits.shape
    grid = (R // BLK_R, V // BLK_V)
    cand2 = cand.astype(jnp.int32)[:, None]
    am, m, s, cl = pl.pallas_call(
        _verify_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((BLK_R, BLK_V), lambda i, j: (i, j)),
                  pl.BlockSpec((BLK_R, 1), lambda i, j: (i, 0))],
        out_specs=[pl.BlockSpec((BLK_R, 1), lambda i, j: (i, 0)),
                   pl.BlockSpec((BLK_R, 1), lambda i, j: (i, 0)),
                   pl.BlockSpec((BLK_R, 1), lambda i, j: (i, 0)),
                   pl.BlockSpec((BLK_R, 1), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, 1), jnp.int32),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)],
        interpret=interpret,
    )(logits, cand2)
    return am[:, 0], m[:, 0], s[:, 0], cl[:, 0]


# ---------------------------------------------------------------------------
# Row-wise top-k over vocab tiles (greedy tree-draft expansion)
# ---------------------------------------------------------------------------
def _select_topk(vals, idx, K):
    """(R, C) candidates -> (R, K) selected, first-maximal-index ties.
    K and C are static and tiny; K rounds of masked argmax on the VPU."""
    BIG = jnp.int32(2**30)
    out_v, out_i = [], []
    for _ in range(K):
        vmax = jnp.max(vals, axis=-1, keepdims=True)
        # among entries equal to the max, take the smallest index
        imin = jnp.min(jnp.where(vals >= vmax, idx, BIG), axis=-1,
                       keepdims=True)
        out_v.append(vmax)
        out_i.append(imin)
        vals = jnp.where(idx == imin, NEG, vals)   # retire the winner
    return jnp.concatenate(out_v, -1), jnp.concatenate(out_i, -1)


def _topk_kernel(x_ref, v_ref, i_ref, *, K):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        v_ref[...] = jnp.full_like(v_ref, NEG)
        i_ref[...] = jnp.zeros_like(i_ref)

    x = x_ref[...].astype(jnp.float32)                   # (BLK_R, BLK_V)
    base = j * BLK_V
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + base
    tv, ti = _select_topk(x, col, K)                     # tile top-K
    # merge with the running top-K: running entries carry SMALLER indices
    # than anything in this tile, so putting them first preserves the
    # first-maximal-index tie-break through the re-selection
    mv = jnp.concatenate([v_ref[...], tv], axis=-1)      # (BLK_R, 2K)
    mi = jnp.concatenate([i_ref[...], ti], axis=-1)
    nv, ni = _select_topk(mv, mi, K)
    v_ref[...] = nv
    i_ref[...] = ni


def topk_pallas(logits: jnp.ndarray, k: int, *, interpret: bool):
    """logits: (R, V) padded to tile boundaries; returns
    (values (R, k) f32, indices (R, k) i32), argmax tie-breaking."""
    R, V = logits.shape
    grid = (R // BLK_R, V // BLK_V)
    vals, idx = pl.pallas_call(
        functools.partial(_topk_kernel, K=k),
        grid=grid,
        in_specs=[pl.BlockSpec((BLK_R, BLK_V), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((BLK_R, k), lambda i, j: (i, 0)),
                   pl.BlockSpec((BLK_R, k), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, k), jnp.float32),
                   jax.ShapeDtypeStruct((R, k), jnp.int32)],
        interpret=interpret,
    )(logits)
    return vals, idx
