"""Pallas TPU kernel: Total Variation Distance over a large vocabulary
(paper Eq. 5 — the SimScore probe runs this against up-to-262k vocabs).

Two single-pass kernels over vocab tiles:
  1. ``softmax_stats``: online (max, rescaled-sum) accumulation — one read
     of the logits.
  2. ``dtv_accum``: given both rows' normalizers, accumulates
     0.5·Σ|p − q| tile by tile.

VMEM budget per grid step: 2 tiles of (BLK_R × BLK_V) f32 plus (BLK_R × 1)
accumulators — (8 × 2048) tiles ≈ 128 KiB, far under the ~16 MiB VMEM of a
v5e core, and the 2048 lane dim is 128-aligned for the VPU.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLK_R = 8          # rows per tile (sublane-aligned)
BLK_V = 2048       # vocab lanes per tile (128-aligned)
NEG = -1e30


def dtv_probs(p: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """0.5 · Σ_v |p − q| over the last axis (paper Eq. 5), probability
    domain.  The single DTV definition shared by every on-device consumer:
    the per-op verify math AND the fused cycle program import it from here,
    so the similarity signal is identical whichever path produced it.  The
    Pallas kernels below are the logits-domain variant for probe-time
    comparisons over vocabularies too large to materialize as probs."""
    return 0.5 * jnp.sum(jnp.abs(p - q), axis=-1)


# ---------------------------------------------------------------------------
# Kernel 1: online softmax statistics
# ---------------------------------------------------------------------------
def _stats_kernel(x_ref, m_ref, s_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[...].astype(jnp.float32)          # (BLK_R, BLK_V)
    m_old = m_ref[...]                          # (BLK_R, 1)
    m_tile = jnp.max(x, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_old, m_tile)
    s_tile = jnp.sum(jnp.exp(x - m_new), axis=-1, keepdims=True)
    s_ref[...] = s_ref[...] * jnp.exp(m_old - m_new) + s_tile
    m_ref[...] = m_new


def softmax_stats(logits: jnp.ndarray, *, interpret: bool):
    """(R, V) -> (max (R, 1), sumexp (R, 1)); V, R padded by caller."""
    R, V = logits.shape
    grid = (R // BLK_R, V // BLK_V)
    return pl.pallas_call(
        _stats_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((BLK_R, BLK_V), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((BLK_R, 1), lambda i, j: (i, 0)),
                   pl.BlockSpec((BLK_R, 1), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)],
        interpret=interpret,
    )(logits)


# ---------------------------------------------------------------------------
# Kernel 2: |p - q| accumulation given normalizers
# ---------------------------------------------------------------------------
def _dtv_kernel(a_ref, b_ref, ma_ref, sa_ref, mb_ref, sb_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    p = jnp.exp(a - ma_ref[...]) / sa_ref[...]
    q = jnp.exp(b - mb_ref[...]) / sb_ref[...]
    out_ref[...] += 0.5 * jnp.sum(jnp.abs(p - q), axis=-1, keepdims=True)


def dtv_pallas(a_logits: jnp.ndarray, b_logits: jnp.ndarray,
               *, interpret: bool) -> jnp.ndarray:
    """(R, V) x 2 -> (R,) TV distance. Caller pads R to BLK_R and V to
    BLK_V multiples (padding lanes use NEG logits -> zero probability)."""
    R, V = a_logits.shape
    ma, sa = softmax_stats(a_logits, interpret=interpret)
    mb, sb = softmax_stats(b_logits, interpret=interpret)
    grid = (R // BLK_R, V // BLK_V)
    row = pl.BlockSpec((BLK_R, 1), lambda i, j: (i, 0))
    out = pl.pallas_call(
        _dtv_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((BLK_R, BLK_V), lambda i, j: (i, j)),
                  pl.BlockSpec((BLK_R, BLK_V), lambda i, j: (i, j)),
                  row, row, row, row],
        out_specs=pl.BlockSpec((BLK_R, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.float32),
        interpret=interpret,
    )(a_logits, b_logits, ma, sa, mb, sb)
    return out[:, 0]
