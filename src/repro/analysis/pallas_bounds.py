"""HLO-tier companion: symbolic bounds check of Pallas BlockSpec index maps.

Pallas index maps return *block* indices; an index map that walks past an
operand's shape reads garbage (interpret mode) or faults (TPU).  Nothing
in tracing catches it — the maps are evaluated at run/lower time per grid
step.  This checker drives every registered kernel launcher with small
concrete operands, intercepts ``pallas_call`` to capture
(grid, in_specs, out_specs, out_shape, operands), then evaluates every
index map at every grid point and asserts

    0 <= index_map(idx)[d] * block[d]           (non-negative start)
    index_map(idx)[d] * block[d] + block[d] <= operand.shape[d]

for every dimension of every operand, including the scalar-prefetch block
table of the paged kernel (the map dereferences ``table[b*R + r]``, so
table *values* are exercised too).

Rule ids: ``pallas-oob`` (a map escapes an operand),
``pallas-spec-arity`` (block rank != operand rank).
"""
from __future__ import annotations

import contextlib
import inspect
import itertools
from pathlib import Path
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from .findings import Finding


class _Record:
    def __init__(self, kernel_name: str, grid: Tuple[int, ...],
                 in_specs: Sequence[Any], out_specs: Sequence[Any],
                 out_shapes: Sequence[Any], num_scalar_prefetch: int):
        self.kernel_name = kernel_name
        self.grid = grid
        self.in_specs = list(in_specs)
        self.out_specs = list(out_specs)
        self.out_shapes = list(out_shapes)
        self.num_scalar_prefetch = num_scalar_prefetch
        self.operands: List[Any] = []


def _as_list(x: Any) -> List[Any]:
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


@contextlib.contextmanager
def _capture_pallas_calls(records: List[_Record]):
    """Monkeypatch jax.experimental.pallas.pallas_call to record launch
    geometry and return zero outputs (skips actually running the kernel)."""
    from jax.experimental import pallas as pl

    real = pl.pallas_call

    def fake_pallas_call(kernel, *, grid=None, grid_spec=None, in_specs=None,
                         out_specs=None, out_shape=None, **kwargs):
        num_prefetch = 0
        if grid_spec is not None:
            grid = tuple(getattr(grid_spec, "grid", ()) or ())
            in_specs = _as_list(getattr(grid_spec, "in_specs", None))
            out_specs = _as_list(getattr(grid_spec, "out_specs", None))
            num_prefetch = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
        grid_t = tuple(grid) if grid is not None else ()
        name = getattr(kernel, "__name__", None) or getattr(
            getattr(kernel, "func", None), "__name__", "<kernel>")
        rec = _Record(name, grid_t, _as_list(in_specs), _as_list(out_specs),
                      _as_list(out_shape), num_prefetch)
        records.append(rec)

        def runner(*operands):
            rec.operands = list(operands)
            outs = [np.zeros(tuple(s.shape), dtype=s.dtype)
                    for s in rec.out_shapes]
            if out_shape is not None and not isinstance(out_shape, (list, tuple)):
                return outs[0]
            return outs

        return runner

    pl.pallas_call = fake_pallas_call
    try:
        yield
    finally:
        pl.pallas_call = real


def _check_record(rec: _Record, anchor_path: str, anchor_line: int,
                  launcher: str) -> List[Finding]:
    findings: List[Finding] = []

    def emit(rule: str, message: str) -> None:
        findings.append(Finding(
            rule=rule, path=anchor_path, line=anchor_line,
            message=f"{launcher} [{rec.kernel_name}]: {message}",
            snippet=f"{launcher}:{rec.kernel_name}:{rule}:{message}",
        ))

    prefetch = rec.operands[: rec.num_scalar_prefetch]
    data_ops = rec.operands[rec.num_scalar_prefetch:]
    out_shapes = [tuple(s.shape) for s in rec.out_shapes]

    groups = [("in", rec.in_specs, [np.shape(o) for o in data_ops]),
              ("out", rec.out_specs, out_shapes)]
    for kind, specs, shapes in groups:
        if len(specs) != len(shapes):
            emit("pallas-spec-arity",
                 f"{len(specs)} {kind}_specs for {len(shapes)} operands")
            continue
        for op_i, (spec, shape) in enumerate(zip(specs, shapes)):
            block = tuple(getattr(spec, "block_shape", ()) or ())
            index_map = getattr(spec, "index_map", None)
            if index_map is None or not block:
                continue
            block = tuple(1 if b is None else int(b) for b in block)
            if len(block) != len(shape):
                emit("pallas-spec-arity",
                     f"{kind}[{op_i}] block rank {len(block)} != operand "
                     f"rank {len(shape)} (block {block}, shape {shape})")
                continue
            for idx in itertools.product(*(range(g) for g in rec.grid)):
                try:
                    bidx = index_map(*idx, *prefetch)
                except TypeError as e:
                    emit("pallas-spec-arity",
                         f"{kind}[{op_i}] index map rejects grid point "
                         f"{idx}: {e}")
                    break
                bidx = tuple(int(b) for b in _as_list(bidx))
                if len(bidx) != len(shape):
                    emit("pallas-spec-arity",
                         f"{kind}[{op_i}] index map returns {len(bidx)} "
                         f"indices for rank-{len(shape)} operand")
                    break
                bad_dim = None
                for d, (b, blk, extent) in enumerate(zip(bidx, block, shape)):
                    start = b * blk
                    if start < 0 or start + blk > extent:
                        bad_dim = (d, start, blk, extent)
                        break
                if bad_dim is not None:
                    d, start, blk, extent = bad_dim
                    emit("pallas-oob",
                         f"{kind}[{op_i}] dim {d}: grid point {idx} maps to "
                         f"[{start}, {start + blk}) outside extent {extent}")
                    break  # one finding per spec is enough
    return findings


def _anchor(fn: Callable) -> Tuple[str, int]:
    try:
        path = inspect.getsourcefile(fn) or "<kernels>"
        _, line = inspect.getsourcelines(fn)
    except (OSError, TypeError):
        path, line = "<kernels>", 0
    try:
        path = str(Path(path).resolve().relative_to(Path.cwd()))
    except ValueError:
        pass
    return path, line


def check_launch(launcher: Callable, *args: Any, **kwargs: Any) -> List[Finding]:
    """Run one launcher under capture and bounds-check every pallas_call
    it makes."""
    records: List[_Record] = []
    path, line = _anchor(launcher)
    name = getattr(launcher, "__name__", str(launcher))
    try:
        with _capture_pallas_calls(records):
            launcher(*args, **kwargs)
    except Exception as e:  # pragma: no cover - driver bug, not a finding
        return [Finding(
            rule="pallas-driver-error", path=path, line=line,
            message=f"could not drive {name}: {type(e).__name__}: {e}",
            snippet=f"{name}:driver",
        )]
    findings: List[Finding] = []
    for rec in records:
        findings.extend(_check_record(rec, path, line, name))
    return findings


def default_drives() -> List[Tuple[Callable, tuple, dict]]:
    """The repo's kernel launchers with small concrete shapes that cover
    multi-block grids (including the paged block-table dereference)."""
    from repro.kernels import attention as _attn
    from repro.kernels import dtv as _dtv
    from repro.kernels import verify as _verify

    rng = np.random.default_rng(0)
    B, H, Hkv, D = 2, 4, 2, 128
    S = 2 * _attn.BLK_S
    T = 4
    q1 = rng.standard_normal((B, H, D), dtype=np.float32)
    qT = rng.standard_normal((B, T, H, D), dtype=np.float32)
    k = rng.standard_normal((B, S, Hkv, D), dtype=np.float32)
    v = rng.standard_normal((B, S, Hkv, D), dtype=np.float32)
    mask1 = np.ones((B, S), dtype=bool)
    maskT = np.ones((B, T, S), dtype=bool)

    P, bs, R = 5, 8, 3
    kp = rng.standard_normal((P, bs, Hkv, D), dtype=np.float32)
    vp = rng.standard_normal((P, bs, Hkv, D), dtype=np.float32)
    table = rng.integers(0, P, size=(B, R)).astype(np.int32)
    maskP = np.ones((B, T, R * bs), dtype=bool)

    Rr, V = 2 * _verify.BLK_R, 2 * _verify.BLK_V
    logits = rng.standard_normal((Rr, V), dtype=np.float32)
    logits_b = rng.standard_normal((Rr, V), dtype=np.float32)
    cand = rng.integers(0, V, size=(Rr,)).astype(np.int32)

    # the capture never runs a kernel, so the interpret flag is inert
    kw = {"interpret": True}
    return [
        (_attn.masked_decode_attention_pallas, (q1, k, v, mask1), kw),
        (_attn.masked_tree_attention_pallas, (qT, k, v, maskT), kw),
        (_attn.paged_flash_decode_pallas, (qT, kp, vp, table, maskP), kw),
        (_verify.verify_stats_pallas, (logits, cand), kw),
        (_verify.topk_pallas, (logits, 4), kw),
        (_dtv.softmax_stats, (logits,), kw),
        (_dtv.dtv_pallas, (logits, logits_b), kw),
    ]


def run(drives: Optional[List[Tuple[Callable, tuple, dict]]] = None
        ) -> List[Finding]:
    findings: List[Finding] = []
    for launcher, args, kwargs in (drives if drives is not None
                                   else default_drives()):
        findings.extend(check_launch(launcher, *args, **kwargs))
    return findings
