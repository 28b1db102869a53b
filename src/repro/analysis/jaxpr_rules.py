"""Tier-2 jaxpr rules: trace the registered device-program entry points
and inspect the traced programs themselves.

Entry points:
  * the fused linear cycle body (``Executor._build_fused_linear`` via the
    real serving path — see ``harness.capture_fused_linear``), and
  * every public ``kernels.ops`` wrapper.

Checks:
  * ``jaxpr-callback`` — no host-callback / infeed / outfeed primitives
    anywhere in the traced program (a stray ``jax.debug.print`` or
    ``io_callback`` inside the fused cycle would reintroduce a host hop
    per cycle and silently break PR 5's contract);
  * ``jaxpr-donation`` — the fused program actually lowers with input-
    output aliasing for the donated argnums (states, seq, seq_len,
    active), and every donated leaf has a same-shape/dtype output to
    alias into.  Donation that cannot alias silently falls back to a
    copy: the cycle still runs, 2x the memory.
"""
from __future__ import annotations

from collections import Counter
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax

from . import harness
from .findings import Finding


_EXECUTOR_PATH = "src/repro/core/executor.py"
_OPS_PATH = "src/repro/kernels/ops.py"


def iter_all_eqns(jaxpr) -> List[Any]:
    """Flatten a (closed) jaxpr and every sub-jaxpr reachable through eqn
    params (pjit bodies, scan/while/cond branches, pallas kernels)."""
    core_jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    out = []
    stack = [core_jaxpr]
    seen = set()
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        for eqn in getattr(j, "eqns", ()):
            out.append(eqn)
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    stack.append(sub)
    return out


def _sub_jaxprs(v: Any) -> List[Any]:
    subs = []
    if hasattr(v, "eqns"):
        subs.append(v)
    elif hasattr(v, "jaxpr"):
        subs.append(v.jaxpr)
    elif isinstance(v, (list, tuple)):
        for item in v:
            subs.extend(_sub_jaxprs(item))
    return subs


def is_host_hop(eqn) -> bool:
    """True when an equation hands control to the host.  Keyed on what the
    equation does, not on its name: it carries an effect other than a
    read/write of one of its own operands (debug print/callback,
    io_callback, in/outfeed — kernel-body ref accesses carry an
    ``input_index``), or it calls back into Python (``pure_callback``
    carries no effect)."""
    if callable(eqn.params.get("callback")):
        return True
    return any(not hasattr(e, "input_index") for e in eqn.effects)


def forbidden_primitives(jaxpr) -> List[str]:
    return [eqn.primitive.name for eqn in iter_all_eqns(jaxpr)
            if is_host_hop(eqn)]


def check_entry_point(name: str, fn: Callable, args: Sequence[Any],
                      anchor_path: str) -> List[Finding]:
    findings: List[Finding] = []
    try:
        jaxpr = jax.make_jaxpr(fn)(*args)
    except Exception as e:
        return [Finding(
            rule="jaxpr-trace-error", path=anchor_path, line=0,
            message=f"could not trace {name}: {type(e).__name__}: {e}",
            snippet=f"{name}:trace",
        )]
    for prim in sorted(set(forbidden_primitives(jaxpr))):
        findings.append(Finding(
            rule="jaxpr-callback", path=anchor_path, line=0,
            message=(f"{name}: traced program contains host primitive "
                     f"'{prim}' — a host hop inside the device program"),
            snippet=f"{name}:{prim}",
        ))
    return findings


def _leaf_avals(tree: Any) -> List[Tuple[Tuple[int, ...], Any]]:
    return [(tuple(leaf.shape), jax.numpy.dtype(leaf.dtype))
            for leaf in jax.tree_util.tree_leaves(tree)]


def check_fused_donation(cap: harness.FusedCapture) -> List[Finding]:
    import contextlib

    findings: List[Finding] = []
    placement = getattr(cap, "placement", None)
    mctx = (placement.mesh_context() if placement is not None
            else contextlib.nullcontext())
    jitted = jax.jit(cap.body, donate_argnums=harness.DONATE_ARGNUMS)
    try:
        with mctx:
            text = jitted.lower(*cap.arg_sds).as_text()
    except Exception as e:
        return [Finding(
            rule="jaxpr-trace-error", path=_EXECUTOR_PATH, line=0,
            message=f"could not lower fused body: {type(e).__name__}: {e}",
            snippet="fused_linear:lower",
        )]
    if "tf.aliasing_output" not in text and "jax.buffer_donor" not in text:
        findings.append(Finding(
            rule="jaxpr-donation", path=_EXECUTOR_PATH, line=0,
            message=("fused linear program lowered WITHOUT input-output "
                     "aliasing despite donate_argnums — donated session "
                     "buffers are being copied, not reused"),
            snippet="fused_linear:no-aliasing",
        ))

    out_sds = jax.eval_shape(cap.body, *cap.arg_sds)
    out_avals = Counter(_leaf_avals(out_sds))
    for argnum in harness.DONATE_ARGNUMS:
        for shape, dtype in _leaf_avals(cap.arg_sds[argnum]):
            if out_avals[(shape, dtype)] > 0:
                out_avals[(shape, dtype)] -= 1
            else:
                findings.append(Finding(
                    rule="jaxpr-donation", path=_EXECUTOR_PATH, line=0,
                    message=(f"donated arg {argnum} leaf {dtype}{shape} "
                             "has no matching output to alias — that "
                             "buffer is freed, not reused (donation is a "
                             "no-op for it)"),
                    snippet=f"fused_linear:donate:{argnum}:{dtype}{shape}",
                ))
    return findings


def run(cap: Optional[harness.FusedCapture] = None) -> List[Finding]:
    findings: List[Finding] = []
    if cap is None:
        try:
            cap = harness.capture_fused_linear()
        except Exception as e:
            return [Finding(
                rule="jaxpr-trace-error", path=_EXECUTOR_PATH, line=0,
                message=("could not capture the fused linear cycle: "
                         f"{type(e).__name__}: {e}"),
                snippet="fused_linear:capture",
            )]
    import contextlib
    placement = getattr(cap, "placement", None)
    mctx = (placement.mesh_context() if placement is not None
            else contextlib.nullcontext())
    with mctx:
        findings.extend(check_entry_point(
            "fused_linear_cycle", cap.body, cap.arg_sds, _EXECUTOR_PATH))
    findings.extend(check_fused_donation(cap))
    for name, fn, args in harness.kernel_op_entry_points():
        findings.extend(check_entry_point(name, fn, args, _OPS_PATH))
    return findings
