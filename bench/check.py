"""The comparison that decides ``correct``.

Every request of the window is replayed through the configuration's plain
float32 reference, teacher-forced over its prompt and the tokens the
serving path committed.  At each committed position the *gap* is the
reference's largest logit minus the reference's logit of the committed
token: 0 where the served token is the reference's argmax, small where
bf16 rounding picked a near tie, large where a token is wrong.  The number
compared is the widest gap over the window.

With ``quant`` the same replay also runs the reference in that lower
precision and reads, at each position, the gap of the token that the lower
precision puts first: the control.  ``judge(..., quant=...)`` holds the
control to the same limits, and its verdict has to read not correct.
"""
from __future__ import annotations

from functools import lru_cache
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 8          # sequences per reference block
CHUNK = 512       # positions per output-head chunk
SEQ_BUCKET = 128  # sequence lengths padded to a multiple of this


@lru_cache(maxsize=None)
def _gap_fn(ref, tied: bool, quant: Optional[str]):
    def f(flat_h, flat_q, idx, served, w_out):
        lg = ref.logits(w_out, flat_h[idx], tied)
        mx = lg.max(-1)
        gap = mx - jnp.take_along_axis(lg, served[:, None], 1)[:, 0]
        if quant is None:
            return gap, gap
        pick = jnp.argmax(ref.logits(w_out, flat_q[idx], tied, quant), -1)
        return gap, mx - jnp.take_along_axis(lg, pick[:, None], 1)[:, 0]
    return jax.jit(f)


def replay_gaps(ref, w: Dict, hf: Dict, prompts: Sequence[np.ndarray],
                outputs: Sequence[np.ndarray],
                quant: Optional[str] = None):
    """Per request: (served gaps, control gaps) over its output positions.
    Control gaps equal served gaps when ``quant`` is None."""
    tied = bool(hf["tie_word_embeddings"])
    w_out = w["embed"] if tied else w["head"]
    fn = _gap_fn(ref, tied, quant)
    served_out: List[np.ndarray] = []
    ctrl_out: List[np.ndarray] = []
    for b0 in range(0, len(prompts), ROWS):
        ps, os_ = prompts[b0:b0 + ROWS], outputs[b0:b0 + ROWS]
        L = max(len(p) + len(o) for p, o in zip(ps, os_))
        S = -(-L // SEQ_BUCKET) * SEQ_BUCKET
        toks = np.zeros((ROWS, S), np.int32)
        idx, served, owner = [], [], []
        for r, (p, o) in enumerate(zip(ps, os_)):
            seq = np.concatenate([p, o]).astype(np.int32)
            toks[r, :len(seq)] = seq
            # the logits at position t predict token t + 1
            idx.extend(r * S + len(p) - 1 + np.arange(len(o)))
            served.extend(o)
            owner.extend([r] * len(o))
        h = ref.hidden(w, hf, jnp.asarray(toks))
        flat_h = h.reshape(-1, h.shape[-1])
        flat_q = flat_h
        if quant is not None:
            hq = ref.hidden(w, hf, jnp.asarray(toks), quant=quant)
            flat_q = hq.reshape(-1, hq.shape[-1])
        g_all, c_all = [], []
        for c0 in range(0, len(idx), CHUNK):
            n = min(CHUNK, len(idx) - c0)
            ci = np.zeros(CHUNK, np.int32)
            cs = np.zeros(CHUNK, np.int32)
            ci[:n] = idx[c0:c0 + n]
            cs[:n] = served[c0:c0 + n]
            g, c = fn(flat_h, flat_q, jnp.asarray(ci), jnp.asarray(cs), w_out)
            g_all.append(np.asarray(g)[:n])
            c_all.append(np.asarray(c)[:n])
        del h, flat_h, flat_q
        g_cat = np.concatenate(g_all) if g_all else np.zeros(0)
        c_cat = np.concatenate(c_all) if c_all else np.zeros(0)
        owner_a = np.asarray(owner)
        for r in range(len(ps)):
            served_out.append(g_cat[owner_a == r])
            ctrl_out.append(c_cat[owner_a == r])
    return served_out, ctrl_out


def judge(ref, w: Dict, hf: Dict, requests, limits: Dict,
          quant: Optional[str] = None) -> Dict:
    """Compare every request of the window; returns the numbers compared
    (each with its limit), per-class widest gaps, and the verdict.  With
    ``quant`` the gaps judged are the control's: the tokens that the
    reference computed in that precision puts first."""
    done = [r for r in requests if r.output_tokens is not None]
    short = sum(1 for r in requests
                if r.output_tokens is None
                or len(r.output_tokens) != r.max_new_tokens)
    served, ctrl = replay_gaps(ref, w, hf, [r.prompt for r in done],
                               [np.asarray(r.output_tokens) for r in done],
                               quant=quant)
    gaps = served if quant is None else ctrl
    by_class: Dict[str, float] = {}
    for r, g in zip(done, gaps):
        if g.size:
            by_class[r.dataset] = max(by_class.get(r.dataset, 0.0),
                                      float(g.max()))
    worst = max((float(g.max()) for g in gaps if g.size), default=0.0)
    compared = {
        "max_gap": {"value": worst, "limit": limits["max_gap"]},
        "short_requests": {"value": short, "limit": 0},
    }
    ok = all(v["value"] <= v["limit"] for v in compared.values())
    return dict(correct=ok, compared=compared, by_class=by_class,
                tokens=int(sum(g.size for g in gaps)), requests=len(done))
