"""LanguageModel facade: one uniform interface over all architecture
families, consumed by the SpecRouter core, the trainer, the serving engine,
and the dry-run launcher.

    lm = LanguageModel(cfg)
    params, axes = lm.init(key)
    state, state_axes = lm.make_state(batch, max_len, rollback_span=...)
    logits, state = lm.prefill(params, state, tokens, **extras)
    logits, state = lm.decode(params, state, tokens, valid=..., **extras)
    state = lm.rollback(state, r)
    logits[, aux] = lm.train_logits(params, tokens, **extras)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from . import (frontends, hybrid, kv_cache as kvc, moe, qwen3_next, ssm,
               transformer as tf)
from .config import ModelConfig

_FAMILY = {
    "dense": tf, "audio": tf, "vlm": tf,
    "moe": moe, "ssm": ssm, "hybrid": hybrid, "qwen3_next": qwen3_next,
}


class LanguageModel:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.mod = _FAMILY[cfg.arch_type]

    # ---- params ------------------------------------------------------
    def init(self, key):
        return self.mod.init(key, self.cfg)

    def param_axes(self):
        return self.mod.param_axes(self.cfg)

    # ---- abstract (no-allocation) views for the dry-run ---------------
    def abstract_params(self):
        return jax.eval_shape(
            lambda k: self.init(k)[0],
            # under eval_shape the key is abstract; nothing is drawn
            jax.random.PRNGKey(0))  # speclint: disable=rng-literal-key -- abstract eval only

    def abstract_state(self, batch: int, max_len: int):
        """(ShapeDtypeStruct state, axes) without allocating the buffers."""
        state = jax.eval_shape(lambda: self.make_state(batch, max_len)[0])
        axes = self.make_state(1, 8)[1]   # axes structure is size-free
        return state, axes

    # ---- state -------------------------------------------------------
    def make_state(self, batch: int, max_len: int, rollback_span: int = 0,
                   paged: bool = False, block_size: int = 0,
                   pool_blocks: int = 0):
        """``paged=True`` builds a PagedModelState (per-row block tables
        over a shared block pool) where the arch supports it; SSM/hybrid
        silently keep the contiguous layout (their recurrent carries need
        the snapshot-ring machinery).  ``rollback_span > 0`` makes a
        recurrent arch keep what rollback restores: the snapshot rings of
        SSM/hybrid, or the inputs of up to ``rollback_span`` positions
        (the widest block the caller runs) of the linear-attention mixer;
        0 keeps nothing, so no rollback can restore the recurrent state."""
        cfg = self.cfg
        rec = dict(span=rollback_span) if cfg.recurrent else {}
        if paged and cfg.supports_paged:
            bs = block_size or kvc.PAGE_BLOCK
            layers, axes = self.mod.make_paged_cache(
                cfg, batch, max_len, bs, pool_blocks or None, **rec)
            state = kvc.make_paged_state(batch, max_len, layers,
                                         block_size=bs,
                                         pool_blocks=pool_blocks or None)
            return state, kvc.paged_state_axes(axes, bs)
        layers, axes = self.mod.make_cache(cfg, batch, max_len, **rec)
        state = kvc.make_state(batch, max_len, layers)
        state_axes = kvc.ModelState(
            token_buf=("batch", "seq"), pos_buf=("batch", "seq"),
            mask=("batch", "seq"), length=("batch",), write_ptr=(),
            layers=axes)
        return state, state_axes

    # ---- extras handling ----------------------------------------------
    def _prep(self, params, state, tokens, extras):
        """Returns (kwargs for forward_cached, state possibly updated)."""
        cfg = self.cfg
        kw: Dict[str, Any] = {}
        if cfg.arch_type == "audio":
            enc = extras.get("enc_states")
            if enc is not None and state is not None:
                xk, xv = tf.precompute_cross_kv(params, cfg, enc)
                state = dataclasses.replace(
                    state, layers={**state.layers, "cross_k": xk,
                                   "cross_v": xv})
        if cfg.arch_type == "vlm" and extras.get("mrope_positions") is not None:
            kw["mrope_positions"] = extras["mrope_positions"]
        if extras.get("input_embeds") is not None:
            kw["input_embeds"] = extras["input_embeds"]
        return kw, state

    # ---- inference -----------------------------------------------------
    def prefill(self, params, state, tokens, valid=None, logits_mode="last",
                **extras):
        kw, state = self._prep(params, state, tokens, extras)
        return self.mod.forward_cached(
            params, self.cfg, state, tokens, valid=valid,
            logits_mode=logits_mode, **kw)

    def decode(self, params, state, tokens, valid=None, logits_mode="all",
               spec_depth=None, spec_attend=None, **extras):
        kw, state = self._prep(params, state, tokens, extras)
        if spec_depth is not None or spec_attend is not None:
            # tree-structured speculation needs a per-position cache whose
            # branches can be masked independently; recurrent carries
            # (SSM/hybrid) cannot branch, so those archs stay linear-only
            if not self.cfg.supports_tree:
                raise NotImplementedError(
                    f"{self.cfg.arch_type} models cannot decode token trees")
            kw["spec_depth"] = spec_depth
            kw["spec_attend"] = spec_attend
        return self.mod.forward_cached(
            params, self.cfg, state, tokens, valid=valid,
            logits_mode=logits_mode, **kw)

    # ---- rollback (paper §4.4; SSM snapshot adaptation DESIGN §5) ------
    def rollback(self, state: kvc.ModelState, r: jnp.ndarray):
        if self.cfg.recurrent:
            state = self.mod.restore(state, r)
        return kvc.rollback(state, r)

    # ---- training ------------------------------------------------------
    def train_logits(self, params, tokens, remat=True, **extras):
        """Dense/ssm/hybrid: logits. MoE: (logits, aux_loss)."""
        cfg = self.cfg
        kw: Dict[str, Any] = {}
        if cfg.arch_type == "audio":
            kw["enc_states"] = extras.get("enc_states")
        if cfg.arch_type == "vlm":
            if extras.get("mrope_positions") is not None:
                kw["mrope_positions"] = extras["mrope_positions"]
            if extras.get("input_embeds") is not None:
                kw["input_embeds"] = extras["input_embeds"]
        return self.mod.forward_train(params, cfg, tokens, remat=remat, **kw)

    def has_aux_loss(self) -> bool:
        return self.cfg.arch_type == "moe"

    # ---- convenience ---------------------------------------------------
    def extras_for(self, batch: int, key=None) -> Dict[str, Any]:
        """Concrete stub frontend inputs for smoke tests / serving."""
        cfg = self.cfg
        if cfg.arch_type == "audio":
            return {"enc_states": frontends.audio_encoder_stub(cfg, batch, key)}
        return {}

    def extras_specs(self, batch: int) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for the dry-run."""
        cfg = self.cfg
        if cfg.arch_type == "audio":
            return {"enc_states": frontends.audio_encoder_spec(cfg, batch)}
        return {}
