"""Unified model builder: one ``Model`` object per architecture family.

Entry points (all pure functions of (params, ...) — jit/pjit-ready):

    train_loss(params, batch)            -> (loss, metrics)
    prefill(params, batch)               -> (logits [B,V], DecodeState)
    decode_step(params, state, tokens)   -> (logits [B,V], DecodeState)

Layer stacks are scanned (``lax.scan`` over stacked per-layer params) with
``jax.checkpoint`` rematerialization in training — compile time and HLO
size stay O(1) in depth.  Heterogeneous families (VLM cross-attention
every 5 layers, zamba2's shared attention every 6 Mamba2 layers) scan
homogeneous segments and interleave the special blocks.

DecodeState is a dict pytree; KV caches are laid out [L, B, S, H_kv, hd]
(or [L, B, S, d_latent+d_rope] for MLA) so the sequence dim can be
sharded over the ``model`` mesh axis for flash-decoding-style decode
(DESIGN.md §Decode-sharding).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import (FAMILY_DECODER, FAMILY_ENCDEC, FAMILY_HYBRID,
                          FAMILY_MOE, FAMILY_RWKV, FAMILY_VLM, KIND_DECODE,
                          KIND_PREFILL, KIND_TRAIN, MLA, ModelConfig,
                          ShapeConfig)
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (NOSHARD, PSpec, abstract, cross_entropy,
                                 layer_norm, materialize, rms_norm,
                                 sinusoidal_positions, stack_specs, swiglu)

Params = Any
Batch = Dict[str, jax.Array]
DecodeState = Dict[str, Any]


def _ln_spec(d: int) -> PSpec:
    return PSpec((d,), ("embed",), init="ones")


def _dense_ffn_pspecs(cfg: ModelConfig) -> Dict[str, PSpec]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w_gate": PSpec((d, f), ("embed", "mlp")),
        "w_up": PSpec((d, f), ("embed", "mlp")),
        "w_down": PSpec((f, d), ("mlp", "embed"),
                        scale=0.02 / math.sqrt(2 * max(cfg.n_layers, 1))),
    }


def _sinusoid_at(positions: jax.Array, dim: int) -> jax.Array:
    """Sinusoidal embedding at arbitrary integer positions [...]->[...,dim]."""
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    angle = positions[..., None].astype(jnp.float32) / jnp.power(
        10000.0, 2 * i / dim)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)


# ===========================================================================
# Base class
# ===========================================================================
class Model:
    family: str = "base"

    def __init__(self, cfg: ModelConfig, shd=NOSHARD,
                 aligned_decode: bool = False, scan_unroll: int = 1,
                 kv_dtype: str = "bfloat16"):
        self.cfg = cfg
        self.shd = shd
        self.aligned = aligned_decode
        self.scan_unroll = scan_unroll   # layer-scan unroll factor (perf)
        self.kv_dtype = kv_dtype         # "bfloat16" | "int8" (decode cache)
        self.specs = self.param_specs()

    # -- params -------------------------------------------------------------
    def param_specs(self) -> Params:
        raise NotImplementedError

    def init_params(self, rng: jax.Array) -> Params:
        return materialize(self.specs, rng, dtype=jnp.bfloat16)

    def abstract_params(self) -> Params:
        return abstract(self.specs)

    # -- state --------------------------------------------------------------
    def decode_state_specs(self, batch: int, max_len: int) -> Params:
        raise NotImplementedError

    def init_decode_state(self, batch: int, max_len: int) -> DecodeState:
        specs = self.decode_state_specs(batch, max_len)
        state = jax.tree.map(
            lambda p: jnp.zeros(p.shape, _state_dtype(p)), specs,
            is_leaf=lambda x: isinstance(x, PSpec))
        return state

    def abstract_decode_state(self, batch: int, max_len: int) -> DecodeState:
        specs = self.decode_state_specs(batch, max_len)
        return jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, _state_dtype(p)), specs,
            is_leaf=lambda x: isinstance(x, PSpec))

    # -- entry points ---------------------------------------------------------
    def train_loss(self, params: Params, batch: Batch
                   ) -> Tuple[jax.Array, Dict]:
        raise NotImplementedError

    def prefill(self, params: Params, batch: Batch
                ) -> Tuple[jax.Array, DecodeState]:
        raise NotImplementedError

    def decode_step(self, params: Params, state: DecodeState,
                    tokens: jax.Array) -> Tuple[jax.Array, DecodeState]:
        raise NotImplementedError

    def supports_paged_decode(self) -> bool:
        """Whether decode_step_paged is available for this config."""
        return False

    def decode_step_paged(self, params: Params, state: DecodeState,
                          tokens: jax.Array,
                          backend: str = None) -> Tuple[jax.Array,
                                                        DecodeState]:
        raise NotImplementedError(
            f"{type(self).__name__} has no paged decode path")

    def supports_chunked_prefill(self) -> bool:
        """Whether prefill_chunk is available for this config."""
        return False

    def prefill_chunk(self, params: Params, state: DecodeState,
                      tokens: jax.Array, offset: jax.Array,
                      backend: str = None) -> Dict:
        raise NotImplementedError(
            f"{type(self).__name__} has no chunked prefill path")

    def prefill_chunk_seg(self, params: Params, state: DecodeState,
                          tokens: jax.Array, chunk_positions: jax.Array,
                          backend: str = None) -> Dict:
        raise NotImplementedError(
            f"{type(self).__name__} has no segment prefill path")

    # -- dry-run inputs -------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every input of the entry point."""
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind == KIND_TRAIN:
            out = {"tokens": jax.ShapeDtypeStruct((b, s), i32),
                   "labels": jax.ShapeDtypeStruct((b, s), i32)}
        elif shape.kind == KIND_PREFILL:
            out = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        else:
            out = {"tokens": jax.ShapeDtypeStruct((b,), i32)}
        self._add_aux_specs(out, shape)
        return out

    def _add_aux_specs(self, out: Dict, shape: ShapeConfig) -> None:
        pass

    # -- helpers --------------------------------------------------------------
    def _embed(self, params: Params, tokens: jax.Array) -> jax.Array:
        return self.shd(params["embed"][tokens], "batch", "seq", None)

    def _logits(self, params: Params, x: jax.Array) -> jax.Array:
        if self.cfg.tie_embeddings:
            lg = jnp.einsum("...d,vd->...v", x, params["embed"])
        else:
            lg = jnp.einsum("...d,dv->...v", x, params["head"])
        axes = (("batch", "vocab") if lg.ndim == 2
                else ("batch", "seq", "vocab"))
        return self.shd(lg, *axes)

    def _loss_from_logits(self, logits, labels) -> Tuple[jax.Array, Dict]:
        loss = cross_entropy(logits, labels)
        return loss, {"loss": loss}


def _state_dtype(p: PSpec):
    return jnp.dtype(p.dtype) if p.dtype else jnp.bfloat16


def _int_spec(shape, axes) -> PSpec:
    return PSpec(tuple(shape), tuple(axes), init="zeros", dtype="int32")


# ===========================================================================
# Decoder-only (dense / MoE / MLA) + VLM
# ===========================================================================
class DecoderModel(Model):
    family = FAMILY_DECODER

    # -- parameter tree -------------------------------------------------------
    def _layer_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        if cfg.attention_variant == MLA:
            a = attn.mla_pspecs(cfg)
        else:
            a = attn.attn_pspecs(cfg)
        out = {"attn": a,
               "ln1": _ln_spec(cfg.d_model), "ln2": _ln_spec(cfg.d_model)}
        if cfg.n_experts == 0:
            out["ffn"] = _dense_ffn_pspecs(cfg)
        return out

    def param_specs(self) -> Params:
        cfg = self.cfg
        out = {
            "embed": PSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed")),
            "layers": stack_specs(self._layer_specs(), cfg.n_layers),
            "ln_f": _ln_spec(cfg.d_model),
        }
        if cfg.n_experts > 0:
            # FFNs of a MoE model stack apart from the layers: the
            # leading dense ones, then the MoE ones (see ``_ffn``)
            out["moe"] = stack_specs(moe_mod.moe_pspecs(cfg),
                                     cfg.n_moe_layers)
            if cfg.n_dense_layers:
                out["dense"] = stack_specs(_dense_ffn_pspecs(cfg),
                                           cfg.n_dense_layers)
        if not cfg.tie_embeddings:
            out["head"] = PSpec((cfg.d_model, cfg.vocab_size),
                                ("embed", "vocab"))
        if cfg.family == FAMILY_VLM:
            n_cross = len(cfg.cross_attn_layer_ids())
            cross = {"attn": attn.attn_pspecs(cfg, cross=True),
                     "ln": _ln_spec(cfg.d_model),
                     "gate": PSpec((1,), (None,), init="zeros")}
            out["cross"] = stack_specs(cross, n_cross)
            out["patch_proj"] = PSpec((cfg.d_model, cfg.d_model),
                                      ("embed", "embed_out"))
        return out

    # -- blocks -----------------------------------------------------------
    def _ffn(self, params, lp, i, h, valid=None, backend=None):
        """The FFN of layer ``i`` (a traced scan index) -> (out, aux
        loss, tokens per expert [E] or None).  A MoE model's first
        ``n_dense_layers`` layers run a dense SwiGLU and the rest MoE
        layer ``i - n_dense_layers``, chosen on the device, so one scan
        covers every layer and the KV pool is never split by layer.
        ``valid`` masks rows out of the experts and their counts."""
        cfg = self.cfg
        if cfg.n_experts == 0:
            return swiglu(h, lp["ffn"]["w_gate"], lp["ffn"]["w_up"],
                          lp["ffn"]["w_down"], self.shd), 0.0, None
        n_dense = cfg.n_dense_layers

        def moe(h):
            out, aux, counts = moe_mod.moe_ffn(
                params["moe"], h, cfg, jnp.maximum(i - n_dense, 0),
                valid=valid, backend=backend, shd=self.shd)
            return out, aux.astype(jnp.float32), counts
        if not n_dense:
            return moe(h)

        def dense(h):
            dp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, jnp.minimum(i, n_dense - 1), keepdims=False),
                params["dense"])
            return (swiglu(h, dp["w_gate"], dp["w_up"], dp["w_down"],
                           self.shd), jnp.float32(0.0),
                    jnp.zeros((cfg.layout_n_experts,), jnp.int32))
        return jax.lax.cond(i < n_dense, dense, moe, h)

    def _expert_counts(self, state, counts):
        """The state's per-MoE-layer expert counter [Lm, E] plus a
        step's per-layer counts [L, E] (a state without one is left
        as it is)."""
        if counts is None or "expert_tokens" not in state:
            return {}
        return {"expert_tokens": state["expert_tokens"]
                + counts[self.cfg.n_dense_layers:]}

    def _self_block_full(self, params, lp, i, x, positions):
        """Training/prefill layer; returns (x, (k, v or latent), aux)."""
        cfg = self.cfg
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        if cfg.attention_variant == MLA:
            o, latent = attn.mla_attention_prefill(lp["attn"], h, positions,
                                                   cfg, shd=self.shd)
            kv = (latent,)
        else:
            q, k, v = attn.project_qkv(lp["attn"], h, positions, cfg,
                                       shd=self.shd)
            o = attn.causal_attention(q, k, v, shd=self.shd)
            mask = attn.head_mask(cfg, o.dtype)
            if mask is not None:
                o = o * mask          # zero padded layout heads
            o = jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"])
            kv = (k, v)
        x = self.shd(x + o, "batch", "seq_res", None)
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        f, aux, _ = self._ffn(params, lp, i, h)
        return self.shd(x + f, "batch", "seq_res", None), kv, aux

    def _self_block_decode(self, params, lp, i, x, kv, lengths):
        """Decode layer; x [B,1,D]; kv = per-layer cache slices."""
        cfg = self.cfg
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        new_len = lengths + 1
        if cfg.attention_variant == MLA:
            (latent,) = kv
            o, latent = attn.mla_attention_decode(lp["attn"], h, latent,
                                                  new_len, cfg, self.shd,
                                                  aligned=self.aligned)
            new_kv = (latent,)
        elif self.kv_dtype == "int8":
            k_c, v_c, ks_c, vs_c = kv
            pos = lengths[:, None]
            q, k_new, v_new = attn.project_qkv(lp["attn"], h, pos, cfg,
                                               shd=NOSHARD)
            kq, ks = attn.quantize_kv(k_new)
            vq, vs = attn.quantize_kv(v_new)
            k_c = attn.cache_write(k_c, kq, lengths, aligned=self.aligned)
            v_c = attn.cache_write(v_c, vq, lengths, aligned=self.aligned)
            ks_c = attn.cache_write(ks_c, ks, lengths, aligned=self.aligned)
            vs_c = attn.cache_write(vs_c, vs, lengths, aligned=self.aligned)
            kd = attn.dequantize_kv(k_c, ks_c, h.dtype)
            vd = attn.dequantize_kv(v_c, vs_c, h.dtype)
            o = attn.decode_attention(q, kd, vd, new_len, shd=self.shd)
            mask = attn.head_mask(cfg, o.dtype)
            if mask is not None:
                o = o * mask
            o = jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"])
            new_kv = (k_c, v_c, ks_c, vs_c)
            x = x + o
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            f, _, _ = self._ffn(params, lp, i, h)
            return x + f, new_kv
        else:
            k_c, v_c = kv
            pos = lengths[:, None]
            q, k_new, v_new = attn.project_qkv(lp["attn"], h, pos, cfg,
                                               shd=NOSHARD)
            k_c = attn.cache_write(k_c, k_new, lengths, aligned=self.aligned)
            v_c = attn.cache_write(v_c, v_new, lengths, aligned=self.aligned)
            o = attn.decode_attention(q, k_c, v_c, new_len, shd=self.shd)
            mask = attn.head_mask(cfg, o.dtype)
            if mask is not None:
                o = o * mask
            o = jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"])
            new_kv = (k_c, v_c)
        x = x + o
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        f, _, _ = self._ffn(params, lp, i, h)
        return x + f, new_kv

    def _cross_block(self, cp, x, xk, xv):
        cfg = self.cfg
        h = rms_norm(x, cp["ln"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, cp["attn"]["wq"])
        o = attn.full_attention(q, xk, xv)
        o = jnp.einsum("bshk,hkd->bsd", o, cp["attn"]["wo"])
        return x + jnp.tanh(cp["gate"].astype(jnp.float32)).astype(x.dtype) * o

    def _cross_kv(self, cp, patches):
        k = jnp.einsum("bpd,dhk->bphk", patches, cp["attn"]["wk"])
        v = jnp.einsum("bpd,dhk->bphk", patches, cp["attn"]["wv"])
        return k, v

    # -- full-sequence forward ------------------------------------------------
    def _forward_full(self, params, tokens, patches=None, *,
                      collect_cache: bool = False, remat: bool = False):
        cfg = self.cfg
        b, s = tokens.shape
        x = self._embed(params, tokens)
        positions = jnp.arange(s)[None, :]
        aux_total = 0.0

        def layer_fn(x, inp):
            lp, i = inp
            x, kv, aux = self._self_block_full(params, lp, i, x, positions)
            return x, (kv if collect_cache else None, aux)

        body = jax.checkpoint(layer_fn) if remat else layer_fn

        if cfg.family == FAMILY_VLM:
            patches_e = jnp.einsum("bpd,de->bpe", patches,
                                   params["patch_proj"])
            n_cross = len(cfg.cross_attn_layer_ids())
            per = cfg.n_layers // n_cross
            self_stack = jax.tree.map(
                lambda a: a.reshape((n_cross, per) + a.shape[1:]),
                params["layers"])

            def group_fn(x, gp):
                cp, sp = gp
                xk, xv = self._cross_kv(cp, patches_e)
                x = self._cross_block(cp, x, xk, xv)
                x, outs = jax.lax.scan(body, x, (sp, None))
                return x, outs

            gbody = jax.checkpoint(group_fn) if remat else group_fn
            x, outs = jax.lax.scan(gbody, x, (params["cross"], self_stack))
            caches, auxes = outs
            if collect_cache:
                caches = jax.tree.map(
                    lambda a: a.reshape((-1,) + a.shape[2:]), caches)
        else:
            x, (caches, auxes) = jax.lax.scan(
                body, x, (params["layers"], jnp.arange(cfg.n_layers)),
                unroll=min(self.scan_unroll, cfg.n_layers))
        aux_total = jnp.mean(auxes) if cfg.n_experts > 0 else 0.0
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        return x, caches, aux_total

    # -- entry points ------------------------------------------------------
    def train_loss(self, params, batch):
        x, _, aux = self._forward_full(
            params, batch["tokens"], batch.get("patches"), remat=True)
        logits = self._logits(params, x)
        loss, metrics = self._loss_from_logits(logits, batch["labels"])
        if self.cfg.n_experts > 0:
            loss = loss + self.cfg.router_aux_weight * aux
            metrics["aux_loss"] = aux
        metrics["loss"] = loss
        return loss, metrics

    def prefill(self, params, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        x, caches, _ = self._forward_full(
            params, tokens, batch.get("patches"), collect_cache=True)
        logits = self._logits(params, x[:, -1])
        state: DecodeState = {"lengths": jnp.full((b,), s, jnp.int32)}
        if cfg.attention_variant == MLA:
            state["latent"] = self.shd(caches[0], None, "batch", "kv_seq", None)
        else:
            state["k"] = self.shd(caches[0], None, "batch", "kv_seq", None, None)
            state["v"] = self.shd(caches[1], None, "batch", "kv_seq", None, None)
        if cfg.family == FAMILY_VLM:
            patches_e = jnp.einsum("bpd,de->bpe", batch["patches"],
                                   params["patch_proj"])
            xks, xvs = jax.vmap(self._cross_kv, in_axes=(0, None))(
                params["cross"], patches_e)
            state["xk"], state["xv"] = xks, xvs
        return logits, state

    def prefill_suffix(self, params, batch, prefix_kv, q_offset: int):
        """Prefix-cache-aware prefill: attend suffix queries over
        [cached prefix KV ; suffix KV].  prefix_kv = (k, v) [L,B,P,..]
        (or (latent,) for MLA).  This is what converts radix-tree prefix
        hits into skipped prefill compute (paper §III-F)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = self._embed(params, tokens)
        positions = q_offset + jnp.arange(s)[None, :]

        if cfg.attention_variant == MLA:
            (lat_pre,) = prefix_kv

            def layer_fn(x, inp):
                lp, lpre, i = inp
                h = rms_norm(x, lp["ln1"], cfg.norm_eps)
                q_nope, q_rope, lat_new = attn.mla_project(
                    lp["attn"], h, positions, cfg, self.shd)
                lat_full = jnp.concatenate([lpre, lat_new], axis=1)
                dl, dr = cfg.d_latent, cfg.d_rope
                c_kv, k_rope = lat_full[..., :dl], lat_full[..., dl:]
                k = jnp.einsum("bsl,lhk->bshk", c_kv, lp["attn"]["w_uk"])
                v = jnp.einsum("bsl,lhk->bshk", c_kv, lp["attn"]["w_uv"])
                q = jnp.concatenate([q_nope, q_rope], axis=-1)
                k = jnp.concatenate(
                    [k, jnp.broadcast_to(k_rope[:, :, None, :],
                                         k.shape[:3] + (dr,))], axis=-1)
                o = attn.causal_attention(q, k, v, q_offset=q_offset,
                                          scale=cfg.mla_softmax_scale,
                                          shd=self.shd)
                o = jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"])
                x = x + o
                h = rms_norm(x, lp["ln2"], cfg.norm_eps)
                f, _, _ = self._ffn(params, lp, i, h)
                return x + f, lat_new

            x, lat_suffix = jax.lax.scan(
                layer_fn, x,
                (params["layers"], lat_pre, jnp.arange(cfg.n_layers)))
            x = rms_norm(x, params["ln_f"], cfg.norm_eps)
            return self._logits(params, x[:, -1]), (lat_suffix,)

        k_pre, v_pre = prefix_kv

        def layer_fn(x, inp):
            lp, kp, vp, i = inp
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = attn.project_qkv(lp["attn"], h, positions, cfg,
                                       shd=self.shd)
            k_full = jnp.concatenate([kp, k], axis=1)
            v_full = jnp.concatenate([vp, v], axis=1)
            o = attn.causal_attention(q, k_full, v_full,
                                      q_offset=q_offset, shd=self.shd)
            mask = attn.head_mask(cfg, o.dtype)
            if mask is not None:
                o = o * mask
            o = jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"])
            x = x + o
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            f, _, _ = self._ffn(params, lp, i, h)
            return x + f, (k, v)

        x, (ks, vs) = jax.lax.scan(
            layer_fn, x,
            (params["layers"], k_pre, v_pre, jnp.arange(cfg.n_layers)))
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        return self._logits(params, x[:, -1]), (ks, vs)

    def decode_step(self, params, state, tokens):
        cfg = self.cfg
        b = tokens.shape[0]
        x = self.shd.embed_lookup(params["embed"], tokens)[:, None, :]
        lengths = state["lengths"]
        layer_ids = jnp.arange(cfg.n_layers)

        if cfg.attention_variant == MLA:
            def layer_fn(x, inp):
                lp, latent, i = inp
                x, (latent,) = self._self_block_decode(
                    params, lp, i, x, (latent,), lengths)
                return x, latent
            x, latents = jax.lax.scan(
                layer_fn, x,
                (params["layers"], state["latent"], layer_ids))
            new_state = {**state, "latent": latents,
                         "lengths": lengths + 1}
        elif cfg.family == FAMILY_VLM:
            n_cross = len(cfg.cross_attn_layer_ids())
            per = cfg.n_layers // n_cross
            self_stack = jax.tree.map(
                lambda a: a.reshape((n_cross, per) + a.shape[1:]),
                params["layers"])
            kv_stack = jax.tree.map(
                lambda a: a.reshape((n_cross, per) + a.shape[1:]),
                (state["k"], state["v"]))

            def layer_fn(x, inp):
                lp, kv = inp
                x, kv = self._self_block_decode(params, lp, None, x, kv,
                                                lengths)
                return x, kv

            def group_fn(x, gp):
                cp, sp, kvs, xk, xv = gp
                x = self._cross_block(cp, x, xk, xv)
                x, kvs = jax.lax.scan(layer_fn, x, (sp, kvs))
                return x, kvs

            x, kvs = jax.lax.scan(
                group_fn, x, (params["cross"], self_stack, kv_stack,
                              state["xk"], state["xv"]))
            k_new, v_new = jax.tree.map(
                lambda a: a.reshape((-1,) + a.shape[2:]), kvs)
            new_state = {**state, "k": k_new, "v": v_new,
                         "lengths": lengths + 1}
        elif self.kv_dtype == "int8":
            def layer_fn(x, inp):
                lp, k_c, v_c, ks_c, vs_c, i = inp
                x, kv = self._self_block_decode(
                    params, lp, i, x, (k_c, v_c, ks_c, vs_c), lengths)
                return x, kv
            x, (ks, vs, kss, vss) = jax.lax.scan(
                layer_fn, x, (params["layers"], state["k"], state["v"],
                              state["k_scale"], state["v_scale"],
                              layer_ids))
            new_state = {**state, "k": ks, "v": vs, "k_scale": kss,
                         "v_scale": vss, "lengths": lengths + 1}
        else:
            def layer_fn(x, inp):
                lp, k_c, v_c, i = inp
                x, (k_c, v_c) = self._self_block_decode(
                    params, lp, i, x, (k_c, v_c), lengths)
                return x, (k_c, v_c)
            x, (ks, vs) = jax.lax.scan(
                layer_fn, x,
                (params["layers"], state["k"], state["v"], layer_ids))
            new_state = {**state, "k": ks, "v": vs, "lengths": lengths + 1}

        x = rms_norm(x[:, 0], params["ln_f"], cfg.norm_eps)
        return self._logits(params, x), new_state

    # -- paged decode (block-table KV pool; serving fast path) ---------------
    def supports_paged_decode(self) -> bool:
        """Paged decode covers dense/MoE decoder-only configs (incl. MLA).
        VLM (cross-attn state) and int8 caches fall back to the dense
        slot layout."""
        cfg = self.cfg
        return (cfg.family in (FAMILY_DECODER, FAMILY_MOE)
                and self.kv_dtype != "int8")

    def decode_step_paged(self, params, state, tokens, backend=None):
        """One batched decode step over a paged KV pool.

        state: {"k_pages"/"v_pages" [L, N, page, Hkv, hd]} (or MLA
        {"latent_pages" [L, N, page, dl+dr]}) + "block_tables" [B, P]
        int32 + "lengths" [B] int32.  The new token's KV is scattered
        into each request's current page; attention reads through the
        block table via the paged attention ops (table entry 0 is the
        caller's scratch page for inactive batch rows).  The GQA pools
        are carried whole through the layer scan: layer i writes its
        token into the stack in place (``.at[i, page, off]``) and the
        kernels read layer i's pages from the stack by index, so no
        layer is sliced out or stacked back; the carried pools are the
        new state.  ``backend`` selects the kernel backend
        (``kernels/backend.py``: compiled Pallas on TPU / jitted XLA
        gathers elsewhere by default).  A MoE
        model adds the step's routed tokens to ``state["expert_tokens"]``
        [Lm, E] where the state holds that counter.
        """
        from repro.kernels import ops

        cfg = self.cfg
        b = tokens.shape[0]
        x = self.shd.embed_lookup(params["embed"], tokens)[:, None, :]
        lengths = state["lengths"]
        bt = state["block_tables"]
        pool_key = "latent_pages" if cfg.attention_variant == MLA else "k_pages"
        page = state[pool_key].shape[2]
        page_ids = bt[jnp.arange(b), lengths // page]
        offs = lengths % page
        new_len = lengths + 1
        # a decoding row has its first page mapped; idle rows read the
        # scratch page and stay out of the experts and their counts
        valid = (bt[:, 0] > 0)[:, None]
        layer_ids = jnp.arange(cfg.n_layers)

        if cfg.attention_variant == MLA:
            dl = cfg.d_latent

            def layer_fn(x, inp):
                lp, latp, i = inp
                h = rms_norm(x, lp["ln1"], cfg.norm_eps)
                q_nope, q_rope, new_latent = attn.mla_project(
                    lp["attn"], h, lengths[:, None], cfg)
                latp = latp.at[page_ids, offs].set(
                    new_latent[:, 0].astype(latp.dtype))
                # absorb W_uk into the query; the kernel attends in
                # latent space and returns ctx [B, Hq, dl]
                q_lat = jnp.einsum("bshk,lhk->bshl", q_nope,
                                   lp["attn"]["w_uk"])
                ctx = ops.mla_decode(q_lat[:, 0], q_rope[:, 0], latp, bt,
                                     new_len, d_latent=dl,
                                     scale=cfg.mla_softmax_scale,
                                     backend=backend)
                out = jnp.einsum("bhl,lhk->bhk", ctx, lp["attn"]["w_uv"])
                o = jnp.einsum("bhk,hkd->bd", out, lp["attn"]["wo"])[:, None]
                x = x + o
                h = rms_norm(x, lp["ln2"], cfg.norm_eps)
                f, _, counts = self._ffn(params, lp, i, h, valid, backend)
                return x + f, (latp, counts)

            x, (lats, counts) = jax.lax.scan(
                layer_fn, x,
                (params["layers"], state["latent_pages"], layer_ids))
            new_state = {**state, "latent_pages": lats, "lengths": new_len,
                         **self._expert_counts(state, counts)}
        else:
            def layer_fn(carry, inp):
                x, kp, vp = carry
                lp, i = inp
                h = rms_norm(x, lp["ln1"], cfg.norm_eps)
                q, k_new, v_new = attn.project_qkv(lp["attn"], h,
                                                   lengths[:, None], cfg,
                                                   shd=NOSHARD)
                kp = kp.at[i, page_ids, offs].set(
                    k_new[:, 0].astype(kp.dtype))
                vp = vp.at[i, page_ids, offs].set(
                    v_new[:, 0].astype(vp.dtype))
                o = ops.paged_decode(q[:, 0], kp, vp, bt, new_len, i,
                                     backend=backend)
                mask = attn.head_mask(cfg, o.dtype)
                if mask is not None:
                    o = o * mask              # zero padded layout heads
                o = jnp.einsum("bhk,hkd->bd", o, lp["attn"]["wo"])[:, None]
                x = x + o
                h = rms_norm(x, lp["ln2"], cfg.norm_eps)
                f, _, counts = self._ffn(params, lp, i, h, valid, backend)
                return (x + f, kp, vp), counts

            (x, kps, vps), counts = jax.lax.scan(
                layer_fn, (x, state["k_pages"], state["v_pages"]),
                (params["layers"], layer_ids))
            new_state = {**state, "k_pages": kps, "v_pages": vps,
                         "lengths": new_len,
                         **self._expert_counts(state, counts)}

        x = rms_norm(x[:, 0], params["ln_f"], cfg.norm_eps)
        return self._logits(params, x), new_state

    # -- chunked prefill (token-budget mixed batches; serving fast path) -----
    def supports_chunked_prefill(self) -> bool:
        return self.supports_paged_decode()

    def prefill_chunk(self, params, state, tokens, offset, backend=None):
        """Prefill a fixed-size prompt chunk against the request's
        already-resident paged KV.

        tokens [1, C] int32 (zero-padded past the valid suffix); offset
        [1] int32 — tokens already written to this slot's pages; state:
        {"k_pages"/"v_pages"} (or MLA {"latent_pages"}) + "block_table"
        [1, P] int32.  Attention is causal within the chunk and full
        over pool tokens < offset (kernels/paged_prefill.py); the GQA
        kernels read layer i's pages from the whole stacked pool by
        index, so the pool is never sliced per layer.  Returns
        the chunk's per-layer KV ({"k"/"v"} [L,1,C,Hkv,hd] or
        {"latent"} [L,1,C,dl+dr]) for the caller to scatter into the
        pool via ``PagedKVCache.write_chunk`` — logits are never needed:
        the first decode step consumes the final prompt token.  With
        experts the state may hold ``n_valid`` (the chunk's prompt
        tokens; the rest is padding the experts skip) and the counter
        ``expert_tokens``, which comes back with the chunk's tokens
        added.
        """
        from repro.kernels import ops

        cfg = self.cfg
        c = tokens.shape[1]
        x = self._embed(params, tokens)
        positions = offset[:, None] + jnp.arange(c)[None, :]
        bt = state["block_table"]
        # the chunk's first n_valid tokens are the prompt's, the rest
        # padding that the experts and their counts leave out
        valid = (jnp.arange(c)[None, :] < state["n_valid"]
                 if "n_valid" in state else None)
        layer_ids = jnp.arange(cfg.n_layers)

        if cfg.attention_variant == MLA:
            dl = cfg.d_latent

            def layer_fn(x, inp):
                lp, latp, i = inp
                h = rms_norm(x, lp["ln1"], cfg.norm_eps)
                q_nope, q_rope, latent = attn.mla_project(
                    lp["attn"], h, positions, cfg)
                q_lat = jnp.einsum("bshk,lhk->bshl", q_nope,
                                   lp["attn"]["w_uk"])
                ctx = ops.mla_prefill(q_lat, q_rope, latent, latp, bt,
                                      offset, d_latent=dl,
                                      scale=cfg.mla_softmax_scale,
                                      backend=backend)
                out = jnp.einsum("bshl,lhk->bshk", ctx, lp["attn"]["w_uv"])
                o = jnp.einsum("bshk,hkd->bsd", out, lp["attn"]["wo"])
                x = x + o
                h = rms_norm(x, lp["ln2"], cfg.norm_eps)
                f, _, counts = self._ffn(params, lp, i, h, valid, backend)
                return x + f, (latent, counts)

            _, (lats, counts) = jax.lax.scan(
                layer_fn, x,
                (params["layers"], state["latent_pages"], layer_ids))
            return {"latent": lats, **self._expert_counts(state, counts)}

        kp, vp = state["k_pages"], state["v_pages"]

        def layer_fn(x, inp):
            lp, i = inp
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = attn.project_qkv(lp["attn"], h, positions, cfg,
                                       shd=NOSHARD)
            o = ops.paged_prefill(q, k, v, kp, vp, bt, offset, i,
                                  backend=backend)
            mask = attn.head_mask(cfg, o.dtype)
            if mask is not None:
                o = o * mask              # zero padded layout heads
            o = jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"])
            x = x + o
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            f, _, counts = self._ffn(params, lp, i, h, valid, backend)
            return x + f, (k, v, counts)

        _, (ks, vs, counts) = jax.lax.scan(
            layer_fn, x, (params["layers"], layer_ids))
        return {"k": ks, "v": vs, **self._expert_counts(state, counts)}

    def prefill_chunk_seg(self, params, state, tokens, chunk_positions,
                          backend=None):
        """Segment-prefill a chunk of prompt *gap* tokens at arbitrary
        ascending absolute positions (``chunk_positions`` [1, C] int32;
        negative = padding).  Same contract as ``prefill_chunk`` except
        the chunk may span multiple gaps with resumed pool-resident
        segments between them: RoPE is applied at each token's true
        position and attention runs through the segment kernels
        (kernels/ops.py ``paged_prefill_seg``/``mla_prefill_seg``).
        Every position below a chunk token's that is not in the chunk
        must already be resident in the slot's pages."""
        from repro.kernels import ops

        cfg = self.cfg
        positions = jnp.maximum(chunk_positions, 0)   # RoPE-safe padding
        bt = state["block_table"]

        x = self._embed(params, tokens)
        valid = chunk_positions >= 0
        layer_ids = jnp.arange(cfg.n_layers)
        if cfg.attention_variant == MLA:
            dl = cfg.d_latent

            def layer_fn(x, inp):
                lp, latp, i = inp
                h = rms_norm(x, lp["ln1"], cfg.norm_eps)
                q_nope, q_rope, latent = attn.mla_project(
                    lp["attn"], h, positions, cfg)
                q_lat = jnp.einsum("bshk,lhk->bshl", q_nope,
                                   lp["attn"]["w_uk"])
                ctx = ops.mla_prefill_seg(q_lat, q_rope, latent, latp, bt,
                                          chunk_positions, d_latent=dl,
                                          scale=cfg.mla_softmax_scale,
                                          backend=backend)
                out = jnp.einsum("bshl,lhk->bshk", ctx, lp["attn"]["w_uv"])
                o = jnp.einsum("bshk,hkd->bsd", out, lp["attn"]["wo"])
                x = x + o
                h = rms_norm(x, lp["ln2"], cfg.norm_eps)
                f, _, counts = self._ffn(params, lp, i, h, valid, backend)
                return x + f, (latent, counts)

            _, (lats, counts) = jax.lax.scan(
                layer_fn, x,
                (params["layers"], state["latent_pages"], layer_ids))
            return {"latent": lats, **self._expert_counts(state, counts)}

        kp, vp = state["k_pages"], state["v_pages"]

        def layer_fn(x, inp):
            lp, i = inp
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = attn.project_qkv(lp["attn"], h, positions, cfg,
                                       shd=NOSHARD)
            o = ops.paged_prefill_seg(q, k, v, kp, vp, bt,
                                      chunk_positions, i, backend=backend)
            mask = attn.head_mask(cfg, o.dtype)
            if mask is not None:
                o = o * mask              # zero padded layout heads
            o = jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"])
            x = x + o
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            f, _, counts = self._ffn(params, lp, i, h, valid, backend)
            return x + f, (k, v, counts)

        _, (ks, vs, counts) = jax.lax.scan(
            layer_fn, x, (params["layers"], layer_ids))
        return {"k": ks, "v": vs, **self._expert_counts(state, counts)}

    # -- decode state ----------------------------------------------------------
    def decode_state_specs(self, batch, max_len):
        cfg = self.cfg
        L, hkv, hd = cfg.n_layers, max(cfg.n_kv_heads, 1), cfg.hd
        out = {"lengths": _int_spec((batch,), ("batch",))}
        if cfg.attention_variant == MLA:
            out["latent"] = PSpec((L, batch, max_len,
                                   cfg.d_latent + cfg.d_rope),
                                  ("layers", "batch", "kv_seq", None),
                                  init="zeros")
        else:
            dt = "int8" if self.kv_dtype == "int8" else None
            kvspec = PSpec((L, batch, max_len, hkv, hd),
                           ("layers", "batch", "kv_seq", None, None),
                           init="zeros", dtype=dt)
            out["k"] = kvspec
            out["v"] = kvspec
            if self.kv_dtype == "int8":
                sspec = PSpec((L, batch, max_len, hkv, 1),
                              ("layers", "batch", "kv_seq", None, None),
                              init="zeros")
                out["k_scale"] = sspec
                out["v_scale"] = sspec
        if cfg.family == FAMILY_VLM:
            n_cross = len(cfg.cross_attn_layer_ids())
            xspec = PSpec((n_cross, batch, cfg.n_patches, hkv, hd),
                          ("layers", "batch", None, "kv_heads", None),
                          init="zeros")
            out["xk"] = xspec
            out["xv"] = xspec
        return out

    def _add_aux_specs(self, out, shape):
        cfg = self.cfg
        if cfg.family == FAMILY_VLM and shape.kind != KIND_DECODE:
            out["patches"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.n_patches, cfg.d_model),
                jnp.bfloat16)


class VLMModel(DecoderModel):
    family = FAMILY_VLM


# ===========================================================================
# Hybrid: Mamba2 backbone + shared attention block (zamba2)
# ===========================================================================
class HybridModel(Model):
    family = FAMILY_HYBRID

    def param_specs(self):
        cfg = self.cfg
        layer = {"mamba": ssm_mod.mamba_pspecs(cfg),
                 "ln": _ln_spec(cfg.d_model)}
        shared = {"attn": attn.attn_pspecs(cfg),
                  "ffn": _dense_ffn_pspecs(cfg),
                  "ln1": _ln_spec(cfg.d_model),
                  "ln2": _ln_spec(cfg.d_model)}
        return {
            "embed": PSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed")),
            "layers": stack_specs(layer, cfg.n_layers),
            "shared": shared,
            "ln_f": _ln_spec(cfg.d_model),
        }

    def _segments(self):
        cfg = self.cfg
        ids = cfg.attn_layer_ids()
        bounds, prev = [], 0
        for i in ids:
            bounds.append((prev, i + 1, True))
            prev = i + 1
        if prev < cfg.n_layers:
            bounds.append((prev, cfg.n_layers, False))
        return bounds

    def _mamba_layer_full(self, lp, x):
        h = rms_norm(x, lp["ln"], self.cfg.norm_eps)
        return x + ssm_mod.mamba_block(lp["mamba"], h, self.cfg,
                                       shd=self.shd)

    def _shared_attn_full(self, params, x, positions, *, cache=None,
                          lengths=None):
        cfg, sp = self.cfg, params["shared"]
        h = rms_norm(x, sp["ln1"], cfg.norm_eps)
        if cache is None:
            q, k, v = attn.project_qkv(sp["attn"], h, positions, cfg,
                                       shd=self.shd)
            o = attn.causal_attention(q, k, v, shd=self.shd)
            new_cache = (k, v)
        else:
            k_c, v_c = cache
            q, k_new, v_new = attn.project_qkv(sp["attn"], h,
                                               lengths[:, None], cfg)
            k_c = attn.cache_write(k_c, k_new, lengths, aligned=self.aligned)
            v_c = attn.cache_write(v_c, v_new, lengths, aligned=self.aligned)
            o = attn.decode_attention(q, k_c, v_c, lengths + 1, shd=self.shd)
            new_cache = (k_c, v_c)
        o = jnp.einsum("bshk,hkd->bsd", o, sp["attn"]["wo"])
        x = x + o
        h = rms_norm(x, sp["ln2"], cfg.norm_eps)
        x = x + swiglu(h, sp["ffn"]["w_gate"], sp["ffn"]["w_up"],
                       sp["ffn"]["w_down"], self.shd)
        return x, new_cache

    def _forward_full(self, params, tokens, *, collect_cache=False,
                      remat=False):
        cfg = self.cfg
        b, s = tokens.shape
        x = self._embed(params, tokens)
        positions = jnp.arange(s)[None, :]
        body = (jax.checkpoint(lambda x, lp: (self._mamba_layer_full(lp, x),
                                              None))
                if remat else lambda x, lp: (self._mamba_layer_full(lp, x),
                                             None))
        caches = []
        for (lo, hi, has_attn) in self._segments():
            seg = jax.tree.map(lambda a: a[lo:hi], params["layers"])
            x, _ = jax.lax.scan(body, x, seg)
            if has_attn:
                x, kv = self._shared_attn_full(params, x, positions)
                if collect_cache:
                    caches.append(kv)
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        if collect_cache:
            ks = jnp.stack([c[0] for c in caches])
            vs = jnp.stack([c[1] for c in caches])
            return x, (ks, vs)
        return x, None

    def train_loss(self, params, batch):
        x, _ = self._forward_full(params, batch["tokens"], remat=True)
        logits = self._logits(params, x)
        return self._loss_from_logits(logits, batch["labels"])

    def prefill(self, params, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        # run full forward for caches; recompute mamba states via chunked
        # scan final states
        cfg = self.cfg
        x = self._embed(params, tokens)
        positions = jnp.arange(s)[None, :]
        ssm_states, conv_states, attn_caches = [], [], []

        def layer_with_state(x, lp):
            h = rms_norm(x, lp["ln"], cfg.norm_eps)
            y, st = _mamba_block_with_state(lp["mamba"], h, cfg, self.shd)
            return x + y, st

        for (lo, hi, has_attn) in self._segments():
            seg = jax.tree.map(lambda a: a[lo:hi], params["layers"])
            x, sts = jax.lax.scan(layer_with_state, x, seg)
            ssm_states.append(sts)
            if has_attn:
                x, kv = self._shared_attn_full(params, x, positions)
                attn_caches.append(kv)
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = self._logits(params, x[:, -1])
        sts = jax.tree.map(lambda *xs: jnp.concatenate(xs), *ssm_states)
        state = {"mamba": sts,
                 "k": self.shd(jnp.stack([c[0] for c in attn_caches]),
                               None, "batch", "kv_seq", None, None),
                 "v": self.shd(jnp.stack([c[1] for c in attn_caches]),
                               None, "batch", "kv_seq", None, None),
                 "lengths": jnp.full((b,), s, jnp.int32)}
        return logits, state

    def decode_step(self, params, state, tokens):
        cfg = self.cfg
        x = self.shd.embed_lookup(params["embed"], tokens)    # [B,D]
        lengths = state["lengths"]

        def layer_fn(x, inp):
            lp, st = inp
            h = rms_norm(x[:, None], lp["ln"], cfg.norm_eps)[:, 0]
            y, st = ssm_mod.mamba_decode_step(lp["mamba"], h, st, cfg)
            return x + y, st

        new_m, new_k, new_v = [], [], []
        ai = 0
        for (lo, hi, has_attn) in self._segments():
            seg = jax.tree.map(lambda a: a[lo:hi], params["layers"])
            mst = jax.tree.map(lambda a: a[lo:hi], state["mamba"])
            x, mst = jax.lax.scan(layer_fn, x, (seg, mst))
            new_m.append(mst)
            if has_attn:
                kv = (state["k"][ai], state["v"][ai])
                x2, kv = self._shared_attn_full(
                    params, x[:, None], None, cache=kv, lengths=lengths)
                x = x2[:, 0]
                new_k.append(kv[0])
                new_v.append(kv[1])
                ai += 1
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = self._logits(params, x)
        new_state = {"mamba": jax.tree.map(lambda *xs: jnp.concatenate(xs),
                                           *new_m),
                     "k": jnp.stack(new_k), "v": jnp.stack(new_v),
                     "lengths": lengths + 1}
        return logits, new_state

    def decode_state_specs(self, batch, max_len):
        cfg = self.cfg
        n_apps = len(cfg.attn_layer_ids())
        m = stack_specs(ssm_mod.mamba_state_pspecs(cfg, batch), cfg.n_layers)
        kv = PSpec((n_apps, batch, max_len, cfg.n_kv_heads, cfg.hd),
                   ("layers", "batch", "kv_seq", None, None), init="zeros")
        return {"mamba": m, "k": kv, "v": kv,
                "lengths": _int_spec((batch,), ("batch",))}


def _mamba_block_with_state(p, x, cfg, shd):
    """mamba_block variant that also returns the final SSM/conv states
    (for prefill -> decode handoff)."""
    bsz, s, d = x.shape
    h, hd = cfg.n_ssm_heads, cfg.ssm_head_dim
    z = jnp.einsum("bsd,di->bsi", x, p["w_z"])
    x_pre = jnp.einsum("bsd,di->bsi", x, p["w_x"])
    b_pre = jnp.einsum("bsd,dn->bsn", x, p["w_B"])
    c_pre = jnp.einsum("bsd,dn->bsn", x, p["w_C"])
    xs = ssm_mod._causal_conv(x_pre, p["conv_x"])
    xs = shd(jax.nn.silu(xs.astype(jnp.float32)).astype(x.dtype),
             "batch", "seq", "inner")
    b_in = jax.nn.silu(ssm_mod._causal_conv(b_pre, p["conv_B"])
                       .astype(jnp.float32))
    c_in = jax.nn.silu(ssm_mod._causal_conv(c_pre, p["conv_C"])
                       .astype(jnp.float32))
    dt = jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", x, p["w_dt"]).astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xs.reshape(bsz, s, h, hd)
    y, final = ssm_mod.ssd_chunked(xh, dt, a, b_in, c_in)
    y = y + p["D"][None, None, :, None] * xh
    y = y.reshape(bsz, s, cfg.d_inner)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                 p["norm"], cfg.norm_eps)
    out = jnp.einsum("bsi,id->bsd", y, p["w_out"])
    k = cfg.ssm_conv
    state = {"ssm": final,
             "conv_x": x_pre[:, -(k - 1):, :],
             "conv_B": b_pre[:, -(k - 1):, :],
             "conv_C": c_pre[:, -(k - 1):, :]}
    return out, state


# ===========================================================================
# RWKV6
# ===========================================================================
class RWKVModel(Model):
    family = FAMILY_RWKV

    def param_specs(self):
        cfg = self.cfg
        layer = dict(rwkv_mod.rwkv_pspecs(cfg))
        layer.update(ln1_g=_ln_spec(cfg.d_model),
                     ln1_b=PSpec((cfg.d_model,), ("embed",), init="zeros"),
                     ln2_g=_ln_spec(cfg.d_model),
                     ln2_b=PSpec((cfg.d_model,), ("embed",), init="zeros"))
        return {
            "embed": PSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed")),
            "ln0_g": _ln_spec(cfg.d_model),
            "ln0_b": PSpec((cfg.d_model,), ("embed",), init="zeros"),
            "layers": stack_specs(layer, cfg.n_layers),
            "ln_f": _ln_spec(cfg.d_model),
            "head": PSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab")),
        }

    def _layer_full(self, lp, x):
        cfg = self.cfg
        h = layer_norm(x, lp["ln1_g"], lp["ln1_b"], cfg.norm_eps)
        x = x + rwkv_mod.time_mix(lp, h, cfg, shd=self.shd)
        h = layer_norm(x, lp["ln2_g"], lp["ln2_b"], cfg.norm_eps)
        x = x + rwkv_mod.channel_mix(lp, h, shd=self.shd)
        return x

    def _forward_full(self, params, tokens, remat=False):
        cfg = self.cfg
        x = self._embed(params, tokens)
        x = layer_norm(x, params["ln0_g"], params["ln0_b"], cfg.norm_eps)
        body = (jax.checkpoint(lambda x, lp: (self._layer_full(lp, x), None))
                if remat else lambda x, lp: (self._layer_full(lp, x), None))
        x, _ = jax.lax.scan(body, x, params["layers"])
        return layer_norm(x, params["ln_f"],
                          jnp.zeros_like(params["ln_f"]), cfg.norm_eps)

    def train_loss(self, params, batch):
        x = self._forward_full(params, batch["tokens"], remat=True)
        logits = self._logits(params, x)
        return self._loss_from_logits(logits, batch["labels"])

    def prefill(self, params, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = self._embed(params, tokens)
        x = layer_norm(x, params["ln0_g"], params["ln0_b"], cfg.norm_eps)

        def layer_with_state(x, lp):
            h = layer_norm(x, lp["ln1_g"], lp["ln1_b"], cfg.norm_eps)
            tm_out, wkv = _time_mix_with_state(lp, h, cfg, self.shd)
            x = x + tm_out
            h2 = layer_norm(x, lp["ln2_g"], lp["ln2_b"], cfg.norm_eps)
            x = x + rwkv_mod.channel_mix(lp, h2, shd=self.shd)
            return x, {"wkv": wkv, "tm_x": h[:, -1], "cm_x": h2[:, -1]}

        x, states = jax.lax.scan(layer_with_state, x, params["layers"])
        x = layer_norm(x, params["ln_f"], jnp.zeros_like(params["ln_f"]),
                       cfg.norm_eps)
        logits = self._logits(params, x[:, -1])
        states["lengths"] = jnp.full((b,), s, jnp.int32)
        return logits, states

    def decode_step(self, params, state, tokens):
        cfg = self.cfg
        x = self.shd.embed_lookup(params["embed"], tokens)
        x = layer_norm(x[:, None], params["ln0_g"], params["ln0_b"],
                       cfg.norm_eps)[:, 0]

        def layer_fn(x, inp):
            lp, st = inp
            h = layer_norm(x[:, None], lp["ln1_g"], lp["ln1_b"],
                           cfg.norm_eps)[:, 0]
            tm_out, st = rwkv_mod.rwkv_decode_step(lp, h, None, st, cfg)
            x = x + tm_out
            h2 = layer_norm(x[:, None], lp["ln2_g"], lp["ln2_b"],
                            cfg.norm_eps)[:, 0]
            cm_out, st = rwkv_mod.channel_mix_step(lp, h2, st)
            return x + cm_out, st

        lstate = {k: state[k] for k in ("wkv", "tm_x", "cm_x")}
        x, new_lstate = jax.lax.scan(layer_fn, x, (params["layers"], lstate))
        x = layer_norm(x[:, None], params["ln_f"],
                       jnp.zeros_like(params["ln_f"]), cfg.norm_eps)[:, 0]
        new_state = dict(new_lstate)
        new_state["lengths"] = state["lengths"] + 1
        return self._logits(params, x), new_state

    def decode_state_specs(self, batch, max_len):
        cfg = self.cfg
        st = stack_specs(rwkv_mod.rwkv_state_pspecs(cfg, batch),
                         cfg.n_layers)
        st["lengths"] = _int_spec((batch,), ("batch",))
        return st


def _time_mix_with_state(p, x, cfg, shd):
    bsz, s, d = x.shape
    xprev = rwkv_mod._shift(x)
    xr = rwkv_mod._mix(x, xprev, p["mu_r"])
    xk = rwkv_mod._mix(x, xprev, p["mu_k"])
    xv = rwkv_mod._mix(x, xprev, p["mu_v"])
    xw = rwkv_mod._mix(x, xprev, p["mu_w"])
    xg = rwkv_mod._mix(x, xprev, p["mu_g"])
    r = jnp.einsum("bsd,dhk->bshk", xr, p["w_r"])
    k = jnp.einsum("bsd,dhk->bshk", xk, p["w_k"])
    v = jnp.einsum("bsd,dhk->bshk", xv, p["w_v"])
    g = jax.nn.silu(jnp.einsum("bsd,dhk->bshk", xg, p["w_g"])
                    .astype(jnp.float32))
    logw = rwkv_mod._log_decay(p, xw)
    y, final = rwkv_mod.wkv_chunked(r, k, v, logw, p["bonus_u"])
    y = rwkv_mod._group_norm(y, p["ln_x"], cfg.norm_eps) * g
    out = jnp.einsum("bshk,hkd->bsd", y.astype(x.dtype), p["w_o"])
    return out, final.astype(jnp.bfloat16)


# ===========================================================================
# Whisper-style encoder-decoder
# ===========================================================================
class EncDecModel(Model):
    family = FAMILY_ENCDEC

    def param_specs(self):
        cfg = self.cfg
        enc_layer = {"attn": attn.attn_pspecs(cfg),
                     "ffn": _dense_ffn_pspecs(cfg),
                     "ln1": _ln_spec(cfg.d_model), "ln2": _ln_spec(cfg.d_model)}
        dec_layer = {"attn": attn.attn_pspecs(cfg),
                     "xattn": attn.attn_pspecs(cfg, cross=True),
                     "ffn": _dense_ffn_pspecs(cfg),
                     "ln1": _ln_spec(cfg.d_model), "ln2": _ln_spec(cfg.d_model),
                     "ln3": _ln_spec(cfg.d_model)}
        return {
            "embed": PSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed")),
            "enc_layers": stack_specs(enc_layer, cfg.n_enc_layers),
            "dec_layers": stack_specs(dec_layer, cfg.n_layers),
            "ln_enc": _ln_spec(cfg.d_model),
            "ln_dec": _ln_spec(cfg.d_model),
        }

    def encode(self, params, frames):
        """frames [B, enc_len, D] — precomputed (conv frontend stub)."""
        cfg = self.cfg
        b, s, d = frames.shape
        pos = _sinusoid_at(jnp.arange(s), d).astype(frames.dtype)
        x = frames + pos[None]

        def layer_fn(x, lp):
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = attn.project_qkv(lp["attn"], h,
                                       jnp.arange(s)[None], cfg,
                                       rope=False, shd=self.shd)
            o = attn.full_attention(q, k, v)
            x = x + jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"])
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            x = x + swiglu(h, lp["ffn"]["w_gate"], lp["ffn"]["w_up"],
                           lp["ffn"]["w_down"], self.shd)
            return x, None

        x, _ = jax.lax.scan(layer_fn, x, params["enc_layers"])
        return rms_norm(x, params["ln_enc"], cfg.norm_eps)

    def _dec_layer_full(self, lp, x, enc_out, positions):
        cfg = self.cfg
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = attn.project_qkv(lp["attn"], h, positions, cfg,
                                   rope=False, shd=self.shd)
        o = attn.causal_attention(q, k, v, shd=self.shd)
        x = x + jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"])
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, lp["xattn"]["wq"])
        xk = jnp.einsum("bpd,dhk->bphk", enc_out, lp["xattn"]["wk"])
        xv = jnp.einsum("bpd,dhk->bphk", enc_out, lp["xattn"]["wv"])
        o = attn.full_attention(q, xk, xv)
        x = x + jnp.einsum("bshk,hkd->bsd", o, lp["xattn"]["wo"])
        h = rms_norm(x, lp["ln3"], cfg.norm_eps)
        x = x + swiglu(h, lp["ffn"]["w_gate"], lp["ffn"]["w_up"],
                       lp["ffn"]["w_down"], self.shd)
        return x, (k, v, xk, xv)

    def _embed_dec(self, params, tokens, positions):
        if tokens.shape[-1] == 1:
            x = self.shd.embed_lookup(params["embed"],
                                      tokens[:, 0])[:, None, :]
        else:
            x = params["embed"][tokens]
        return x + _sinusoid_at(positions, self.cfg.d_model).astype(x.dtype)

    def train_loss(self, params, batch):
        cfg = self.cfg
        enc_out = self.encode(params, batch["frames"])
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.arange(s)[None]
        x = self._embed_dec(params, tokens, positions)

        def body(x, lp):
            x, _ = self._dec_layer_full(lp, x, enc_out, positions)
            return x, None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, params["dec_layers"])
        x = rms_norm(x, params["ln_dec"], cfg.norm_eps)
        logits = self._logits(params, x)
        return self._loss_from_logits(logits, batch["labels"])

    def prefill(self, params, batch):
        cfg = self.cfg
        enc_out = self.encode(params, batch["frames"])
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.arange(s)[None]
        x = self._embed_dec(params, tokens, positions)

        def body(x, lp):
            x, caches = self._dec_layer_full(lp, x, enc_out, positions)
            return x, caches

        x, (ks, vs, xks, xvs) = jax.lax.scan(body, x, params["dec_layers"])
        x = rms_norm(x, params["ln_dec"], cfg.norm_eps)
        logits = self._logits(params, x[:, -1])
        state = {"k": self.shd(ks, None, "batch", "kv_seq", None, None),
                 "v": self.shd(vs, None, "batch", "kv_seq", None, None),
                 "xk": xks, "xv": xvs,
                 "lengths": jnp.full((b,), s, jnp.int32)}
        return logits, state

    def decode_step(self, params, state, tokens):
        cfg = self.cfg
        lengths = state["lengths"]
        x = self._embed_dec(params, tokens[:, None], lengths[:, None])

        def layer_fn(x, inp):
            lp, k_c, v_c, xk, xv = inp
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k_new, v_new = attn.project_qkv(lp["attn"], h,
                                               lengths[:, None], cfg,
                                               rope=False)
            k_c = attn.cache_write(k_c, k_new, lengths, aligned=self.aligned)
            v_c = attn.cache_write(v_c, v_new, lengths, aligned=self.aligned)
            o = attn.decode_attention(q, k_c, v_c, lengths + 1,
                                      shd=self.shd)
            x = x + jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"])
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", h, lp["xattn"]["wq"])
            o = attn.full_attention(q, xk, xv)
            x = x + jnp.einsum("bshk,hkd->bsd", o, lp["xattn"]["wo"])
            h = rms_norm(x, lp["ln3"], cfg.norm_eps)
            x = x + swiglu(h, lp["ffn"]["w_gate"], lp["ffn"]["w_up"],
                           lp["ffn"]["w_down"])
            return x, (k_c, v_c)

        x, (ks, vs) = jax.lax.scan(
            layer_fn, x, (params["dec_layers"], state["k"], state["v"],
                          state["xk"], state["xv"]))
        x = rms_norm(x[:, 0], params["ln_dec"], cfg.norm_eps)
        new_state = {**state, "k": ks, "v": vs, "lengths": lengths + 1}
        return self._logits(params, x), new_state

    def decode_state_specs(self, batch, max_len):
        cfg = self.cfg
        L, hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
        kv = PSpec((L, batch, max_len, hkv, hd),
                   ("layers", "batch", "kv_seq", None, None), init="zeros")
        xkv = PSpec((L, batch, cfg.enc_len, hkv, hd),
                    ("layers", "batch", None, "kv_heads", None), init="zeros")
        return {"k": kv, "v": kv, "xk": xkv, "xv": xkv,
                "lengths": _int_spec((batch,), ("batch",))}

    def _add_aux_specs(self, out, shape):
        cfg = self.cfg
        if shape.kind != KIND_DECODE:
            out["frames"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.enc_len, cfg.d_model), jnp.bfloat16)


# ===========================================================================
# factory
# ===========================================================================
FAMILIES = {
    FAMILY_DECODER: DecoderModel,
    FAMILY_MOE: DecoderModel,
    FAMILY_VLM: VLMModel,
    FAMILY_HYBRID: HybridModel,
    FAMILY_RWKV: RWKVModel,
    FAMILY_ENCDEC: EncDecModel,
}


def build_model(cfg: ModelConfig, shd=NOSHARD,
                aligned_decode: bool = False,
                scan_unroll: int = 1,
                kv_dtype: str = "bfloat16") -> Model:
    return FAMILIES[cfg.family](cfg, shd, aligned_decode=aligned_decode,
                                scan_unroll=scan_unroll, kv_dtype=kv_dtype)
