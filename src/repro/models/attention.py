"""Attention variants: MHA / GQA / MQA (head-grouped) and MLA (latent).

Prefill/training uses query-chunked exact causal attention (bounded score
memory at 32k+ context, flash-style).  Decode attends one query against a
pre-allocated KV cache with per-request length masks; the cache layout is
chosen for sequence sharding over the ``model`` mesh axis (flash-decoding
style — tiny softmax-stat collectives instead of KV all-gathers, see
DESIGN.md §Decode-sharding).

MLA (paper §II-B / §III-A) caches only ``[c_kv ; k_rope]`` per token —
(d_latent + d_rope) bytes * p — and decodes in the absorbed form, so the
57x memory claim is structural in the cache layout here.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.common import (NOSHARD, PSpec, apply_rope, rms_norm,
                                 yarn_freqs)

NEG_INF = -1e30
SCORES_BF16 = False   # set True to store score buffers at bf16 (perf flag)
STATIC_CAUSAL = False  # unroll q-chunks with static growing KV ranges:
                       # true-causal flops (2x less than masked-rectangle)


# ---------------------------------------------------------------------------
# Parameter declarations
# ---------------------------------------------------------------------------
def head_mask(cfg: ModelConfig, dtype=jnp.bfloat16):
    """[layout_q_heads, 1] multiplicative mask zeroing padded heads.
    Layout: per GQA group, real heads first, pads last — preserves the
    q->kv mapping i // layout_q_group."""
    hp, hkv = cfg.layout_q_heads, max(cfg.n_kv_heads, 1)
    if hp == cfg.n_heads:
        return None
    g, gp = cfg.q_group, cfg.layout_q_group
    idx = jnp.arange(hp)
    return ((idx % gp) < g).astype(dtype)[:, None]


def attn_pspecs(cfg: ModelConfig, *, cross: bool = False) -> Dict[str, PSpec]:
    d, hq, hkv, hd = (cfg.d_model, cfg.layout_q_heads,
                      max(cfg.n_kv_heads, 1), cfg.hd)
    scale = 0.02
    out = {
        "wq": PSpec((d, hq, hd), ("embed", "heads", None), scale=scale),
        "wk": PSpec((d, hkv, hd), ("embed", "kv_heads", None), scale=scale),
        "wv": PSpec((d, hkv, hd), ("embed", "kv_heads", None), scale=scale),
        "wo": PSpec((hq, hd, d), ("heads", None, "embed"),
                    scale=scale / math.sqrt(2 * max(cfg.n_layers, 1))),
    }
    if cfg.qkv_bias and not cross:
        out.update(
            bq=PSpec((hq, hd), ("heads", None), init="zeros"),
            bk=PSpec((hkv, hd), ("kv_heads", None), init="zeros"),
            bv=PSpec((hkv, hd), ("kv_heads", None), init="zeros"))
    return out


def mla_pspecs(cfg: ModelConfig) -> Dict[str, PSpec]:
    d, hq, hd = cfg.d_model, cfg.n_heads, cfg.hd
    dl, dr = cfg.d_latent, cfg.d_rope
    out = {
        "wq": PSpec((d, hq, hd + dr), ("embed", "heads", None)),
        "w_dkv": PSpec((d, dl), ("embed", "latent")),
        "w_kr": PSpec((d, dr), ("embed", None)),
        "w_uk": PSpec((dl, hq, hd), ("latent", "heads", None)),
        "w_uv": PSpec((dl, hq, hd), ("latent", "heads", None)),
        "wo": PSpec((hq, hd, d), ("heads", None, "embed"),
                    scale=0.02 / math.sqrt(2 * max(cfg.n_layers, 1))),
    }
    if cfg.latent_norm:
        out["kv_norm"] = PSpec((dl,), ("latent",), init="ones")
    return out


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------
def project_qkv(p: Dict, x: jax.Array, positions: jax.Array,
                cfg: ModelConfig, *, rope: bool = True, shd=NOSHARD):
    """x [B,S,D] -> q [B,S,Hq,hd], k/v [B,S,Hkv,hd] (RoPE applied)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    # q is TP-sharded on heads; k/v inherit sharding from the weights
    # (replicated when h_kv doesn't divide TP — constraining them onto
    # padded shards forces replicate-and-repartition resharding storms).
    q = shd(q, "batch", "seq", "heads", None)
    if rope and cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# Exact causal attention, query-chunked (prefill / training)
# ---------------------------------------------------------------------------
def _grouped_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q [B,Sq,Hq,hd], k [B,Sk,Hkv,hd] -> scores [B,Hkv,G,Sq,Sk] fp32."""
    b, sq, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    return s / math.sqrt(hd)


def _grouped_out(probs: jax.Array, v: jax.Array) -> jax.Array:
    """probs [B,Hkv,G,Sq,Sk] fp32, v [B,Sk,Hkv,hd] -> [B,Sq,Hq,hd]."""
    b, hkv, g, sq, sk = probs.shape
    o = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return o.reshape(b, sq, hkv * g, -1)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     *, q_offset: int = 0, chunk: int = 512,
                     scale: Optional[float] = None,
                     shd=NOSHARD) -> jax.Array:
    """Exact causal attention with bounded memory: scan over query chunks,
    each chunk softmaxes over the full (masked) key range.

    GQA keys/values are expanded to query heads *once* (a single reshard,
    head-sharded thereafter) — grouping inside the chunk loop would force
    an SPMD reshard per chunk when h_kv doesn't divide the TP degree.
    """
    b, sq, hq, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    if hkv != hq:
        g = hq // hkv
        k = shd(jnp.repeat(k, g, axis=2), "batch", "seq", "heads", None)
        v = shd(jnp.repeat(v, g, axis=2), "batch", "seq", "heads", None)
    chunk = min(chunk, sq)
    if sq % chunk != 0:
        chunk = sq          # irregular smoke shapes: single chunk
    nc = sq // chunk
    kpos = jnp.arange(sk)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    def one_chunk(ci, qc):
        # qc [B, chunk, Hq, hd]
        qpos = q_offset + ci * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, k) * scale
        mask = kpos[None, :] <= qpos[:, None]            # [c, Sk]
        if SCORES_BF16:
            # halve score-buffer HBM traffic: buffers live at bf16, the
            # softmax max/sum reductions still run in f32 inside the
            # fused computation
            s = jnp.where(mask[None, None], s.astype(jnp.bfloat16),
                          jnp.bfloat16(NEG_INF))
            p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        else:
            s = jnp.where(mask[None, None], s.astype(jnp.float32),
                          NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)

    if nc == 1:
        return one_chunk(0, q)
    if STATIC_CAUSAL and nc <= 16 and sq == sk and q_offset == 0:
        # unrolled chunks, each attending only k[:, :(ci+1)*chunk] — the
        # strictly-upper rectangle is never computed (true causal cost)
        outs = []
        for ci in range(nc):
            qc = q[:, ci * chunk:(ci + 1) * chunk]
            kend = (ci + 1) * chunk
            kc, vc = k[:, :kend], v[:, :kend]
            s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc) * scale
            qpos = ci * chunk + jnp.arange(chunk)
            mask = jnp.arange(kend)[None, :] <= qpos[:, None]
            if SCORES_BF16:
                s = jnp.where(mask[None, None], s.astype(jnp.bfloat16),
                              jnp.bfloat16(NEG_INF))
                pr = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
            else:
                s = jnp.where(mask[None, None], s.astype(jnp.float32),
                              NEG_INF)
                pr = jax.nn.softmax(s, axis=-1)
            outs.append(jnp.einsum("bhqk,bkhd->bqhd",
                                   pr.astype(vc.dtype), vc))
        return jnp.concatenate(outs, axis=1)
    qs = q.reshape(b, nc, chunk, hq, hd).transpose(1, 0, 2, 3, 4)
    outs = jax.lax.map(lambda args: one_chunk(args[0], args[1]),
                       (jnp.arange(nc), qs))
    # v's head width, which MLA's (hd + d_rope)-wide queries exceed
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, hq, v.shape[-1])


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   kv_mask: Optional[jax.Array] = None) -> jax.Array:
    """Bidirectional (encoder / cross) attention. kv_mask [B,Sk] bool."""
    s = _grouped_scores(q, k)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, None, :], s, NEG_INF)
    return _grouped_out(jax.nn.softmax(s, axis=-1), v)


# ---------------------------------------------------------------------------
# Decode: one query vs a length-masked KV cache
# ---------------------------------------------------------------------------
def cache_write(cache: jax.Array, new: jax.Array, lengths: jax.Array,
                *, aligned: bool = False) -> jax.Array:
    """Write one new token per request at its current length.

    cache [B, S, ...], new [B, 1, ...], lengths [B] int32.

    aligned=True: all requests are at the same position (steady-state
    decode benchmark / dry-run) — a single dynamic_update_slice, which
    SPMD-partitions to an in-place shard write.  aligned=False: ragged
    per-request positions via vmapped dus (lowers to scatter; used by the
    live engine — the TPU fast path for ragged batches is the paged
    attention Pallas kernel, kernels/paged_attention.py).
    """
    if aligned:
        idx = (0, lengths[0]) + (0,) * (cache.ndim - 2)
        return jax.lax.dynamic_update_slice(cache, new, idx)

    def upd(c, n, l):
        idx = (l,) + (0,) * (c.ndim - 1)
        return jax.lax.dynamic_update_slice(c, n, idx)
    return jax.vmap(upd)(cache, new, lengths)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *, shd=NOSHARD) -> jax.Array:
    """q [B,1,Hq,hd]; caches [B,S,Hkv,hd]; lengths [B] = #valid tokens
    (including the newly-written one)."""
    b, _, hq, hd = q.shape
    sk = k_cache.shape[1]
    s = _grouped_scores(q, k_cache)                     # [B,Hkv,G,1,S]
    valid = jnp.arange(sk)[None, :] < lengths[:, None]  # [B,S]
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return _grouped_out(p, v_cache)                     # [B,1,Hq,hd]


# ---------------------------------------------------------------------------
# MLA — latent attention (paper §II-B): cache = [c_kv ; k_rope]
# ---------------------------------------------------------------------------
def mla_rope(x: jax.Array, positions: jax.Array,
             cfg: ModelConfig) -> jax.Array:
    """RoPE on the rope part of MLA queries and keys [B,S,H,dr], with
    YaRN's frequencies where the config scales."""
    freqs = None
    if cfg.yarn_factor > 1:
        freqs = jnp.asarray(yarn_freqs(
            cfg.d_rope, cfg.rope_theta, cfg.yarn_factor,
            cfg.yarn_original_max, cfg.yarn_beta_fast, cfg.yarn_beta_slow))
    return apply_rope(x, positions, cfg.rope_theta, freqs=freqs)


def mla_project(p: Dict, x: jax.Array, positions: jax.Array,
                cfg: ModelConfig, shd=NOSHARD):
    """Returns q_nope [B,S,H,hd], q_rope [B,S,H,dr], latent [B,S,dl+dr].
    The cached latent is ``[c_kv ; k_rope]``, ``c_kv`` normalised first
    where the config has a latent norm."""
    dr = cfg.d_rope
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :-dr], q[..., -dr:]
    q_rope = mla_rope(q_rope, positions, cfg)
    c_kv = jnp.einsum("bsd,dl->bsl", x, p["w_dkv"])
    if cfg.latent_norm:
        c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = jnp.einsum("bsd,dr->bsr", x, p["w_kr"])
    k_rope = mla_rope(k_rope[:, :, None, :], positions, cfg)[:, :, 0, :]
    latent = jnp.concatenate([c_kv, k_rope], axis=-1)   # the cached state
    return q_nope, q_rope, shd(latent, "batch", "seq", None)


def mla_attention_prefill(p: Dict, x: jax.Array, positions: jax.Array,
                          cfg: ModelConfig, *, chunk: int = 512,
                          shd=NOSHARD) -> Tuple[jax.Array, jax.Array]:
    """Naive (non-absorbed) causal MLA for prefill; returns (out, latent)."""
    dl, dr = cfg.d_latent, cfg.d_rope
    q_nope, q_rope, latent = mla_project(p, x, positions, cfg, shd)
    c_kv, k_rope = latent[..., :dl], latent[..., dl:]
    k = jnp.einsum("bsl,lhk->bshk", c_kv, p["w_uk"])
    v = jnp.einsum("bsl,lhk->bshk", c_kv, p["w_uv"])
    # fold the shared rope key into per-head keys / queries
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k, jnp.broadcast_to(k_rope[:, :, None, :], k.shape[:3] + (dr,))],
        axis=-1)
    out = causal_attention(q, k, v, chunk=chunk,
                           scale=cfg.mla_softmax_scale, shd=shd)
    o = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return o, latent


def mla_attention_decode(p: Dict, x: jax.Array, latent_cache: jax.Array,
                         lengths: jax.Array, cfg: ModelConfig,
                         shd=NOSHARD, aligned: bool = False
                         ) -> Tuple[jax.Array, jax.Array]:
    """Absorbed-form decode: queries move into latent space, so attention
    reads only the (d_latent + d_rope)-wide cache — the 57x win.

    latent_cache [B, S, dl+dr] must already contain the new token at index
    lengths-1.  Returns (out [B,1,D], new_latent [B,1,dl+dr]).
    """
    dl, dr = cfg.d_latent, cfg.d_rope
    positions = (lengths - 1)[:, None]
    q_nope, q_rope, new_latent = mla_project(p, x, positions, cfg, shd)
    latent_cache = cache_write(latent_cache, new_latent, lengths - 1,
                               aligned=aligned)
    c_kv, k_rope = latent_cache[..., :dl], latent_cache[..., dl:]
    # absorb W_uk into the query:  q_lat [B,1,H,dl]
    q_lat = jnp.einsum("bshk,lhk->bshl", q_nope, p["w_uk"])
    s = (jnp.einsum("bshl,btl->bhst", q_lat, c_kv)
         + jnp.einsum("bshr,btr->bhst", q_rope, k_rope)).astype(jnp.float32)
    s = s * cfg.mla_softmax_scale
    valid = jnp.arange(latent_cache.shape[1])[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhst,btl->bshl", pr, c_kv)         # [B,1,H,dl]
    out = jnp.einsum("bshl,lhk->bshk", ctx, p["w_uv"])
    o = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return o, latent_cache


# ---------------------------------------------------------------------------
# int8 KV cache (paper §VI: "the sizing formulas accept a precision
# parameter p that can represent quantized formats") — per-token-per-head
# symmetric quantization; scales stored alongside the cache.
# ---------------------------------------------------------------------------
def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x [..., hd] -> (int8 values, f16-ish scale [..., 1])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.bfloat16) * scale).astype(dtype)
