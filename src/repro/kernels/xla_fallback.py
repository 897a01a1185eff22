"""Compiled XLA fallbacks for the paged attention ops.

Off-TPU the Pallas kernels only run in *interpret mode* — a Python-
driven grid that re-enters the interpreter per (batch, page) step and
dominated replay wall-clock on CPU (PR 1/PR 2 measurement artifacts).
These are production-shape, jitted pure-``jax.numpy`` implementations of
the same contracts: the block-table indirection becomes one batched
gather over the pool (``jnp.take`` — XLA fuses it into the attention
computation), masking replaces the grid's page gating, and the softmax
is dense over the gathered window.  No Python runs per page.

Numerics match the ``ref.py`` oracles (same contraction order, f32
accumulation, the shared ``NEG_INF`` mask value) — the oracles remain
the test ground truth; these are their promotion into the serving path,
selected by ``kernels/backend.py`` (the off-TPU default).

Trade-off vs the Pallas path: the gather materializes each request's
full ``[P_max * page]`` KV window, so peak memory scales with the
padded block-table width rather than the VMEM-resident single page of
the flash-accumulator kernels — the right trade everywhere except the
TPU, where the compiled Pallas kernels stay the default.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.ref import NEG_INF


def _layer_pages(pages: jax.Array, layer, block_tables: jax.Array):
    """Pages ``block_tables`` of layer ``layer`` of a stacked pool
    [L, N, ...], gathered straight from the stack (no layer slice first):
    [B, P, ...].  Out-of-range ids clamp into the pool."""
    return pages.at[layer, block_tables].get(mode="clip")


@jax.jit
def paged_decode_attention_xla(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, block_tables: jax.Array,
                               lengths: jax.Array, layer) -> jax.Array:
    """q [B,Hq,hd]; k/v_pages [L,N,page,Hkv,hd]; block_tables [B,P]
    int32; lengths [B] int32; layer int32 -> [B,Hq,hd].  GQA/MHA/MQA via
    head grouping."""
    b, hq, hd = q.shape
    _, _, page, hkv, _ = k_pages.shape
    p_max = block_tables.shape[1]
    g = hq // hkv
    t = p_max * page

    # one batched gather per pool: [B, P, page, Hkv, hd] -> [B, T, Hkv, hd]
    k = _layer_pages(k_pages, layer, block_tables).reshape(b, t, hkv, hd)
    v = _layer_pages(v_pages, layer, block_tables).reshape(b, t, hkv, hd)
    qg = q.reshape(b, hkv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bhgd,bthd->bhgt", qg, k.astype(jnp.float32))
    s = s / math.sqrt(hd)
    pos = jnp.arange(t)
    s = jnp.where(pos[None, None, None, :] < lengths[:, None, None, None],
                  s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgt,bthd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, hd).astype(q.dtype)


@jax.jit
def paged_decode_attention_int8_xla(q, k_pages, v_pages, k_scales,
                                    v_scales, block_tables, lengths):
    """int8 pages + per-token-head scales: gather, dequantize, attend.
    (The DMA-traffic halving of the int8 Pallas kernel does not apply —
    XLA dequantizes in registers after a full-width gather.)"""
    b = q.shape[0]
    page, hkv, hd = k_pages.shape[1:]
    t = block_tables.shape[1] * page
    ks = jnp.take(k_scales, block_tables, axis=0, mode="clip").reshape(b, t, hkv, 1)
    vs = jnp.take(v_scales, block_tables, axis=0, mode="clip").reshape(b, t, hkv, 1)
    k = jnp.take(k_pages, block_tables, axis=0, mode="clip").reshape(b, t, hkv, hd)
    v = jnp.take(v_pages, block_tables, axis=0, mode="clip").reshape(b, t, hkv, hd)
    k = k.astype(jnp.float32) * ks.astype(jnp.float32)
    v = v.astype(jnp.float32) * vs.astype(jnp.float32)

    hq = q.shape[1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bhgd,bthd->bhgt", qg, k) / math.sqrt(hd)
    pos = jnp.arange(t)
    s = jnp.where(pos[None, None, None, :] < lengths[:, None, None, None],
                  s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgt,bthd->bhgd", p, v)
    return o.reshape(b, hq, hd).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("d_latent", "scale"))
def mla_paged_decode_xla(q_lat: jax.Array, q_rope: jax.Array,
                         latent_pages: jax.Array, block_tables: jax.Array,
                         lengths: jax.Array, *, d_latent: int,
                         scale: float) -> jax.Array:
    """Absorbed-MLA decode over latent pages: q_lat [B,Hq,dl];
    q_rope [B,Hq,dr]; latent_pages [N,page,dl+dr] -> ctx [B,Hq,dl]."""
    b, hq, dl = q_lat.shape
    dr = q_rope.shape[-1]
    _, page, dtot = latent_pages.shape
    t = block_tables.shape[1] * page

    lat = jnp.take(latent_pages, block_tables, axis=0, mode="clip").reshape(b, t, dtot)
    lat = lat.astype(jnp.float32)
    c, kr = lat[..., :d_latent], lat[..., d_latent:]
    s = (jnp.einsum("bhl,btl->bht", q_lat.astype(jnp.float32), c)
         + jnp.einsum("bhr,btr->bht", q_rope.astype(jnp.float32), kr)) * scale
    pos = jnp.arange(t)
    s = jnp.where(pos[None, None, :] < lengths[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,btl->bhl", p, c).astype(q_lat.dtype)


@jax.jit
def paged_prefill_attention_xla(q: jax.Array, k_chunk: jax.Array,
                                v_chunk: jax.Array, k_pages: jax.Array,
                                v_pages: jax.Array, block_tables: jax.Array,
                                offsets: jax.Array, layer) -> jax.Array:
    """Chunked prefill: q [B,C,Hq,hd] at absolute positions offset+i
    attends pool tokens < offset (block-table gather from layer
    ``layer`` of the stacked pool [L,N,page,Hkv,hd]) plus chunk tokens
    j <= i.  The chunk KV is dense — not yet scattered into the pool."""
    b, c, hq, hd = q.shape
    _, _, page, hkv, _ = k_pages.shape
    p_max = block_tables.shape[1]
    g = hq // hkv
    t_prior = p_max * page

    kp = _layer_pages(k_pages, layer, block_tables).reshape(
        b, t_prior, hkv, hd)
    vp = _layer_pages(v_pages, layer, block_tables).reshape(
        b, t_prior, hkv, hd)
    k = jnp.concatenate([kp, k_chunk], axis=1)       # [B, T, Hkv, hd]
    v = jnp.concatenate([vp, v_chunk], axis=1)
    qg = q.reshape(b, c, hkv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bchgd,bthd->bchgt", qg, k.astype(jnp.float32))
    s = s / math.sqrt(hd)
    pos = jnp.arange(t_prior + c)
    # pool tokens < offset, plus causal within the chunk
    prior = pos[None, None, :] < offsets[:, None, None]        # [B, 1, T]
    causal = (pos[None, None, :] >= t_prior) & \
        (pos[None, None, :] - t_prior <= jnp.arange(c)[None, :, None])
    mask = prior | causal                                      # [B, C, T]
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bchgt,bthd->bchgd", p, v.astype(jnp.float32))
    return o.reshape(b, c, hq, hd).astype(q.dtype)


@jax.jit
def paged_prefill_segments_xla(q: jax.Array, k_chunk: jax.Array,
                               v_chunk: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, block_tables: jax.Array,
                               chunk_positions: jax.Array,
                               layer) -> jax.Array:
    """Segment prefill: query i of row b sits at absolute position
    ``chunk_positions[b, i]`` (ascending valid entries; negative =
    padding) and attends every resident pool token of layer ``layer``
    (stacked pool [L,N,page,Hkv,hd]) below its position — excluding the
    chunk's own not-yet-scattered positions — plus chunk tokens j <= i.
    Generalizes ``paged_prefill_attention_xla`` to a chunk spanning
    multiple prompt gaps with resumed (pool-resident) segments between
    them."""
    b, c, hq, hd = q.shape
    _, _, page, hkv, _ = k_pages.shape
    p_max = block_tables.shape[1]
    g = hq // hkv
    t_prior = p_max * page

    kp = _layer_pages(k_pages, layer, block_tables).reshape(
        b, t_prior, hkv, hd)
    vp = _layer_pages(v_pages, layer, block_tables).reshape(
        b, t_prior, hkv, hd)
    k = jnp.concatenate([kp, k_chunk], axis=1)       # [B, T, Hkv, hd]
    v = jnp.concatenate([vp, v_chunk], axis=1)
    qg = q.reshape(b, c, hkv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bchgd,bthd->bchgt", qg, k.astype(jnp.float32))
    s = s / math.sqrt(hd)
    pos = jnp.arange(t_prior + c)
    own = jnp.any(pos[None, None, :] == chunk_positions[:, :, None],
                  axis=1)                                      # [B, T]
    prior = (pos[None, None, :] < chunk_positions[:, :, None]) \
        & ~own[:, None, :]                                     # [B, C, T]
    causal = (pos[None, None, :] >= t_prior) & \
        (pos[None, None, :] - t_prior <= jnp.arange(c)[None, :, None])
    mask = prior | causal
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bchgt,bthd->bchgd", p, v.astype(jnp.float32))
    return o.reshape(b, c, hq, hd).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("d_latent", "scale"))
def mla_paged_prefill_segments_xla(q_lat: jax.Array, q_rope: jax.Array,
                                   lat_chunk: jax.Array,
                                   latent_pages: jax.Array,
                                   block_tables: jax.Array,
                                   chunk_positions: jax.Array, *,
                                   d_latent: int,
                                   scale: float) -> jax.Array:
    """Absorbed-MLA segment prefill (same position semantics as
    ``paged_prefill_segments_xla``) -> ctx [B,C,Hq,dl]."""
    b, c, hq, dl = q_lat.shape
    dr = q_rope.shape[-1]
    _, page, dtot = latent_pages.shape
    t_prior = block_tables.shape[1] * page

    lat_p = jnp.take(latent_pages, block_tables,
                     axis=0, mode="clip").reshape(b, t_prior, dtot)
    lat = jnp.concatenate([lat_p, lat_chunk], axis=1).astype(jnp.float32)
    c_kv, kr = lat[..., :d_latent], lat[..., d_latent:]
    s = (jnp.einsum("bchl,btl->bcht", q_lat.astype(jnp.float32), c_kv)
         + jnp.einsum("bchr,btr->bcht", q_rope.astype(jnp.float32),
                      kr)) * scale
    pos = jnp.arange(t_prior + c)
    own = jnp.any(pos[None, None, :] == chunk_positions[:, :, None], axis=1)
    prior = (pos[None, None, :] < chunk_positions[:, :, None]) \
        & ~own[:, None, :]
    causal = (pos[None, None, :] >= t_prior) & \
        (pos[None, None, :] - t_prior <= jnp.arange(c)[None, :, None])
    s = jnp.where((prior | causal)[:, :, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bcht,btl->bchl", p, c_kv).astype(q_lat.dtype)


@functools.partial(jax.jit, static_argnames=("d_latent", "scale"))
def mla_paged_prefill_xla(q_lat: jax.Array, q_rope: jax.Array,
                          lat_chunk: jax.Array, latent_pages: jax.Array,
                          block_tables: jax.Array, offsets: jax.Array, *,
                          d_latent: int,
                          scale: float) -> jax.Array:
    """Absorbed-MLA chunked prefill: q_lat [B,C,Hq,dl]; q_rope
    [B,C,Hq,dr]; lat_chunk [B,C,dl+dr]; latent_pages [N,page,dl+dr]
    -> ctx [B,C,Hq,dl]."""
    b, c, hq, dl = q_lat.shape
    dr = q_rope.shape[-1]
    _, page, dtot = latent_pages.shape
    t_prior = block_tables.shape[1] * page

    lat_p = jnp.take(latent_pages, block_tables,
                     axis=0, mode="clip").reshape(b, t_prior, dtot)
    lat = jnp.concatenate([lat_p, lat_chunk], axis=1).astype(jnp.float32)
    c_kv, kr = lat[..., :d_latent], lat[..., d_latent:]
    s = (jnp.einsum("bchl,btl->bcht", q_lat.astype(jnp.float32), c_kv)
         + jnp.einsum("bchr,btr->bcht", q_rope.astype(jnp.float32),
                      kr)) * scale
    pos = jnp.arange(t_prior + c)
    prior = pos[None, None, :] < offsets[:, None, None]
    causal = (pos[None, None, :] >= t_prior) & \
        (pos[None, None, :] - t_prior <= jnp.arange(c)[None, :, None])
    s = jnp.where((prior | causal)[:, :, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bcht,btl->bchl", p, c_kv).astype(q_lat.dtype)


@functools.partial(jax.jit, static_argnames=())
def flash_causal_xla(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Dense causal attention (the flash-prefill contract) as one fused
    XLA computation.  q [B,S,Hq,hd], k/v [B,S,Hkv,hd] -> [B,S,Hq,hd]."""
    b, s_len, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, s_len, hkv, g, hd).astype(jnp.float32)
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    sc = sc / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((s_len, s_len), bool))
    sc = jnp.where(mask[None, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(b, s_len, hq, hd).astype(q.dtype)
