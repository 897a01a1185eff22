"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def paged_decode_attention_ref(q, k_pages, v_pages, block_tables,
                               lengths) -> jax.Array:
    """q [B,Hq,hd]; k/v_pages [N,page,Hkv,hd]; block_tables [B,P];
    lengths [B] -> [B,Hq,hd]."""
    b, hq, hd = q.shape
    n, page, hkv, _ = k_pages.shape
    p_max = block_tables.shape[1]
    g = hq // hkv

    def one(qb, bt, ln):
        k = k_pages[bt].reshape(p_max * page, hkv, hd)   # gather pages
        v = v_pages[bt].reshape(p_max * page, hkv, hd)
        qg = qb.reshape(hkv, g, hd).astype(jnp.float32)
        s = jnp.einsum("hgd,thd->hgt", qg, k.astype(jnp.float32))
        s = s / math.sqrt(hd)
        pos = jnp.arange(p_max * page)
        s = jnp.where(pos[None, None, :] < ln, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgt,thd->hgd", p, v.astype(jnp.float32))
        return o.reshape(hq, hd)

    return jax.vmap(one)(q, block_tables, lengths).astype(q.dtype)


def mla_paged_decode_ref(q_lat, q_rope, latent_pages, block_tables,
                         lengths, d_latent: int,
                         scale: float) -> jax.Array:
    """q_lat [B,Hq,dl]; q_rope [B,Hq,dr]; latent_pages [N,page,dl+dr];
    -> ctx [B,Hq,dl] (absorbed-form attention output in latent space)."""
    b, hq, dl = q_lat.shape
    dr = q_rope.shape[-1]
    n, page, dtot = latent_pages.shape
    p_max = block_tables.shape[1]

    def one(ql, qr, bt, ln):
        lat = latent_pages[bt].reshape(p_max * page, dtot)
        c, kr = lat[:, :dl], lat[:, dl:]
        s = (jnp.einsum("hl,tl->ht", ql.astype(jnp.float32),
                        c.astype(jnp.float32))
             + jnp.einsum("hr,tr->ht", qr.astype(jnp.float32),
                          kr.astype(jnp.float32))) * scale
        pos = jnp.arange(p_max * page)
        s = jnp.where(pos[None, :] < ln, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("ht,tl->hl", p, c.astype(jnp.float32))

    return jax.vmap(one)(q_lat, q_rope, block_tables, lengths
                         ).astype(q_lat.dtype)


def paged_prefill_attention_ref(q, k_chunk, v_chunk, k_pages, v_pages,
                                block_tables, offsets) -> jax.Array:
    """Chunked-prefill oracle: q [B,C,Hq,hd] at positions offset+i attends
    pool tokens < offset (via block table) plus chunk tokens j <= i."""
    b, c, hq, hd = q.shape
    n, page, hkv, _ = k_pages.shape
    p_max = block_tables.shape[1]
    g = hq // hkv

    def one(qb, kc, vc, bt, off):
        kp = k_pages[bt].reshape(p_max * page, hkv, hd)
        vp = v_pages[bt].reshape(p_max * page, hkv, hd)
        k = jnp.concatenate([kp, kc], axis=0)            # [T, Hkv, hd]
        v = jnp.concatenate([vp, vc], axis=0)
        qg = qb.reshape(c, hkv, g, hd).astype(jnp.float32)
        s = jnp.einsum("chgd,thd->chgt", qg, k.astype(jnp.float32))
        s = s.reshape(c, hq, -1) / math.sqrt(hd)
        pos = jnp.arange(p_max * page + c)
        prior = pos[None, :] < off                       # pool tokens
        causal = (pos[None, :] >= p_max * page) & \
            (pos[None, :] - p_max * page <= jnp.arange(c)[:, None])
        mask = prior | causal                            # [C, T]
        s = jnp.where(mask[:, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("cht,thd->chd",
                       p.reshape(c, hq, -1),
                       jnp.repeat(v, g, axis=1).astype(jnp.float32))
        return o

    return jax.vmap(one)(q, k_chunk, v_chunk, block_tables, offsets
                         ).astype(q.dtype)


def paged_prefill_segments_ref(q, k_chunk, v_chunk, k_pages, v_pages,
                               block_tables, chunk_positions) -> jax.Array:
    """Segment-prefill oracle: query i of row b sits at absolute position
    ``chunk_positions[b, i]`` (strictly ascending among valid entries;
    negative entries are padding).  It attends every *resident* pool
    token below its position — pool positions t < cpos[i] that are NOT
    one of the chunk's own positions (the chunk's KV arrives densely and
    is only scattered into the pool afterwards) — plus chunk tokens
    j <= i.  Every position below cpos[i] not in cpos must already be
    resident (earlier gaps filled, resumed segments shared/injected).
    With cpos = offset + arange(C) this reduces exactly to
    ``paged_prefill_attention_ref``."""
    b, c, hq, hd = q.shape
    n, page, hkv, _ = k_pages.shape
    p_max = block_tables.shape[1]
    g = hq // hkv

    def one(qb, kc, vc, bt, cpos):
        kp = k_pages[bt].reshape(p_max * page, hkv, hd)
        vp = v_pages[bt].reshape(p_max * page, hkv, hd)
        k = jnp.concatenate([kp, kc], axis=0)            # [T, Hkv, hd]
        v = jnp.concatenate([vp, vc], axis=0)
        qg = qb.reshape(c, hkv, g, hd).astype(jnp.float32)
        s = jnp.einsum("chgd,thd->chgt", qg, k.astype(jnp.float32))
        s = s.reshape(c, hq, -1) / math.sqrt(hd)
        pos = jnp.arange(p_max * page + c)
        own = jnp.any(pos[None, :] == cpos[:, None], axis=0)   # [T]
        prior = (pos[None, :] < cpos[:, None]) & ~own[None, :]
        causal = (pos[None, :] >= p_max * page) & \
            (pos[None, :] - p_max * page <= jnp.arange(c)[:, None])
        mask = prior | causal                            # [C, T]
        s = jnp.where(mask[:, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("cht,thd->chd",
                       p.reshape(c, hq, -1),
                       jnp.repeat(v, g, axis=1).astype(jnp.float32))
        return o

    return jax.vmap(one)(q, k_chunk, v_chunk, block_tables, chunk_positions
                         ).astype(q.dtype)


def mla_paged_prefill_ref(q_lat, q_rope, lat_chunk, latent_pages,
                          block_tables, offsets, d_latent: int,
                          scale: float) -> jax.Array:
    """Absorbed-MLA chunked-prefill oracle -> ctx [B,C,Hq,dl]."""
    b, c, hq, dl = q_lat.shape
    dr = q_rope.shape[-1]
    n, page, dtot = latent_pages.shape
    p_max = block_tables.shape[1]

    def one(ql, qr, lc, bt, off):
        lat = jnp.concatenate(
            [latent_pages[bt].reshape(p_max * page, dtot), lc], axis=0)
        c_kv, kr = lat[:, :dl], lat[:, dl:]
        s = (jnp.einsum("chl,tl->cht", ql.astype(jnp.float32),
                        c_kv.astype(jnp.float32))
             + jnp.einsum("chr,tr->cht", qr.astype(jnp.float32),
                          kr.astype(jnp.float32))) * scale
        pos = jnp.arange(p_max * page + c)
        prior = pos[None, :] < off
        causal = (pos[None, :] >= p_max * page) & \
            (pos[None, :] - p_max * page <= jnp.arange(c)[:, None])
        s = jnp.where((prior | causal)[:, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("cht,tl->chl", p, c_kv.astype(jnp.float32))

    return jax.vmap(one)(q_lat, q_rope, lat_chunk, block_tables, offsets
                         ).astype(q_lat.dtype)


def mla_paged_prefill_segments_ref(q_lat, q_rope, lat_chunk, latent_pages,
                                   block_tables, chunk_positions,
                                   d_latent: int,
                                   scale: float) -> jax.Array:
    """Absorbed-MLA segment-prefill oracle (same position semantics as
    ``paged_prefill_segments_ref``) -> ctx [B,C,Hq,dl]."""
    b, c, hq, dl = q_lat.shape
    dr = q_rope.shape[-1]
    n, page, dtot = latent_pages.shape
    p_max = block_tables.shape[1]

    def one(ql, qr, lc, bt, cpos):
        lat = jnp.concatenate(
            [latent_pages[bt].reshape(p_max * page, dtot), lc], axis=0)
        c_kv, kr = lat[:, :dl], lat[:, dl:]
        s = (jnp.einsum("chl,tl->cht", ql.astype(jnp.float32),
                        c_kv.astype(jnp.float32))
             + jnp.einsum("chr,tr->cht", qr.astype(jnp.float32),
                          kr.astype(jnp.float32))) * scale
        pos = jnp.arange(p_max * page + c)
        own = jnp.any(pos[None, :] == cpos[:, None], axis=0)
        prior = (pos[None, :] < cpos[:, None]) & ~own[None, :]
        causal = (pos[None, :] >= p_max * page) & \
            (pos[None, :] - p_max * page <= jnp.arange(c)[:, None])
        s = jnp.where((prior | causal)[:, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("cht,tl->chl", p, c_kv.astype(jnp.float32))

    return jax.vmap(one)(q_lat, q_rope, lat_chunk, block_tables,
                         chunk_positions).astype(q_lat.dtype)


def flash_prefill_ref(q, k, v) -> jax.Array:
    """Causal attention oracle. q [B,S,Hq,hd], k/v [B,S,Hkv,hd]."""
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, s, hkv, g, hd).astype(jnp.float32)
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    sc = sc / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask[None, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(b, s, hq, hd).astype(q.dtype)


def paged_decode_attention_int8_ref(q, k_pages, v_pages, k_scales,
                                    v_scales, block_tables, lengths):
    """Dequantize-then-attend oracle for the int8 paged kernel."""
    k = k_pages.astype(jnp.float32) * k_scales.astype(jnp.float32)
    v = v_pages.astype(jnp.float32) * v_scales.astype(jnp.float32)
    return paged_decode_attention_ref(q, k.astype(q.dtype),
                                      v.astype(q.dtype),
                                      block_tables, lengths)
