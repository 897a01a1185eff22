"""Chunked prefill over a paged KV pool — Pallas TPU kernels.

The unified token-budget step loop (Sarathi-style mixed batches) feeds
prompt *chunks* through these kernels: a fixed-size block of C query
tokens attends with full attention to the request's already-resident
KV pages (block-table indirection, exactly like the paged decode
kernels) and causally to the chunk's own freshly-projected KV, which
arrives as a dense operand and is only scattered into the pool *after*
the layer stack runs.

Each query carries its own absolute position, so one chunk may span
several prompt *gaps* with resumed (pool-resident) content segments
between them: query i at position cpos[i] attends every resident pool
token below its position — excluding the chunk's own not-yet-scattered
positions — plus chunk tokens j <= i.  The scalar-offset entry points
are the contiguous case, cpos = offset + arange(C).

Grid: (batch, [head tile,] n_pages + 1).  Page iterations stream prior
pages through VMEM; iterations at or past the pool limit are
compute-gated (``pl.when``) AND their index map clamps to the last
useful page, so the pipeline elides the redundant DMA (consecutive
identical block indices reuse the staged copy) — a chunk early in the
prompt pays for no empty pages.  The final grid step attends the chunk
against itself with a causal mask and writes the output.  Flash softmax
stats (m, l, acc) persist in VMEM scratch across the sequential page
iterations; the block table and per-row pool limits are scalar-prefetch
operands, so page resolution happens on the scalar core ahead of the
DMA.  The GQA kernels take the whole stacked pool [L, N, page, Hkv, hd]
and the layer index as a third scalar-prefetch operand, and DMA each
page of that layer straight from the stack.

Grouped-query layout: the wrapper lays q out as [B, Hkv, C·g, hd]
(row r of KV head h is query r // g, head h·g + r % g), so every kernel
step is a head-batched 2-D matmul against the page's [page, Hkv, hd]
tile and no value is reshaped across the sublane or lane dimension —
Mosaic refuses such splits (``infer-vector-layout: unsupported shape
cast``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30

# Scoped-VMEM budget for one MLA prefill grid step (double-buffered
# blocks + f32 scratch and temporaries).  The TPU compiler's default
# scoped limit is 16 MiB; the rest is headroom for Mosaic's own spills.
MLA_VMEM_BUDGET = 12 * 2**20


def _pool_limits(chunk_positions: jax.Array, c: int) -> jax.Array:
    """Tokens of resident pool context any query may attend, per row:
    the start position of the trailing contiguous run of valid chunk
    positions (everything from there up is the chunk's own writes, never
    read from the pool).  Drives page-iteration gating and the DMA
    clamp, and reduces to ``offsets`` in the degenerate contiguous
    case."""
    cp = chunk_positions
    valid = cp >= 0
    idx = jnp.arange(c, dtype=cp.dtype)[None, :]
    d = cp - idx                         # constant along a contiguous run
    any_valid = jnp.any(valid, axis=1)
    last = jnp.argmax(jnp.where(valid, idx, -1), axis=1)       # [B]
    d_last = jnp.take_along_axis(d, last[:, None], axis=1)     # [B, 1]
    in_suffix = idx <= last[:, None]
    ok = jnp.where(in_suffix, (d == d_last) & valid, True)
    suffix_all = jnp.flip(
        jnp.cumprod(jnp.flip(ok.astype(jnp.int32), 1), 1), 1).astype(bool)
    big = jnp.asarray(jnp.iinfo(jnp.int32).max, cp.dtype)
    run_min = jnp.min(
        jnp.where(suffix_all & in_suffix & valid, cp, big), axis=1)
    return jnp.where(any_valid, run_min, 0).astype(jnp.int32)


def _contiguous_positions(offsets: jax.Array, c: int) -> jax.Array:
    return (offsets.astype(jnp.int32)[:, None]
            + jnp.arange(c, dtype=jnp.int32)[None, :])


def _clamped_page_idx(page: int, p_max: int):
    """Block-table index map that clamps iterations past the pool limit
    to the last page holding attendable resident tokens, so consecutive
    identical indices elide the DMA entirely."""
    def page_idx(bi, pi, bt, lim):
        last_useful = jnp.maximum((lim[bi] + page - 1) // page - 1, 0)
        return bt[bi, jnp.minimum(pi, jnp.minimum(last_useful, p_max - 1))]
    return page_idx


def _prefill_seg_kernel(
    # scalar-prefetch operands
    block_tables_ref,      # [B, P] int32
    limits_ref,            # [B] int32  (resident pool tokens attendable)
    layer_ref,             # [1] int32  (read by the index maps only)
    # array operands (blocked)
    rpos_ref,              # [1, R, 1] int32  absolute position of each row
    q_ref,                 # [1, Hkv, R, hd]  R = C·g rows per KV head
    kc_ref,                # [1, C, Hkv, hd]  chunk KV (not yet in the pool)
    vc_ref,                # [1, C, Hkv, hd]
    kp_ref,                # [1, page, Hkv, hd]  pool page of layer ``layer``
    vp_ref,                # [1, page, Hkv, hd]
    # outputs
    o_ref,                 # [1, Hkv, R, hd]
    # scratch
    m_ref,                 # [Hkv, R, 1] f32
    l_ref,                 # [Hkv, R, 1] f32
    acc_ref,               # [Hkv, R, hd] f32
    *, page: int, n_prior: int, group: int,
):
    b = pl.program_id(0)
    p = pl.program_id(1)
    limit = limits_ref[b]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _attend(k, v, mask):
        """Online-softmax update against k/v [T, Hkv, hd] under mask
        [R, T]."""
        q = q_ref[0].astype(jnp.float32)                 # [Hkv, R, hd]
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jnp.einsum("hrd,thd->hrt", q, k.astype(jnp.float32)) * scale
        s = jnp.where(mask[None], s, NEG_INF)            # [Hkv, R, T]
        m_prev = m_ref[...]                              # [Hkv, R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        prob = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(prob, axis=-1,
                                                  keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "hrt,thd->hrd", prob, v.astype(jnp.float32))
        m_ref[...] = m_new

    # full attention to resident pool tokens below each query's own
    # position; pages wholly at/past the limit hold nothing attendable
    @pl.when((p < n_prior) & (p * page < limit))
    def _prior():
        rpos = rpos_ref[0]                               # [R, 1]
        keyp = p * page + jax.lax.broadcasted_iota(
            jnp.int32, (rpos.shape[0], page), 1)         # rows identical
        # pool slots this chunk itself will occupy are not yet written:
        # row r of eq marks rpos[r]'s slot; any() folds over the chunk
        excl = jnp.any(keyp == rpos, axis=0, keepdims=True)   # [1, page]
        mask = (keyp < rpos) & jnp.logical_not(excl)          # [R, page]
        _attend(kp_ref[0], vp_ref[0], mask)

    # causal attention within the chunk itself (positions are strictly
    # ascending, so index order == position order), then finalize
    @pl.when(p == n_prior)
    def _chunk():
        r, c = rpos_ref.shape[1], kc_ref.shape[1]
        row = jax.lax.broadcasted_iota(jnp.int32, (r, c), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (r, c), 1)
        # row r is query r // g: key j <= r // g  <=>  j·g <= r
        _attend(kc_ref[0], vc_ref[0], col * group <= row)
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_prefill_segments(q: jax.Array, k_chunk: jax.Array,
                           v_chunk: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           chunk_positions: jax.Array, layer, *,
                           interpret: bool | None = None) -> jax.Array:
    """q [B,C,Hq,hd]; k/v_chunk [B,C,Hkv,hd]; k/v_pages
    [L,N,page,Hkv,hd], the whole stacked pool; block_tables [B,P] int32;
    chunk_positions [B,C] int32; layer, an int32 scalar -> [B,C,Hq,hd].
    Pool pages are read from layer ``layer`` of the stack in place (a
    scalar-prefetch operand of the page index map), never sliced out.

    Query i of request b sits at absolute position chunk_positions[b, i]
    (strictly ascending among valid entries; negative = padding): it
    attends every resident pool token below its position through the
    block table — the chunk's own not-yet-scattered positions excluded —
    plus chunk tokens j <= i.  Every position below a query's that is
    not in the chunk must already be resident (earlier gaps filled,
    resumed segments shared or injected).  The chunk's KV must NOT yet
    be written to the pool; the caller scatters it afterwards.
    """
    interpret = resolve_interpret(interpret)
    b, c, hq, hd = q.shape
    _, n, page, hkv, _ = k_pages.shape
    p_max = block_tables.shape[1]
    g = hq // hkv
    r = c * g
    limits = _pool_limits(chunk_positions, c)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    rpos = jnp.repeat(chunk_positions.astype(jnp.int32), g,
                      axis=1)[:, :, None]                 # [B, R, 1]
    qh = q.reshape(b, c, hkv, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, r, hd)
    page_idx = _clamped_page_idx(page, p_max)
    row_block = lambda bi, pi, bt, lim, li: (bi, 0, 0, 0)   # noqa: E731

    def pool_block(bi, pi, bt, lim, li):
        return (li[0], page_idx(bi, pi, bt, lim), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, p_max + 1),
        in_specs=[
            pl.BlockSpec((1, r, 1), lambda bi, pi, bt, lim, li: (bi, 0, 0)),
            pl.BlockSpec((1, hkv, r, hd), row_block),
            pl.BlockSpec((1, c, hkv, hd), row_block),
            pl.BlockSpec((1, c, hkv, hd), row_block),
            pl.BlockSpec((None, 1, page, hkv, hd), pool_block),
            pl.BlockSpec((None, 1, page, hkv, hd), pool_block),
        ],
        out_specs=pl.BlockSpec((1, hkv, r, hd), row_block),
        scratch_shapes=[
            pltpu.VMEM((hkv, r, 1), jnp.float32),
            pltpu.VMEM((hkv, r, 1), jnp.float32),
            pltpu.VMEM((hkv, r, hd), jnp.float32),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(_prefill_seg_kernel, page=page, n_prior=p_max,
                          group=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, r, hd), q.dtype),
        interpret=interpret,
    )
    out = kernel(block_tables, limits, layer, rpos, qh, k_chunk, v_chunk,
                 k_pages, v_pages)
    return out.reshape(b, hkv, c, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, c, hq, hd)


def paged_prefill_attention(q: jax.Array, k_chunk: jax.Array,
                            v_chunk: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, block_tables: jax.Array,
                            offsets: jax.Array, layer, *,
                            interpret: bool | None = None) -> jax.Array:
    """q [B,C,Hq,hd]; k/v_chunk [B,C,Hkv,hd]; k/v_pages
    [L,N,page,Hkv,hd], the whole stacked pool; block_tables [B,P] int32;
    offsets [B] int32; layer, an int32 scalar -> out [B,C,Hq,hd], over
    layer ``layer``'s pages.

    Query i of request b sits at absolute position offsets[b] + i: it
    attends every pool token < offsets[b] through the block table, plus
    chunk tokens j <= i.  The chunk's KV must NOT yet be written to the
    pool (it is passed densely) — the caller scatters it afterwards via
    ``PagedKVCache.write_chunk``.  The contiguous case of
    :func:`paged_prefill_segments`.
    """
    return paged_prefill_segments(
        q, k_chunk, v_chunk, k_pages, v_pages, block_tables,
        _contiguous_positions(offsets, q.shape[1]), layer,
        interpret=interpret)


# ---------------------------------------------------------------------------
# Absorbed-MLA chunked prefill: queries move into latent space, pages are
# dense [page, dl+dr] strips shared by all heads (same layout as
# kernels/mla_paged_decode.py), so one matmul per page serves every head
# of a head tile.  Heads are tiled over the grid so one step's blocks and
# f32 state fit MLA_VMEM_BUDGET at any chunk size.
# ---------------------------------------------------------------------------
def _mla_head_tile(c: int, hq: int, dl: int, dr: int, page: int,
                   itemsize: int) -> int:
    """Largest head tile whose grid step fits ``MLA_VMEM_BUDGET``: the
    double-buffered q_lat/q_rope/out blocks, the f32 accumulator and the
    f32 temporaries of one update (upcast queries, scores,
    probabilities, the PV product), plus the shared latent blocks.  A
    tile is a divisor of hq that is hq itself or a multiple of 16 (the
    bf16 sublane tile)."""
    dtot = dl + dr
    shared = 2 * (c + page) * dtot * itemsize + 2 * c * dtot * 4

    def fits(th: int) -> bool:
        blocks = 2 * c * th * (2 * dl + dr) * itemsize
        f32 = 4 * c * th * (3 * dl + dr + 2 * max(page, c))
        return shared + blocks + f32 <= MLA_VMEM_BUDGET

    tiles = [t for t in range(hq, 0, -1)
             if hq % t == 0 and (t == hq or t % 16 == 0)]
    return next((t for t in tiles if fits(t)), tiles[-1])


def _mla_prefill_seg_kernel(block_tables_ref, limits_ref, cp_ref,
                            q_lat_ref, q_rope_ref, lat_chunk_ref,
                            lat_page_ref, o_ref, m_ref, l_ref, acc_ref,
                            *, page: int, n_prior: int, chunk: int,
                            d_latent: int, scale: float):
    b = pl.program_id(0)
    p = pl.program_id(2)
    limit = limits_ref[b]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _update(s, c_kv):
        m_prev = m_ref[...]                              # [C, Ht]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        prob = jnp.exp(s - m_new[..., None])             # [C, Ht, T]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(prob, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[..., None] + \
            jnp.einsum("cht,tl->chl", prob, c_kv)
        m_ref[...] = m_new

    @pl.when((p < n_prior) & (p * page < limit))
    def _prior():
        ql = q_lat_ref[0].astype(jnp.float32)            # [C, Ht, dl]
        qr = q_rope_ref[0].astype(jnp.float32)           # [C, Ht, dr]
        lat = lat_page_ref[0].astype(jnp.float32)        # [page, dl+dr]
        c_kv, kr = lat[:, :d_latent], lat[:, d_latent:]
        s = (jnp.einsum("chl,tl->cht", ql, c_kv)
             + jnp.einsum("chr,tr->cht", qr, kr)) * scale
        cpos = cp_ref[0]                                 # [C, 1]
        keyp = p * page + jax.lax.broadcasted_iota(
            jnp.int32, (chunk, page), 1)
        excl = jnp.any(keyp == cpos, axis=0, keepdims=True)   # [1, page]
        mask = (keyp < cpos) & jnp.logical_not(excl)          # [C, page]
        s = jnp.where(mask[:, None, :], s, NEG_INF)
        _update(s, c_kv)

    @pl.when(p == n_prior)
    def _chunk():
        ql = q_lat_ref[0].astype(jnp.float32)
        qr = q_rope_ref[0].astype(jnp.float32)
        lat = lat_chunk_ref[0].astype(jnp.float32)       # [C, dl+dr]
        c_kv, kr = lat[:, :d_latent], lat[:, d_latent:]
        s = (jnp.einsum("chl,tl->cht", ql, c_kv)
             + jnp.einsum("chr,tr->cht", qr, kr)) * scale
        qpos = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1, chunk), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1, chunk), 2)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
        _update(s, c_kv)
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[..., None]
                    ).astype(o_ref.dtype)


def mla_paged_prefill_segments(q_lat: jax.Array, q_rope: jax.Array,
                               lat_chunk: jax.Array,
                               latent_pages: jax.Array,
                               block_tables: jax.Array,
                               chunk_positions: jax.Array, *,
                               d_latent: int, scale: float,
                               interpret: bool | None = None) -> jax.Array:
    """Absorbed-MLA segment prefill (same position semantics as
    ``paged_prefill_segments``): q_lat [B,C,Hq,dl]; q_rope [B,C,Hq,dr];
    lat_chunk [B,C,dl+dr]; latent_pages [N,page,dl+dr];
    chunk_positions [B,C] int32 -> ctx [B,C,Hq,dl] (caller applies
    W_uv + the output projection, as in the paged decode kernel)."""
    interpret = resolve_interpret(interpret)
    b, c, hq, dl = q_lat.shape
    dr = q_rope.shape[-1]
    n, page, dtot = latent_pages.shape
    p_max = block_tables.shape[1]
    th = _mla_head_tile(c, hq, dl, dr, page, q_lat.dtype.itemsize)
    limits = _pool_limits(chunk_positions, c)
    cp3 = chunk_positions.astype(jnp.int32)[:, :, None]   # [B, C, 1]
    page_idx = _clamped_page_idx(page, p_max)
    row_block = lambda bi, hi, pi, bt, lim: (bi, 0, 0)       # noqa: E731
    head_block = lambda bi, hi, pi, bt, lim: (bi, 0, hi, 0)  # noqa: E731

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hq // th, p_max + 1),
        in_specs=[
            pl.BlockSpec((1, c, 1), row_block),
            pl.BlockSpec((1, c, th, dl), head_block),
            pl.BlockSpec((1, c, th, dr), head_block),
            pl.BlockSpec((1, c, dtot), row_block),
            pl.BlockSpec((1, page, dtot),
                         lambda bi, hi, pi, bt, lim:
                         (page_idx(bi, pi, bt, lim), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, th, dl), head_block),
        scratch_shapes=[
            pltpu.VMEM((c, th), jnp.float32),
            pltpu.VMEM((c, th), jnp.float32),
            pltpu.VMEM((c, th, dl), jnp.float32),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(_mla_prefill_seg_kernel, page=page,
                          n_prior=p_max, chunk=c, d_latent=dl,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, hq, dl), q_lat.dtype),
        interpret=interpret,
    )
    return kernel(block_tables, limits, cp3, q_lat, q_rope, lat_chunk,
                  latent_pages)


def mla_paged_prefill(q_lat: jax.Array, q_rope: jax.Array,
                      lat_chunk: jax.Array, latent_pages: jax.Array,
                      block_tables: jax.Array, offsets: jax.Array, *,
                      d_latent: int, scale: float,
                      interpret: bool | None = None) -> jax.Array:
    """q_lat [B,C,Hq,dl]; q_rope [B,C,Hq,dr]; lat_chunk [B,C,dl+dr];
    latent_pages [N,page,dl+dr]; offsets [B] -> ctx [B,C,Hq,dl].  The
    contiguous case of :func:`mla_paged_prefill_segments`."""
    return mla_paged_prefill_segments(
        q_lat, q_rope, lat_chunk, latent_pages, block_tables,
        _contiguous_positions(offsets, q_lat.shape[1]), d_latent=d_latent,
        scale=scale, interpret=interpret)
