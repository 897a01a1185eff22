"""Paged decode attention — Pallas TPU kernel.

The serving-engine hot spot: one query per request attends over its KV
blocks scattered across a paged pool, indirected by a block table.  This
is the ragged-batch decode fast path the paper's Tier-0 block layout maps
onto (PagedAttention-compatible, §III-B Tier 0).

TPU adaptation (vs the CUDA original): the block table is a
*scalar-prefetch* operand — Pallas resolves each grid step's page index
on the scalar core before the DMA that stages the page into VMEM, so the
gather indirection costs nothing on the vector path.  The pool comes
whole, stacked over layers [L, N, page, Hkv, hd], with the layer index
as one more scalar-prefetch operand: the DMA picks page ``bt[b, p]`` of
that layer in place, so a layer's pool is never sliced out (and copied)
ahead of the call.  (The int8 kernel below, which the engine does not
serve, still takes one layer's pool.)  Pages are sized so one (page,
h_kv, hd) tile fits VMEM alongside the query and the flash accumulators;
the MXU sees dense [Hq, hd] x [hd, page] contractions.

Grid: (batch, num_pages) — pages iterate innermost (sequential on TPU),
carrying running flash-softmax stats (m, l, acc) in VMEM scratch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

DEFAULT_PAGE = 64
NEG_INF = -1e30


def _decode_kernel(
    # scalar-prefetch operands
    block_tables_ref,      # [B, P_max] int32
    lengths_ref,           # [B] int32
    layer_ref,             # [1] int32 (read by the index maps only)
    # array operands (blocked)
    q_ref,                 # [1, Hq, hd]
    k_ref,                 # [1, page, Hkv, hd]  page of layer ``layer``
    v_ref,                 # [1, page, Hkv, hd]
    # outputs
    o_ref,                 # [1, Hq, hd]
    # scratch
    m_ref,                 # [Hq, 1] f32
    l_ref,                 # [Hq, 1] f32
    acc_ref,               # [Hq, hd] f32
    *, page: int, n_pages: int,
):
    b = pl.program_id(0)
    p = pl.program_id(1)
    length = lengths_ref[b]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = p * page
    valid_page = start < length

    @pl.when(valid_page)
    def _attend():
        q = q_ref[0].astype(jnp.float32)              # [Hq, hd]
        k = k_ref[0].astype(jnp.float32)              # [page, Hkv, hd]
        v = v_ref[0].astype(jnp.float32)
        hq, hd = q.shape
        hkv = k.shape[1]
        g = hq // hkv
        scale = 1.0 / math.sqrt(hd)
        qg = q.reshape(hkv, g, hd)
        s = jnp.einsum("hgd,thd->hgt", qg, k) * scale  # [Hkv, G, page]
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
        s = jnp.where(pos < length, s, NEG_INF)
        s = s.reshape(hq, page)
        m_prev = m_ref[...]                            # [Hq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        prob = jnp.exp(s - m_new)                      # [Hq, page]
        l_ref[...] = l_ref[...] * alpha + \
            jnp.sum(prob, axis=1, keepdims=True)
        pv = jnp.einsum("hgt,thd->hgd", prob.reshape(hkv, g, page), v)
        acc_ref[...] = acc_ref[...] * alpha + pv.reshape(hq, hd)
        m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, layer, *,
                           interpret: bool | None = None) -> jax.Array:
    """q [B,Hq,hd]; k/v_pages [L,N,page,Hkv,hd], the whole stacked pool;
    block_tables [B,P] int32; lengths [B] int32; layer, an int32 scalar
    -> out [B,Hq,hd], attention over layer ``layer``'s pages.

    The layer index is a scalar-prefetch operand: each grid step DMAs
    page ``bt[b, p]`` of layer ``layer`` straight from the stack, so the
    caller never slices a layer out of the pool (a slice would be
    copied first).

    ``interpret`` defaults through ``backend.resolve_interpret``:
    compiled on TPU, interpreter elsewhere (True forces the Python-grid
    debug path; the serving-grade off-TPU route is the ``xla`` backend
    in ``ops.py``).
    """
    interpret = resolve_interpret(interpret)
    b, hq, hd = q.shape
    _, n, page, hkv, _ = k_pages.shape
    p_max = block_tables.shape[1]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    # scratch: running max / denom / accumulator live in VMEM across the
    # sequential page iterations
    from jax.experimental.pallas import tpu as pltpu
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, p_max),
        in_specs=[
            pl.BlockSpec((1, hq, hd), lambda bi, pi, bt, ln, li: (bi, 0, 0)),
            pl.BlockSpec((None, 1, page, hkv, hd),
                         lambda bi, pi, bt, ln, li:
                         (li[0], bt[bi, pi], 0, 0, 0)),
            pl.BlockSpec((None, 1, page, hkv, hd),
                         lambda bi, pi, bt, ln, li:
                         (li[0], bt[bi, pi], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hq, hd),
                               lambda bi, pi, bt, ln, li: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, hd), jnp.float32),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(_decode_kernel, page=page, n_pages=p_max),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, hd), q.dtype),
        interpret=interpret,
    )
    return kernel(block_tables, lengths, layer, q, k_pages, v_pages)


# ---------------------------------------------------------------------------
# int8-quantized paged decode: pages stored int8 + per-token-head scales;
# dequantization happens in VMEM registers (the HBM->VMEM DMA moves 1-byte
# elements — the traffic halving that the XLA fallback cannot deliver,
# EXPERIMENTS §Perf cell A iter 3).
# ---------------------------------------------------------------------------
def _decode_kernel_int8(
    block_tables_ref, lengths_ref,
    q_ref,                 # [1, Hq, hd]
    k_ref, v_ref,          # [1, page, Hkv, hd] int8
    ks_ref, vs_ref,        # [1, page, Hkv, 1] scales
    o_ref,
    m_ref, l_ref, acc_ref,
    *, page: int, n_pages: int,
):
    b = pl.program_id(0)
    p = pl.program_id(1)
    length = lengths_ref[b]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = p * page

    @pl.when(start < length)
    def _attend():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32) * ks_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32) * vs_ref[0].astype(jnp.float32)
        hq, hd = q.shape
        hkv = k.shape[1]
        g = hq // hkv
        scale = 1.0 / math.sqrt(hd)
        qg = q.reshape(hkv, g, hd)
        s = jnp.einsum("hgd,thd->hgt", qg, k) * scale
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
        s = jnp.where(pos < length, s, NEG_INF)
        s = s.reshape(hq, page)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        prob = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(prob, axis=1,
                                                  keepdims=True)
        pv = jnp.einsum("hgt,thd->hgd", prob.reshape(hkv, g, page), v)
        acc_ref[...] = acc_ref[...] * alpha + pv.reshape(hq, hd)
        m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_int8(q, k_pages, v_pages, k_scales, v_scales,
                                block_tables, lengths, *,
                                interpret: bool | None = None):
    """q [B,Hq,hd]; k/v_pages int8 [N,page,Hkv,hd]; scales
    [N,page,Hkv,1]; -> [B,Hq,hd]."""
    interpret = resolve_interpret(interpret)
    b, hq, hd = q.shape
    n, page, hkv, _ = k_pages.shape
    p_max = block_tables.shape[1]
    from jax.experimental.pallas import tpu as pltpu
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, p_max),
        in_specs=[
            pl.BlockSpec((1, hq, hd), lambda bi, pi, bt, ln: (bi, 0, 0)),
            pl.BlockSpec((1, page, hkv, hd),
                         lambda bi, pi, bt, ln: (bt[bi, pi], 0, 0, 0)),
            pl.BlockSpec((1, page, hkv, hd),
                         lambda bi, pi, bt, ln: (bt[bi, pi], 0, 0, 0)),
            pl.BlockSpec((1, page, hkv, 1),
                         lambda bi, pi, bt, ln: (bt[bi, pi], 0, 0, 0)),
            pl.BlockSpec((1, page, hkv, 1),
                         lambda bi, pi, bt, ln: (bt[bi, pi], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hq, hd),
                               lambda bi, pi, bt, ln: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, hd), jnp.float32),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(_decode_kernel_int8, page=page, n_pages=p_max),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, hd), q.dtype),
        interpret=interpret,
    )
    return kernel(block_tables, lengths, q, k_pages, v_pages,
                  k_scales, v_scales)
