"""MLA paged decode attention — Pallas TPU kernel (paper contribution #1).

Absorbed-form MLA decode reads only the (d_latent + d_rope)-wide latent
cache — the structural source of the paper's 57x memory claim.  Compared
to the GQA paged kernel the page tile is a dense 2-D [page, dl+dr] strip
(no head dim: the latent is shared by all query heads via the
up-projection absorbed into q), so the MXU contraction is
[Hq, dl] x [dl, page] — one matmul per page serving *all* heads.

Grid: (batch, num_pages), flash accumulators in VMEM scratch, block
table resolved by scalar prefetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30


def _mla_kernel(block_tables_ref, lengths_ref, q_lat_ref, q_rope_ref,
                lat_ref, o_ref, m_ref, l_ref, acc_ref,
                *, page: int, n_pages: int, d_latent: int, scale: float):
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
        ql = q_lat_ref[0].astype(jnp.float32)          # [Hq, dl]
        qr = q_rope_ref[0].astype(jnp.float32)         # [Hq, dr]
        lat = lat_ref[0].astype(jnp.float32)           # [page, dl+dr]
        c, kr = lat[:, :d_latent], lat[:, d_latent:]
        s = (ql @ c.T + qr @ kr.T) * scale             # [Hq, page]
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        prob = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(prob, axis=1,
                                                  keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + prob @ c  # [Hq, dl]
        m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def mla_paged_decode(q_lat: jax.Array, q_rope: jax.Array,
                     latent_pages: jax.Array, block_tables: jax.Array,
                     lengths: jax.Array, *, d_latent: int, scale: float,
                     interpret: bool | None = None) -> jax.Array:
    """q_lat [B,Hq,dl], q_rope [B,Hq,dr]; latent_pages [N,page,dl+dr];
    -> ctx [B,Hq,dl] (caller applies W_uv + output projection).

    ``scale`` is the softmax scale, the model's own (its query width
    and any YaRN factor are the caller's to know).
    """
    interpret = resolve_interpret(interpret)
    b, hq, dl = q_lat.shape
    dr = q_rope.shape[-1]
    n, page, dtot = latent_pages.shape
    p_max = block_tables.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, p_max),
        in_specs=[
            pl.BlockSpec((1, hq, dl), lambda bi, pi, bt, ln: (bi, 0, 0)),
            pl.BlockSpec((1, hq, dr), lambda bi, pi, bt, ln: (bi, 0, 0)),
            pl.BlockSpec((1, page, dtot),
                         lambda bi, pi, bt, ln: (bt[bi, pi], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hq, dl),
                               lambda bi, pi, bt, ln: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, dl), jnp.float32),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(_mla_kernel, page=page, n_pages=p_max,
                          d_latent=dl, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, dl), q_lat.dtype),
        interpret=interpret,
    )
    return kernel(block_tables, lengths, q_lat, q_rope, latent_pages)
