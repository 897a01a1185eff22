"""Public kernel ops with unified backend dispatch.

Every paged attention op resolves to one of three backends
(``kernels/backend.py``):

* ``pallas``    — compiled Pallas TPU kernels (the TPU default);
* ``interpret`` — the same kernel bodies on the Pallas interpreter
                  (debug/validation — Python-driven grid, slow);
* ``xla``       — jitted pure-``jax.numpy`` fallbacks
                  (``kernels/xla_fallback.py``; the off-TPU default).

Selection order: ``backend=`` argument > legacy ``interpret=`` boolean
(True -> ``interpret``, False -> ``pallas``) > ``REPRO_KERNEL_BACKEND``
env var > platform default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref, xla_fallback
from repro.kernels.backend import (BACKENDS, default_backend,  # noqa: F401
                                   on_tpu, resolve_backend)
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.mla_paged_decode import mla_paged_decode
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.paged_prefill import (mla_paged_prefill,
                                         mla_paged_prefill_segments,
                                         paged_prefill_attention,
                                         paged_prefill_segments)


# -- jitted Pallas entry points (interpret resolved to a static bool) -------
@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_decode_pallas(q, k_pages, v_pages, block_tables, lengths, layer,
                         interpret: bool):
    return paged_decode_attention(q, k_pages, v_pages, block_tables,
                                  lengths, layer, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "block_q",
                                             "block_k"))
def _flash_causal_pallas(q, k, v, block_q: int, block_k: int,
                         interpret: bool):
    return flash_prefill(q, k, v, block_q=block_q, block_k=block_k,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("d_latent", "scale",
                                             "interpret"))
def _mla_decode_pallas(q_lat, q_rope, latent_pages, block_tables, lengths,
                       d_latent: int, scale: float, interpret: bool):
    return mla_paged_decode(q_lat, q_rope, latent_pages, block_tables,
                            lengths, d_latent=d_latent, scale=scale,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_prefill_pallas(q, k_chunk, v_chunk, k_pages, v_pages,
                          block_tables, offsets, layer, interpret: bool):
    return paged_prefill_attention(q, k_chunk, v_chunk, k_pages, v_pages,
                                   block_tables, offsets, layer,
                                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("d_latent", "scale",
                                             "interpret"))
def _mla_prefill_pallas(q_lat, q_rope, lat_chunk, latent_pages,
                        block_tables, offsets, d_latent: int,
                        scale: float, interpret: bool):
    return mla_paged_prefill(q_lat, q_rope, lat_chunk, latent_pages,
                             block_tables, offsets, d_latent=d_latent,
                             scale=scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_prefill_seg_pallas(q, k_chunk, v_chunk, k_pages, v_pages,
                              block_tables, chunk_positions, layer,
                              interpret: bool):
    return paged_prefill_segments(q, k_chunk, v_chunk, k_pages, v_pages,
                                  block_tables, chunk_positions, layer,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("d_latent", "scale",
                                             "interpret"))
def _mla_prefill_seg_pallas(q_lat, q_rope, lat_chunk, latent_pages,
                            block_tables, chunk_positions, d_latent: int,
                            scale: float, interpret: bool):
    return mla_paged_prefill_segments(q_lat, q_rope, lat_chunk,
                                      latent_pages, block_tables,
                                      chunk_positions, d_latent=d_latent,
                                      scale=scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_decode_int8_pallas(q, k_pages, v_pages, k_scales, v_scales,
                              block_tables, lengths, interpret: bool):
    from repro.kernels.paged_attention import paged_decode_attention_int8
    return paged_decode_attention_int8(q, k_pages, v_pages, k_scales,
                                       v_scales, block_tables, lengths,
                                       interpret=interpret)


GMM_TM = 32


def _gmm_tiling(k: int, n: int) -> tuple:
    """(tm, tk, tn) of the expert grouped matmul: 32-row tiles, since a
    decode step or a 64-token chunk gives each expert only a few rows;
    a 512-deep contraction tile where it divides ``k``; and the widest
    output tile (a multiple of 128 that divides ``n``, else all of
    ``n``) that keeps one weight tile within 2 MiB of bf16."""
    tk = 512 if k > 512 and k % 512 == 0 else k
    fits = [t for t in range(128, n + 1, 128)
            if n % t == 0 and tk * t * 2 <= 2 << 20]
    return GMM_TM, tk, (max(fits) if fits else n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _expert_gmm_pallas(lhs, rhs, group_sizes, layer, interpret: bool):
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    lm, e, k, n = rhs.shape
    m = lhs.shape[0]
    m_pad = -(-m // GMM_TM) * GMM_TM
    if m_pad != m:
        lhs = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    # every MoE layer's experts as one [Lm * E] group list, the groups
    # of the other layers empty: the kernel reads layer ``layer``'s
    # weights in place (a slice of the stack would be copied first)
    sizes = jnp.zeros((lm, e), jnp.int32).at[layer].set(
        group_sizes.astype(jnp.int32)).reshape(lm * e)
    # (the custom-VJP megablox gmm, so training differentiates it)
    out = gmm(lhs, rhs.reshape(lm * e, k, n), sizes, lhs.dtype,
              _gmm_tiling(k, n), None, None, False, interpret)
    return out[:m]


@jax.jit
def _expert_gmm_xla(lhs, rhs, group_sizes, layer):
    w = jax.lax.dynamic_index_in_dim(rhs, layer, keepdims=False)
    return jax.lax.ragged_dot(lhs, w, group_sizes.astype(jnp.int32))


# -- dispatching public ops -------------------------------------------------
def expert_matmul(lhs, rhs, group_sizes, layer, backend: str | None = None,
                  interpret: bool | None = None):
    """Grouped matmul of MoE layer ``layer``: lhs [m, K] rows sorted by
    expert, ``group_sizes`` [E] rows per expert (rows past their sum
    are left undefined), rhs the stacked experts [Lm, E, K, N] ->
    [m, N] in lhs's dtype."""
    be = resolve_backend(backend, interpret)
    if be == "xla":
        return _expert_gmm_xla(lhs, rhs, group_sizes, layer)
    return _expert_gmm_pallas(lhs, rhs, group_sizes, layer,
                              interpret=(be == "interpret"))



def paged_decode(q, k_pages, v_pages, block_tables, lengths, layer,
                 backend: str | None = None, interpret: bool | None = None):
    """Paged decode attention (GQA/MHA/MQA): q [B,Hq,hd] over block-table
    -indirected KV pages of layer ``layer`` (int32 scalar) of the whole
    stacked pool k/v_pages [L,N,page,Hkv,hd] -> [B,Hq,hd].  The layer is
    read in place, never sliced out of the stack."""
    be = resolve_backend(backend, interpret)
    if be == "xla":
        return xla_fallback.paged_decode_attention_xla(
            q, k_pages, v_pages, block_tables, lengths, layer)
    return _paged_decode_pallas(q, k_pages, v_pages, block_tables, lengths,
                                layer, interpret=(be == "interpret"))


def flash_causal(q, k, v, block_q: int = 128, block_k: int = 128,
                 backend: str | None = None, interpret: bool | None = None):
    """Causal prefill attention. q [B,S,Hq,hd], k/v [B,S,Hkv,hd]."""
    be = resolve_backend(backend, interpret)
    if be == "xla":
        return xla_fallback.flash_causal_xla(q, k, v)
    return _flash_causal_pallas(q, k, v, block_q=block_q, block_k=block_k,
                                interpret=(be == "interpret"))


def mla_decode(q_lat, q_rope, latent_pages, block_tables, lengths,
               d_latent: int, scale: float,
               backend: str | None = None, interpret: bool | None = None):
    """Absorbed-MLA paged decode over latent pages -> ctx [B,Hq,dl]."""
    be = resolve_backend(backend, interpret)
    if be == "xla":
        return xla_fallback.mla_paged_decode_xla(
            q_lat, q_rope, latent_pages, block_tables, lengths,
            d_latent=d_latent, scale=scale)
    return _mla_decode_pallas(q_lat, q_rope, latent_pages, block_tables,
                              lengths, d_latent=d_latent, scale=scale,
                              interpret=(be == "interpret"))


def paged_prefill(q, k_chunk, v_chunk, k_pages, v_pages, block_tables,
                  offsets, layer, backend: str | None = None,
                  interpret: bool | None = None):
    """Chunked prefill: full attention to pool tokens < offset (block
    table indirection into layer ``layer`` of the stacked pool
    k/v_pages [L,N,page,Hkv,hd]) + causal attention within the chunk."""
    be = resolve_backend(backend, interpret)
    if be == "xla":
        return xla_fallback.paged_prefill_attention_xla(
            q, k_chunk, v_chunk, k_pages, v_pages, block_tables, offsets,
            layer)
    return _paged_prefill_pallas(q, k_chunk, v_chunk, k_pages, v_pages,
                                 block_tables, offsets, layer,
                                 interpret=(be == "interpret"))


def mla_prefill(q_lat, q_rope, lat_chunk, latent_pages, block_tables,
                offsets, d_latent: int, scale: float,
                backend: str | None = None, interpret: bool | None = None):
    """Absorbed-MLA chunked prefill over latent pages."""
    be = resolve_backend(backend, interpret)
    if be == "xla":
        return xla_fallback.mla_paged_prefill_xla(
            q_lat, q_rope, lat_chunk, latent_pages, block_tables, offsets,
            d_latent=d_latent, scale=scale)
    return _mla_prefill_pallas(q_lat, q_rope, lat_chunk, latent_pages,
                               block_tables, offsets, d_latent=d_latent,
                               scale=scale, interpret=(be == "interpret"))


def paged_prefill_seg(q, k_chunk, v_chunk, k_pages, v_pages, block_tables,
                      chunk_positions, layer, backend: str | None = None,
                      interpret: bool | None = None):
    """Segment prefill: per-query absolute positions (``chunk_positions``
    [B,C] int32, ascending valid entries, negative = padding) so one
    chunk can span multiple prompt gaps with resumed pool-resident
    segments between them.  Queries attend every resident pool token of
    layer ``layer`` (stacked pool k/v_pages [L,N,page,Hkv,hd]) below
    their own position — excluding the chunk's not-yet-scattered
    positions — plus chunk tokens causally."""
    be = resolve_backend(backend, interpret)
    if be == "xla":
        return xla_fallback.paged_prefill_segments_xla(
            q, k_chunk, v_chunk, k_pages, v_pages, block_tables,
            chunk_positions, layer)
    return _paged_prefill_seg_pallas(q, k_chunk, v_chunk, k_pages, v_pages,
                                     block_tables, chunk_positions, layer,
                                     interpret=(be == "interpret"))


def mla_prefill_seg(q_lat, q_rope, lat_chunk, latent_pages, block_tables,
                    chunk_positions, d_latent: int, scale: float,
                    backend: str | None = None,
                    interpret: bool | None = None):
    """Absorbed-MLA segment prefill over latent pages (same position
    semantics as ``paged_prefill_seg``)."""
    be = resolve_backend(backend, interpret)
    if be == "xla":
        return xla_fallback.mla_paged_prefill_segments_xla(
            q_lat, q_rope, lat_chunk, latent_pages, block_tables,
            chunk_positions, d_latent=d_latent, scale=scale)
    return _mla_prefill_seg_pallas(q_lat, q_rope, lat_chunk, latent_pages,
                                   block_tables, chunk_positions,
                                   d_latent=d_latent, scale=scale,
                                   interpret=(be == "interpret"))


def paged_decode_int8(q, k_pages, v_pages, k_scales, v_scales,
                      block_tables, lengths, backend: str | None = None,
                      interpret: bool | None = None):
    """int8-paged decode (per-token-head scales, in-register dequant)."""
    be = resolve_backend(backend, interpret)
    if be == "xla":
        return xla_fallback.paged_decode_attention_int8_xla(
            q, k_pages, v_pages, k_scales, v_scales, block_tables, lengths)
    return _paged_decode_int8_pallas(q, k_pages, v_pages, k_scales,
                                     v_scales, block_tables, lengths,
                                     interpret=(be == "interpret"))


# re-export oracles for test convenience
paged_decode_ref = ref.paged_decode_attention_ref
flash_causal_ref = ref.flash_prefill_ref
mla_decode_ref = ref.mla_paged_decode_ref
paged_prefill_ref = ref.paged_prefill_attention_ref
mla_prefill_ref = ref.mla_paged_prefill_ref
paged_prefill_seg_ref = ref.paged_prefill_segments_ref
mla_prefill_seg_ref = ref.mla_paged_prefill_segments_ref
