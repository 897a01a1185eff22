"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode (assignment requirement)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.mla_paged_decode import mla_paged_decode
from repro.kernels.paged_attention import paged_decode_attention

RNG = np.random.default_rng(0)


def _mla_scale(dl, dr):
    """A softmax scale for the MLA kernels, which take it from their
    caller: that of a head whose nope part is a quarter of the latent."""
    return 1.0 / (dl // 4 + dr) ** 0.5


def _arr(shape, dtype):
    a = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(a, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,hd,page,pages,layers", [
    (2, 8, 2, 64, 64, 4, 1),
    (3, 4, 4, 32, 32, 3, 1),       # MHA
    (1, 8, 1, 128, 64, 2, 1),      # MQA
    (2, 8, 2, 64, 64, 4, 3),       # each layer of a 3-layer pool
])
def test_paged_decode_sweep(b, hq, hkv, hd, page, pages, layers, dtype):
    """The kernel reads layer l of the stacked pool [L, N, page, Hkv,
    hd]: its output is the single-layer oracle's on pool[l]."""
    n = b * pages + 2
    q = _arr((b, hq, hd), dtype)
    kp = _arr((layers, n, page, hkv, hd), dtype)
    vp = _arr((layers, n, page, hkv, hd), dtype)
    bt = jnp.asarray(RNG.permutation(n)[:b * pages].reshape(b, pages),
                     jnp.int32)
    ln = jnp.asarray(RNG.integers(1, pages * page, size=b), jnp.int32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    for layer in range(layers):
        out = paged_decode_attention(q, kp, vp, bt, ln, layer,
                                     interpret=True)
        exp = ref.paged_decode_attention_ref(q, kp[layer], vp[layer], bt,
                                             ln)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,hq,hkv,hd,bq,bk", [
    (2, 128, 4, 2, 32, 64, 64),
    (1, 256, 8, 8, 64, 128, 64),
    (2, 64, 2, 1, 16, 32, 32),
])
def test_flash_prefill_sweep(b, s, hq, hkv, hd, bq, bk, dtype):
    q = _arr((b, s, hq, hd), dtype)
    k = _arr((b, s, hkv, hd), dtype)
    v = _arr((b, s, hkv, hd), dtype)
    out = flash_prefill(q, k, v, block_q=bq, block_k=bk, interpret=True)
    exp = ref.flash_prefill_ref(q, k, v)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,hq,dl,dr,page,pages", [
    (2, 4, 64, 16, 32, 3),
    (1, 8, 128, 32, 64, 2),
])
def test_mla_paged_decode_sweep(b, hq, dl, dr, page, pages):
    n = b * pages + 1
    ql = _arr((b, hq, dl), jnp.float32)
    qr = _arr((b, hq, dr), jnp.float32)
    lat = _arr((n, page, dl + dr), jnp.float32)
    bt = jnp.asarray(RNG.permutation(n)[:b * pages].reshape(b, pages),
                     jnp.int32)
    ln = jnp.asarray(RNG.integers(1, pages * page, size=b), jnp.int32)
    sc = _mla_scale(dl, dr)
    out = mla_paged_decode(ql, qr, lat, bt, ln, d_latent=dl, scale=sc,
                           interpret=True)
    exp = ref.mla_paged_decode_ref(ql, qr, lat, bt, ln, dl, scale=sc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


def test_paged_decode_handles_ragged_lengths():
    """Length-masking: padding pages beyond `lengths` never contribute."""
    b, hq, hkv, hd, page, pages = 2, 4, 2, 32, 32, 4
    n = b * pages
    q = _arr((b, hq, hd), jnp.float32)
    kp = _arr((1, n, page, hkv, hd), jnp.float32)
    vp = _arr((1, n, page, hkv, hd), jnp.float32)
    bt = jnp.arange(n, dtype=jnp.int32).reshape(b, pages)
    ln = jnp.asarray([1, 33], jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, ln, 0, interpret=True)
    # corrupt pages beyond the valid length: output must not change
    kp2 = kp.at[:, 2:].set(999.0)
    vp2 = vp.at[:, 2:].set(999.0)
    out2 = paged_decode_attention(q, kp2, vp2, bt, ln, 0, interpret=True)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(out2[0]),
                               rtol=1e-5, atol=1e-5)


def test_int8_paged_decode_vs_oracle_and_fp():
    """int8 pages + in-kernel dequant: matches the dequantize-then-attend
    oracle exactly; quantization error vs fp attention stays small."""
    from repro.kernels.paged_attention import paged_decode_attention_int8
    from repro.models.attention import quantize_kv
    b, hq, hkv, hd, page, pages = 2, 8, 2, 64, 64, 3
    n = b * pages + 1
    k = _arr((n, page, hkv, hd), jnp.float32)
    v = _arr((n, page, hkv, hd), jnp.float32)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    q = _arr((b, hq, hd), jnp.float32)
    bt = jnp.asarray(RNG.permutation(n)[:b * pages].reshape(b, pages),
                     jnp.int32)
    ln = jnp.asarray([pages * page, 70], jnp.int32)
    out = paged_decode_attention_int8(q, kq, vq, ks, vs, bt, ln,
                                      interpret=True)
    exp = ref.paged_decode_attention_int8_ref(q, kq, vq, ks, vs, bt, ln)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)
    exp_fp = ref.paged_decode_attention_ref(q, k, v, bt, ln)
    assert float(jnp.max(jnp.abs(out - exp_fp))) < 0.05


# ---------------------------------------------------------------------------
# chunked prefill over paged KV (kernels/paged_prefill.py)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,c,hq,hkv,hd,page,pages,offs,layers", [
    (2, 16, 4, 2, 32, 8, 5, (19, 0), 1),    # unaligned + zero offset
    (1, 32, 8, 8, 64, 32, 3, (64,), 1),     # MHA, page-aligned offset
    (2, 8, 4, 1, 16, 16, 4, (5, 48), 1),    # MQA
    (2, 16, 4, 2, 32, 8, 5, (19, 0), 3),    # each layer of a 3-layer pool
])
def test_paged_prefill_sweep(b, c, hq, hkv, hd, page, pages, offs, layers,
                             dtype):
    from repro.kernels.paged_prefill import paged_prefill_attention
    n = b * pages + 2
    q = _arr((b, c, hq, hd), dtype)
    kc = _arr((b, c, hkv, hd), dtype)
    vc = _arr((b, c, hkv, hd), dtype)
    kp = _arr((layers, n, page, hkv, hd), dtype)
    vp = _arr((layers, n, page, hkv, hd), dtype)
    bt = jnp.asarray(RNG.permutation(n)[:b * pages].reshape(b, pages),
                     jnp.int32)
    off = jnp.asarray(offs, jnp.int32)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    for layer in range(layers):
        out = paged_prefill_attention(q, kc, vc, kp, vp, bt, off, layer,
                                      interpret=True)
        exp = ref.paged_prefill_attention_ref(q, kc, vc, kp[layer],
                                              vp[layer], bt, off)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("b,c,hq,dl,dr,page,pages,offs", [
    (2, 16, 4, 32, 8, 16, 4, (23, 0)),
    (1, 8, 8, 64, 16, 32, 2, (32,)),
])
def test_mla_paged_prefill_sweep(b, c, hq, dl, dr, page, pages, offs):
    from repro.kernels.paged_prefill import mla_paged_prefill
    n = b * pages + 1
    ql = _arr((b, c, hq, dl), jnp.float32)
    qr = _arr((b, c, hq, dr), jnp.float32)
    lc = _arr((b, c, dl + dr), jnp.float32)
    lp = _arr((n, page, dl + dr), jnp.float32)
    bt = jnp.asarray(RNG.permutation(n)[:b * pages].reshape(b, pages),
                     jnp.int32)
    off = jnp.asarray(offs, jnp.int32)
    sc = _mla_scale(dl, dr)
    out = mla_paged_prefill(ql, qr, lc, lp, bt, off, d_latent=dl,
                            scale=sc, interpret=True)
    exp = ref.mla_paged_prefill_ref(ql, qr, lc, lp, bt, off, dl, scale=sc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


def test_paged_prefill_ignores_pool_garbage_past_offset():
    """Tokens at pool positions >= offset (stale pages, the page the
    chunk will land on) must never contribute to chunk attention."""
    from repro.kernels.paged_prefill import paged_prefill_attention
    b, c, hq, hkv, hd, page, pages = 1, 8, 4, 2, 32, 8, 4
    n = pages + 1
    q = _arr((b, c, hq, hd), jnp.float32)
    kc = _arr((b, c, hkv, hd), jnp.float32)
    vc = _arr((b, c, hkv, hd), jnp.float32)
    kp = _arr((1, n, page, hkv, hd), jnp.float32)
    vp = _arr((1, n, page, hkv, hd), jnp.float32)
    bt = jnp.arange(1, n, dtype=jnp.int32).reshape(1, pages)
    off = jnp.asarray([11], jnp.int32)    # mid-page offset
    out = paged_prefill_attention(q, kc, vc, kp, vp, bt, off, 0,
                                  interpret=True)
    # poison everything at and past the offset
    mask = (jnp.arange(page)[None, :, None, None] +
            page * jnp.arange(n)[:, None, None, None] - page) >= 11
    out2 = paged_prefill_attention(q, kc, vc,
                                   jnp.where(mask, 999.0, kp),
                                   jnp.where(mask, 999.0, vp),
                                   bt, off, 0, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                               rtol=1e-5, atol=1e-5)


def test_paged_prefill_chunk_is_causal():
    """Future chunk tokens must not influence earlier chunk queries."""
    from repro.kernels.paged_prefill import paged_prefill_attention
    b, c, hq, hkv, hd, page, pages = 1, 8, 4, 2, 32, 8, 2
    n = pages + 1
    q = _arr((b, c, hq, hd), jnp.float32)
    kc = _arr((b, c, hkv, hd), jnp.float32)
    vc = _arr((b, c, hkv, hd), jnp.float32)
    kp = _arr((1, n, page, hkv, hd), jnp.float32)
    vp = _arr((1, n, page, hkv, hd), jnp.float32)
    bt = jnp.arange(1, n, dtype=jnp.int32).reshape(1, pages)
    off = jnp.asarray([16], jnp.int32)
    out = paged_prefill_attention(q, kc, vc, kp, vp, bt, off, 0,
                                  interpret=True)
    kc2 = kc.at[:, 5:].set(999.0)
    vc2 = vc.at[:, 5:].set(999.0)
    out2 = paged_prefill_attention(q, kc2, vc2, kp, vp, bt, off, 0,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(out[:, :5]),
                               np.asarray(out2[:, :5]),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# segment prefill: per-query positions over non-contiguous gaps
# ---------------------------------------------------------------------------
def _cpos(rows, c):
    """[B, C] int32 chunk-position array from per-row position lists
    (strictly ascending valid entries, -1 padding)."""
    return jnp.asarray([list(r) + [-1] * (c - len(r)) for r in rows],
                       jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,c,hq,hkv,hd,page,pages,rows,layers", [
    # GQA: two runs straddling a resumed island + a ragged padded row
    (2, 8, 4, 2, 32, 8, 5, ([3, 4, 5, 6, 21, 22, 23, 24],
                            [0, 1, 2, 3, 4, 5]), 1),
    # MHA: page-aligned runs around a whole resumed page
    (1, 16, 8, 8, 64, 32, 3, ([8, 9, 10, 11, 12, 13, 14, 15,
                               64, 65, 66, 67, 68, 69, 70, 71],), 1),
    # MQA: run crossing a page boundary + a far gap
    (2, 8, 4, 1, 16, 16, 4, ([5, 6, 7, 8, 9, 50, 51, 52],
                             [10, 11, 12, 13, 14, 15, 16, 17]), 1),
    # the GQA rows over each layer of a 3-layer pool
    (2, 8, 4, 2, 32, 8, 5, ([3, 4, 5, 6, 21, 22, 23, 24],
                            [0, 1, 2, 3, 4, 5]), 3),
])
def test_paged_prefill_segments_sweep(b, c, hq, hkv, hd, page, pages,
                                      rows, layers, dtype):
    from repro.kernels.paged_prefill import paged_prefill_segments
    n = b * pages + 2
    q = _arr((b, c, hq, hd), dtype)
    kc = _arr((b, c, hkv, hd), dtype)
    vc = _arr((b, c, hkv, hd), dtype)
    kp = _arr((layers, n, page, hkv, hd), dtype)
    vp = _arr((layers, n, page, hkv, hd), dtype)
    bt = jnp.asarray(RNG.permutation(n)[:b * pages].reshape(b, pages),
                     jnp.int32)
    cpos = _cpos(rows, c)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    for layer in range(layers):
        out = paged_prefill_segments(q, kc, vc, kp, vp, bt, cpos, layer,
                                     interpret=True)
        exp = ref.paged_prefill_segments_ref(q, kc, vc, kp[layer],
                                             vp[layer], bt, cpos)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("b,c,hq,dl,dr,page,pages,rows", [
    (2, 8, 4, 32, 8, 16, 4, ([2, 3, 4, 5, 40, 41, 42, 43],
                             [0, 1, 2, 3, 4])),
    (1, 8, 8, 64, 16, 32, 2, ([16, 17, 18, 19, 48, 49, 50, 51],)),
])
def test_mla_prefill_segments_sweep(b, c, hq, dl, dr, page, pages, rows):
    from repro.kernels.paged_prefill import mla_paged_prefill_segments
    n = b * pages + 1
    ql = _arr((b, c, hq, dl), jnp.float32)
    qr = _arr((b, c, hq, dr), jnp.float32)
    lc = _arr((b, c, dl + dr), jnp.float32)
    lp = _arr((n, page, dl + dr), jnp.float32)
    bt = jnp.asarray(RNG.permutation(n)[:b * pages].reshape(b, pages),
                     jnp.int32)
    cpos = _cpos(rows, c)
    sc = _mla_scale(dl, dr)
    out = mla_paged_prefill_segments(ql, qr, lc, lp, bt, cpos,
                                     d_latent=dl, scale=sc, interpret=True)
    exp = ref.mla_paged_prefill_segments_ref(ql, qr, lc, lp, bt, cpos, dl,
                                             scale=sc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


def test_paged_prefill_segments_degenerate_contiguous():
    """cpos = offset + arange(C) (one segment, no gaps) must reproduce
    the scalar-offset kernel exactly — same pages touched, same mask."""
    from repro.kernels.paged_prefill import (paged_prefill_attention,
                                             paged_prefill_segments)
    b, c, hq, hkv, hd, page, pages = 2, 8, 4, 2, 32, 8, 5
    n = b * pages + 2
    q = _arr((b, c, hq, hd), jnp.float32)
    kc = _arr((b, c, hkv, hd), jnp.float32)
    vc = _arr((b, c, hkv, hd), jnp.float32)
    kp = _arr((2, n, page, hkv, hd), jnp.float32)
    vp = _arr((2, n, page, hkv, hd), jnp.float32)
    bt = jnp.asarray(RNG.permutation(n)[:b * pages].reshape(b, pages),
                     jnp.int32)
    offs = (19, 0)
    off = jnp.asarray(offs, jnp.int32)
    cpos = _cpos([[o + i for i in range(c)] for o in offs], c)
    out_seg = paged_prefill_segments(q, kc, vc, kp, vp, bt, cpos, 1,
                                     interpret=True)
    out_off = paged_prefill_attention(q, kc, vc, kp, vp, bt, off, 1,
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(out_seg), np.asarray(out_off))


def test_mla_prefill_segments_degenerate_contiguous():
    from repro.kernels.paged_prefill import (mla_paged_prefill,
                                             mla_paged_prefill_segments)
    b, c, hq, dl, dr, page, pages = 2, 8, 4, 32, 8, 16, 4
    n = b * pages + 1
    ql = _arr((b, c, hq, dl), jnp.float32)
    qr = _arr((b, c, hq, dr), jnp.float32)
    lc = _arr((b, c, dl + dr), jnp.float32)
    lp = _arr((n, page, dl + dr), jnp.float32)
    bt = jnp.asarray(RNG.permutation(n)[:b * pages].reshape(b, pages),
                     jnp.int32)
    offs = (23, 0)
    off = jnp.asarray(offs, jnp.int32)
    cpos = _cpos([[o + i for i in range(c)] for o in offs], c)
    sc = _mla_scale(dl, dr)
    out_seg = mla_paged_prefill_segments(ql, qr, lc, lp, bt, cpos,
                                         d_latent=dl, scale=sc,
                                         interpret=True)
    out_off = mla_paged_prefill(ql, qr, lc, lp, bt, off, d_latent=dl,
                                scale=sc, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_seg), np.asarray(out_off))


def test_paged_prefill_segments_ignore_unresident_slots():
    """Pool slots the chunk itself will occupy (not yet scattered) and
    slots at/after the last query position must never contribute."""
    from repro.kernels.paged_prefill import paged_prefill_segments
    b, c, hq, hkv, hd, page, pages = 1, 8, 4, 2, 32, 8, 6
    n = pages + 1
    positions = [3, 4, 5, 6, 21, 22, 23, 24]
    q = _arr((b, c, hq, hd), jnp.float32)
    kc = _arr((b, c, hkv, hd), jnp.float32)
    vc = _arr((b, c, hkv, hd), jnp.float32)
    kp = _arr((1, n, page, hkv, hd), jnp.float32)
    vp = _arr((1, n, page, hkv, hd), jnp.float32)
    bt = jnp.arange(1, n, dtype=jnp.int32).reshape(1, pages)
    cpos = _cpos([positions], c)
    out = paged_prefill_segments(q, kc, vc, kp, vp, bt, cpos, 0,
                                 interpret=True)
    # poison every pool slot that is a chunk position or >= the last one
    pool_pos = (jnp.arange(page)[None, :, None, None] +
                page * jnp.arange(n)[:, None, None, None] - page)
    own = jnp.zeros_like(pool_pos, bool)
    for p_ in positions:
        own = own | (pool_pos == p_)
    mask = own | (pool_pos >= positions[-1])
    out2 = paged_prefill_segments(q, kc, vc,
                                  jnp.where(mask, 999.0, kp),
                                  jnp.where(mask, 999.0, vp),
                                  bt, cpos, 0, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                               rtol=1e-5, atol=1e-5)
