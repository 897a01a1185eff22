"""The compiled ``xla`` kernel backend vs the ``ref.py`` oracles and the
interpret-mode Pallas kernels: numerical equivalence sweeps across head
layouts / ragged lengths / block-table paddings / masked slots, backend
resolution rules, and an engine-level greedy token-identity A/B."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import backend as kb
from repro.kernels import ops, ref

RNG = np.random.default_rng(7)
TOL = dict(rtol=1e-4, atol=1e-4)


def _mla_scale(dl, dr):
    """A softmax scale for the MLA kernels, which take it from their
    caller: that of a head whose nope part is a quarter of the latent."""
    return 1.0 / (dl // 4 + dr) ** 0.5


def _arr(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape).astype(np.float32), dtype)


# ---------------------------------------------------------------------------
# backend resolution
# ---------------------------------------------------------------------------
def test_resolution_order_and_validation(monkeypatch):
    assert kb.resolve_backend("interpret") == "interpret"
    # legacy interpret= boolean keeps working
    assert kb.resolve_backend(None, True) == "interpret"
    if kb.on_tpu():
        assert kb.resolve_backend(None, False) == "pallas"
    else:
        # pallas is rejected at resolution off-TPU (clear error instead
        # of a Mosaic lowering failure deep inside jit)
        with pytest.raises(ValueError, match="requires a TPU"):
            kb.resolve_backend(None, False)
        with pytest.raises(ValueError, match="requires a TPU"):
            kb.resolve_backend("pallas")
        monkeypatch.setenv(kb.ENV_VAR, "pallas")
        with pytest.raises(ValueError, match="requires a TPU"):
            kb.default_backend()
    # explicit backend wins over the legacy boolean
    assert kb.resolve_backend("xla", True) == "xla"
    # env var sets the default; argument still wins
    monkeypatch.setenv(kb.ENV_VAR, "interpret")
    assert kb.default_backend() == "interpret"
    assert kb.resolve_backend("xla") == "xla"
    monkeypatch.setenv(kb.ENV_VAR, "bogus")
    with pytest.raises(ValueError, match="bogus"):
        kb.default_backend()
    with pytest.raises(ValueError, match="cuda"):
        kb.resolve_backend("cuda")
    monkeypatch.delenv(kb.ENV_VAR)
    # platform default: xla everywhere but TPU (cached probe)
    assert kb.default_backend() == ("pallas" if kb.on_tpu() else "xla")


def test_resolve_interpret_defaults(monkeypatch):
    assert kb.resolve_interpret(True) is True
    assert kb.resolve_interpret(False) is False
    monkeypatch.delenv(kb.ENV_VAR, raising=False)
    assert kb.resolve_interpret(None) is (not kb.on_tpu())
    # the env var reaches direct kernel-module calls too: interpret is
    # honored on any platform; xla has no meaning for a raw Pallas call
    # and keeps the platform default
    monkeypatch.setenv(kb.ENV_VAR, "interpret")
    assert kb.resolve_interpret(None) is True
    monkeypatch.setenv(kb.ENV_VAR, "xla")
    assert kb.resolve_interpret(None) is (not kb.on_tpu())
    monkeypatch.setenv(kb.ENV_VAR, "bogus")
    with pytest.raises(ValueError, match="bogus"):
        kb.resolve_interpret(None)


def test_engine_validates_backend():
    from repro.config import reduce_config
    from repro.configs import get_config
    from repro.serving import EngineConfig, ServingEngine
    cfg = reduce_config(get_config("llama3.2-1b"))
    with pytest.raises(ValueError, match="mxu"):
        ServingEngine(cfg, EngineConfig(kernel_backend="mxu"))


# ---------------------------------------------------------------------------
# decode: xla vs oracle vs interpret across head layouts + ragged lengths
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,hq,hkv,hd,page,pages,layers", [
    (2, 8, 2, 64, 64, 4, 1),    # GQA
    (3, 4, 4, 32, 32, 3, 1),    # MHA
    (1, 8, 1, 128, 64, 2, 1),   # MQA
    (2, 8, 2, 64, 64, 4, 3),    # GQA, each layer of a 3-layer pool
])
def test_paged_decode_xla_equivalence(b, hq, hkv, hd, page, pages, layers):
    n = b * pages + 2
    q = _arr((b, hq, hd))
    kp = _arr((layers, n, page, hkv, hd))
    vp = _arr((layers, n, page, hkv, hd))
    bt = jnp.asarray(RNG.permutation(n)[:b * pages].reshape(b, pages),
                     jnp.int32)
    ln = jnp.asarray(RNG.integers(1, pages * page, size=b), jnp.int32)
    for layer in range(layers):
        out = ops.paged_decode(q, kp, vp, bt, ln, layer, backend="xla")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.paged_decode_attention_ref(
                q, kp[layer], vp[layer], bt, ln)), **TOL)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ops.paged_decode(
                q, kp, vp, bt, ln, layer, backend="interpret")), **TOL)


@pytest.mark.parametrize("b,hq,dl,dr,page,pages", [
    (2, 4, 64, 16, 32, 3),
    (1, 8, 128, 32, 64, 2),
])
def test_mla_decode_xla_equivalence(b, hq, dl, dr, page, pages):
    n = b * pages + 1
    ql, qr = _arr((b, hq, dl)), _arr((b, hq, dr))
    lat = _arr((n, page, dl + dr))
    bt = jnp.asarray(RNG.permutation(n)[:b * pages].reshape(b, pages),
                     jnp.int32)
    ln = jnp.asarray(RNG.integers(1, pages * page, size=b), jnp.int32)
    sc = _mla_scale(dl, dr)
    out = ops.mla_decode(ql, qr, lat, bt, ln, d_latent=dl, scale=sc,
                         backend="xla")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.mla_paged_decode_ref(
            ql, qr, lat, bt, ln, dl, scale=sc)), **TOL)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ops.mla_decode(
            ql, qr, lat, bt, ln, d_latent=dl, scale=sc,
            backend="interpret")), **TOL)


def test_paged_decode_xla_ignores_padded_table_entries():
    """Block-table padding (trailing entries left at the scratch page /
    stale ids past the valid length) must not leak into the output."""
    b, hq, hkv, hd, page, pages = 2, 4, 2, 32, 16, 4
    n = b * pages + 1
    q = _arr((b, hq, hd))
    kp = _arr((1, n, page, hkv, hd))
    vp = _arr((1, n, page, hkv, hd))
    ln = jnp.asarray([17, 5], jnp.int32)     # 2 pages / 1 page valid
    bt = jnp.asarray([[1, 2, 0, 0], [3, 0, 0, 0]], jnp.int32)
    out = ops.paged_decode(q, kp, vp, bt, ln, 0, backend="xla")
    # redirect the padded entries to poisoned pages: output unchanged
    kp2 = kp.at[:, 4:].set(999.0)
    vp2 = vp.at[:, 4:].set(999.0)
    bt2 = jnp.asarray([[1, 2, 4, 5], [3, 6, 7, 8]], jnp.int32)
    out2 = ops.paged_decode(q, kp2, vp2, bt2, ln, 0, backend="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), **TOL)
    # an out-of-range padding id clamps into the pool (mode="clip" —
    # the same semantics as the oracles' fancy indexing), never NaN-fills
    bt3 = jnp.asarray([[1, 2, 4, 99], [3, 6, 7, 99]], jnp.int32)
    out3 = ops.paged_decode(q, kp2, vp2, bt3, ln, 0, backend="xla")
    assert not bool(jnp.any(jnp.isnan(out3)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(out3), **TOL)


def test_int8_decode_xla_matches_oracle():
    from repro.models.attention import quantize_kv
    b, hq, hkv, hd, page, pages = 2, 8, 2, 64, 64, 3
    n = b * pages + 1
    k, v = _arr((n, page, hkv, hd)), _arr((n, page, hkv, hd))
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    q = _arr((b, hq, hd))
    bt = jnp.asarray(RNG.permutation(n)[:b * pages].reshape(b, pages),
                     jnp.int32)
    ln = jnp.asarray([pages * page, 70], jnp.int32)
    out = ops.paged_decode_int8(q, kq, vq, ks, vs, bt, ln, backend="xla")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.paged_decode_attention_int8_ref(
            q, kq, vq, ks, vs, bt, ln)), **TOL)


def test_flash_causal_xla_matches_oracle():
    q, k, v = _arr((2, 64, 4, 32)), _arr((2, 64, 2, 32)), _arr((2, 64, 2, 32))
    out = ops.flash_causal(q, k, v, backend="xla")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.flash_prefill_ref(q, k, v)), **TOL)


# ---------------------------------------------------------------------------
# prefill: xla vs oracle vs interpret, incl. masked mid-prefill slots
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,c,hq,hkv,hd,page,pages,offs,layers", [
    (2, 16, 4, 2, 32, 8, 5, (19, 0), 1),  # GQA, unaligned + zero offset
    (1, 32, 8, 8, 64, 32, 3, (64,), 1),   # MHA, page-aligned offset
    (2, 8, 4, 1, 16, 16, 4, (5, 48), 1),  # MQA
    (2, 16, 4, 2, 32, 8, 5, (19, 0), 3),  # GQA, each of 3 layers
])
def test_paged_prefill_xla_equivalence(b, c, hq, hkv, hd, page, pages, offs,
                                       layers):
    n = b * pages + 2
    q = _arr((b, c, hq, hd))
    kc, vc = _arr((b, c, hkv, hd)), _arr((b, c, hkv, hd))
    kp = _arr((layers, n, page, hkv, hd))
    vp = _arr((layers, n, page, hkv, hd))
    bt = jnp.asarray(RNG.permutation(n)[:b * pages].reshape(b, pages),
                     jnp.int32)
    off = jnp.asarray(offs, jnp.int32)
    for layer in range(layers):
        out = ops.paged_prefill(q, kc, vc, kp, vp, bt, off, layer,
                                backend="xla")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.paged_prefill_attention_ref(
                q, kc, vc, kp[layer], vp[layer], bt, off)), **TOL)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ops.paged_prefill(
                q, kc, vc, kp, vp, bt, off, layer, backend="interpret")),
            **TOL)


@pytest.mark.parametrize("b,c,hq,dl,dr,page,pages,offs", [
    (2, 16, 4, 32, 8, 16, 4, (23, 0)),
    (1, 8, 8, 64, 16, 32, 2, (32,)),
])
def test_mla_prefill_xla_equivalence(b, c, hq, dl, dr, page, pages, offs):
    n = b * pages + 1
    ql, qr = _arr((b, c, hq, dl)), _arr((b, c, hq, dr))
    lc, lp = _arr((b, c, dl + dr)), _arr((n, page, dl + dr))
    bt = jnp.asarray(RNG.permutation(n)[:b * pages].reshape(b, pages),
                     jnp.int32)
    off = jnp.asarray(offs, jnp.int32)
    sc = _mla_scale(dl, dr)
    out = ops.mla_prefill(ql, qr, lc, lp, bt, off, d_latent=dl, scale=sc,
                          backend="xla")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.mla_paged_prefill_ref(
            ql, qr, lc, lp, bt, off, dl, scale=sc)), **TOL)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ops.mla_prefill(
            ql, qr, lc, lp, bt, off, d_latent=dl, scale=sc,
            backend="interpret")),
        **TOL)


def test_prefill_xla_ignores_pool_garbage_past_offset():
    """Mid-prefill masked slots: pool positions >= offset (stale pages,
    the page the chunk will land on) never reach chunk attention."""
    b, c, hq, hkv, hd, page, pages = 1, 8, 4, 2, 32, 8, 4
    n = pages + 1
    q = _arr((b, c, hq, hd))
    kc, vc = _arr((b, c, hkv, hd)), _arr((b, c, hkv, hd))
    kp = _arr((1, n, page, hkv, hd))
    vp = _arr((1, n, page, hkv, hd))
    bt = jnp.arange(1, n, dtype=jnp.int32).reshape(1, pages)
    off = jnp.asarray([11], jnp.int32)       # mid-page offset
    out = ops.paged_prefill(q, kc, vc, kp, vp, bt, off, 0, backend="xla")
    mask = (jnp.arange(page)[None, :, None, None] +
            page * jnp.arange(n)[:, None, None, None] - page) >= 11
    out2 = ops.paged_prefill(q, kc, vc,
                             jnp.where(mask, 999.0, kp),
                             jnp.where(mask, 999.0, vp),
                             bt, off, 0, backend="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), **TOL)


def test_prefill_xla_chunk_is_causal():
    b, c, hq, hkv, hd, page, pages = 1, 8, 4, 2, 32, 8, 2
    n = pages + 1
    q = _arr((b, c, hq, hd))
    kc, vc = _arr((b, c, hkv, hd)), _arr((b, c, hkv, hd))
    kp = _arr((1, n, page, hkv, hd))
    vp = _arr((1, n, page, hkv, hd))
    bt = jnp.arange(1, n, dtype=jnp.int32).reshape(1, pages)
    off = jnp.asarray([16], jnp.int32)
    out = ops.paged_prefill(q, kc, vc, kp, vp, bt, off, 0, backend="xla")
    out2 = ops.paged_prefill(q, kc.at[:, 5:].set(999.0),
                             vc.at[:, 5:].set(999.0), kp, vp, bt, off, 0,
                             backend="xla")
    np.testing.assert_allclose(np.asarray(out[:, :5]),
                               np.asarray(out2[:, :5]), **TOL)


# ---------------------------------------------------------------------------
# segment prefill: xla vs oracle vs interpret
# ---------------------------------------------------------------------------
def _cpos(rows, c):
    return jnp.asarray([list(r) + [-1] * (c - len(r)) for r in rows],
                       jnp.int32)


@pytest.mark.parametrize("b,c,hq,hkv,hd,page,pages,rows,layers", [
    # GQA: two runs around a resumed island + a ragged padded row
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
def test_paged_prefill_seg_xla_equivalence(b, c, hq, hkv, hd, page,
                                           pages, rows, layers):
    n = b * pages + 2
    q = _arr((b, c, hq, hd))
    kc, vc = _arr((b, c, hkv, hd)), _arr((b, c, hkv, hd))
    kp = _arr((layers, n, page, hkv, hd))
    vp = _arr((layers, n, page, hkv, hd))
    bt = jnp.asarray(RNG.permutation(n)[:b * pages].reshape(b, pages),
                     jnp.int32)
    cpos = _cpos(rows, c)
    for layer in range(layers):
        out = ops.paged_prefill_seg(q, kc, vc, kp, vp, bt, cpos, layer,
                                    backend="xla")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.paged_prefill_segments_ref(
                q, kc, vc, kp[layer], vp[layer], bt, cpos)), **TOL)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ops.paged_prefill_seg(
                q, kc, vc, kp, vp, bt, cpos, layer, backend="interpret")),
            **TOL)


@pytest.mark.parametrize("b,c,hq,dl,dr,page,pages,rows", [
    (2, 8, 4, 32, 8, 16, 4, ([2, 3, 4, 5, 40, 41, 42, 43],
                             [0, 1, 2, 3, 4])),
    (1, 8, 8, 64, 16, 32, 2, ([16, 17, 18, 19, 48, 49, 50, 51],)),
])
def test_mla_prefill_seg_xla_equivalence(b, c, hq, dl, dr, page, pages,
                                         rows):
    n = b * pages + 1
    ql, qr = _arr((b, c, hq, dl)), _arr((b, c, hq, dr))
    lc, lp = _arr((b, c, dl + dr)), _arr((n, page, dl + dr))
    bt = jnp.asarray(RNG.permutation(n)[:b * pages].reshape(b, pages),
                     jnp.int32)
    cpos = _cpos(rows, c)
    sc = _mla_scale(dl, dr)
    out = ops.mla_prefill_seg(ql, qr, lc, lp, bt, cpos, d_latent=dl,
                              scale=sc, backend="xla")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.mla_paged_prefill_segments_ref(
            ql, qr, lc, lp, bt, cpos, dl, scale=sc)), **TOL)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ops.mla_prefill_seg(
            ql, qr, lc, lp, bt, cpos, d_latent=dl, scale=sc,
            backend="interpret")), **TOL)


def test_paged_prefill_seg_xla_degenerate_contiguous():
    """One contiguous segment (cpos = offset + arange) reproduces the
    scalar-offset dispatcher bit-for-bit on the xla backend."""
    b, c, hq, hkv, hd, page, pages = 2, 8, 4, 2, 32, 8, 5
    n = b * pages + 2
    q = _arr((b, c, hq, hd))
    kc, vc = _arr((b, c, hkv, hd)), _arr((b, c, hkv, hd))
    kp = _arr((2, n, page, hkv, hd))
    vp = _arr((2, n, page, hkv, hd))
    bt = jnp.asarray(RNG.permutation(n)[:b * pages].reshape(b, pages),
                     jnp.int32)
    offs = (19, 0)
    cpos = _cpos([[o + i for i in range(c)] for o in offs], c)
    out_seg = ops.paged_prefill_seg(q, kc, vc, kp, vp, bt, cpos, 1,
                                    backend="xla")
    out_off = ops.paged_prefill(q, kc, vc, kp, vp, bt,
                                jnp.asarray(offs, jnp.int32), 1,
                                backend="xla")
    np.testing.assert_allclose(np.asarray(out_seg), np.asarray(out_off),
                               **TOL)


# ---------------------------------------------------------------------------
# engine-level: greedy replay A/B is token-identical across backends
# ---------------------------------------------------------------------------
def _greedy_engine_tokens(backend: str):
    from repro.config import reduce_config
    from repro.configs import get_config
    from repro.serving import EngineConfig, SamplingParams, ServingEngine
    cfg = reduce_config(get_config("llama3.2-1b"))
    eng = ServingEngine(cfg, EngineConfig(
        max_len=160, kv_budget_bytes=1e6, async_transfers=False,
        kernel_backend=backend))
    assert eng.kernel_backend == backend
    rng = np.random.default_rng(3)
    template = [int(t) for t in rng.integers(0, 200, size=40)]
    for i in range(4):
        user = [int(t) for t in rng.integers(0, 200, size=12)]
        # shared template: requests 1+ take the CoW prefix-share path,
        # so the A/B covers chunk prefill at nonzero offsets too
        eng.submit(template + user,
                   params=SamplingParams(max_new_tokens=8, temperature=0.0),
                   session_id=f"s{i}", block_type="system_prompt")
    eng.run(max_steps=500)
    eng.shutdown()
    done = sorted(eng.scheduler.done, key=lambda r: r.request_id)
    assert len(done) == 4 and all(len(r.generated) == 8 for r in done)
    return [list(r.generated) for r in done]


def test_replay_greedy_token_identical_across_backends():
    assert _greedy_engine_tokens("xla") == \
        _greedy_engine_tokens("interpret")
