"""Seeded weights and the plain reference of a dense GQA decoder.

Nothing here imports the program.  ``init_weights`` makes the weights
from the seed in one jitted call, on the device and in bfloat16, in the
layout the program's decoder takes (``embed`` [V, D]; ``layers``
stacked over depth with ``attn`` wq [D, Hq, hd], wk/wv [D, Hkv, hd],
bq/bk/bv, wo [Hq, hd, D], ``ffn`` w_gate/w_up [D, F], w_down [F, D],
``ln1``/``ln2`` [D]; ``ln_f`` [D]; ``head`` [D, V]).

``logits`` is the architecture's forward pass written out in float32
at ``highest`` matmul precision: embedding, then per layer RMSNorm,
q/k/v projections with bias, rotary embedding over the whole head in
half-split form, causal softmax attention with each group of query
heads reading one KV head, output projection, RMSNorm and a SwiGLU
feed-forward, both with residuals; then the final RMSNorm and the
untied head.  It runs layer by layer and attends in blocks of queries,
so a 4096-token sequence fits beside the weights.  ``quant="fp8"``
rounds every matmul weight to float8 (e4m3, one scale per output
column) first: the control, one precision below the bfloat16 the
configuration is served in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block
BUCKET = 1024          # sequences are padded to a multiple of this
BIAS_STD = 0.1         # enough that a dropped bias shows
EMBED_STD = 0.25       # below the layers' outputs, so the context counts


def dims(model: dict) -> dict:
    """Sizes from a configuration file's ``model`` block."""
    return dict(L=model["n_layers"], D=model["d_model"], Hq=model["n_heads"],
                Hkv=model["n_kv_heads"], hd=model["head_dim"],
                F=model["d_ff"], V=model["vocab_size"],
                theta=float(model["rope_theta"]),
                eps=float(model["norm_eps"]))


def weight_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (the driver's exceed 32
    bits)."""
    return jax.random.PRNGKey(
        int(np.random.default_rng(seed).integers(0, 2**31 - 1)))


def init_weights(d: dict, seed: int):
    L, D, Hq, Hkv, hd, F, V = (d[k] for k in
                               ("L", "D", "Hq", "Hkv", "hd", "F", "V"))
    # fan-in scaling, so that activations, attention scores and logits
    # have about unit spread at any width; residual branches are scaled
    # down with depth
    sd = 1.0 / math.sqrt(D)
    so = 1.0 / math.sqrt(2 * L)
    shapes = {
        "embed": ((V, D), EMBED_STD), "head": ((D, V), sd),
        "ln_f": ((D,), None),
        "layers": {
            "attn": {"wq": ((L, D, Hq, hd), sd), "wk": ((L, D, Hkv, hd), sd),
                     "wv": ((L, D, Hkv, hd), sd),
                     "wo": ((L, Hq, hd, D), so / math.sqrt(Hq * hd)),
                     "bq": ((L, Hq, hd), BIAS_STD),
                     "bk": ((L, Hkv, hd), BIAS_STD),
                     "bv": ((L, Hkv, hd), BIAS_STD)},
            "ffn": {"w_gate": ((L, D, F), sd), "w_up": ((L, D, F), sd),
                    "w_down": ((L, F, D), so / math.sqrt(F))},
            "ln1": ((L, D), None), "ln2": ((L, D), None)}}
    leaves, tree = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, s) in zip(keys, leaves):
            if s is None:      # norm gains: 1 + small noise
                out.append((1.0 + 0.1 * jax.random.normal(
                    k, shape, jnp.float32)).astype(jnp.bfloat16))
            else:
                out.append((jax.random.normal(k, shape, jnp.bfloat16)
                            * jnp.bfloat16(s)))
        return jax.tree.unflatten(tree, out)

    return make(weight_key(seed))


# ---------------------------------------------------------------------------
# the reference forward
# ---------------------------------------------------------------------------
def _fp8(w: jax.Array, axis) -> jax.Array:
    """Round to float8 e4m3 with one scale per output column (``axis``
    are the contracted axes)."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _w(w, quant, axis):
    w = w.astype(jnp.float32)
    return _fp8(w, axis) if quant == "fp8" else w


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [S, H, hd]; rotate pairs (i, i + hd/2) by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("d_items", "quant"))
def _layer(x, layers, i, d_items, quant):
    d = dict(d_items)
    S = x.shape[0]
    Hq, Hkv, hd = d["Hq"], d["Hkv"], d["hd"]
    g = Hq // Hkv
    lp = jax.tree.map(lambda a: a[i], layers)
    at, ff = lp["attn"], lp["ffn"]
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    pos = jnp.arange(S)
    h = _rms(x, lp["ln1"], d["eps"])
    q = mm("sd,dhk->shk", h, _w(at["wq"], quant, 0)) + at["bq"].astype(
        jnp.float32)
    k = mm("sd,dhk->shk", h, _w(at["wk"], quant, 0)) + at["bk"].astype(
        jnp.float32)
    v = mm("sd,dhk->shk", h, _w(at["wv"], quant, 0)) + at["bv"].astype(
        jnp.float32)
    q, k = _rope(q, pos, d["theta"]), _rope(k, pos, d["theta"])
    kk = jnp.repeat(k, g, axis=1)          # query head j reads KV head j // g
    vv = jnp.repeat(v, g, axis=1)
    blocks = []
    for b0 in range(0, S, Q_BLOCK):
        qb = q[b0:b0 + Q_BLOCK]
        s = mm("qhk,shk->hqs", qb, kk) / math.sqrt(hd)
        mask = (b0 + jnp.arange(qb.shape[0]))[:, None] >= pos[None, :]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        blocks.append(mm("hqs,shk->qhk", p, vv))
    o = jnp.concatenate(blocks, 0)
    x = x + mm("shk,hkd->sd", o, _w(at["wo"], quant, (0, 1)))
    h = _rms(x, lp["ln2"], d["eps"])
    a = mm("sd,df->sf", h, _w(ff["w_gate"], quant, 0))
    u = mm("sd,df->sf", h, _w(ff["w_up"], quant, 0))
    return x + mm("sf,fd->sd", jax.nn.silu(a) * u,
                  _w(ff["w_down"], quant, 0))


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, rows, ln_f, head, eps, quant):
    h = _rms(x[rows], ln_f, eps)
    return jnp.einsum("nd,dv->nv", h, _w(head, quant, 0), precision=HIGHEST)


def logits(params, d: dict, tokens, rows, quant: str = "none") -> np.ndarray:
    """Float32 logits [len(rows), V] of ``tokens`` at positions ``rows``
    (each row's logits predict the token after it)."""
    S = len(tokens)
    S_pad = -(-S // BUCKET) * BUCKET
    toks = np.zeros(S_pad, np.int32)
    toks[:S] = tokens
    x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
    items = tuple(sorted((k, v) for k, v in d.items()))
    for i in range(d["L"]):
        x = _layer(x, params["layers"], i, items, quant)
    out = _head(x, jnp.asarray(np.asarray(rows, np.int32)), params["ln_f"],
                params["head"], d["eps"], quant)
    return np.asarray(out, np.float32)


def served_gaps(ref: np.ndarray, tokens) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best, in units of that position's logit spread."""
    ref = np.asarray(ref, np.float64)
    tok = np.asarray(tokens)
    best = ref.max(axis=1)
    got = ref[np.arange(len(tok)), tok]
    return (best - got) / ref.std(axis=1)
