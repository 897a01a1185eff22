"""The dense GQA decoder: seeded weights, the plain reference, the
operations and bytes of its attention kernels and steps, and the names
of those kernels in the device trace.

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

The counts take what a call must compute and move, not what a kernel
happens to fetch: the keys and values of each active row's valid tokens,
one read of each weight per program.  A kernel that reads more than it
needs therefore shows as a share of its roofline below 100%.  ``d``
holds the sizes of ``dims``: L layers, width D, Hq query and Hkv KV
heads of hd, feed-forward F, vocabulary V.  Activations and the KV cache
are bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from archlib import BF16, HIGHEST, _rms, _w, weight_key

DECODE_KERNEL = "_paged_decode_pallas"   # op kinds in the device trace
PREFILL_KERNEL = "_paged_prefill"        # (a pattern: any op holding it)
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


# ---------------------------------------------------------------------------
# operations and bytes, from shapes alone
# ---------------------------------------------------------------------------
def layer_matmul_params(d: dict) -> int:
    """Weights one token multiplies by in one layer (projections and
    the SwiGLU feed-forward)."""
    D, Hq, Hkv, hd, F = d["D"], d["Hq"], d["Hkv"], d["hd"], d["F"]
    return 2 * D * Hq * hd + 2 * D * Hkv * hd + 3 * D * F


def kv_bytes_per_token_layer(d: dict) -> int:
    return 2 * d["Hkv"] * d["hd"] * BF16


def decode_attention(d: dict, n_rows: int, ctx_sum: int):
    """(flops, bytes) of the paged decode attention of one layer for a
    batch of ``n_rows`` rows that attend ``ctx_sum`` tokens together."""
    Hq, hd = d["Hq"], d["hd"]
    flops = 4 * Hq * hd * ctx_sum
    nbytes = (ctx_sum * kv_bytes_per_token_layer(d)
              + 2 * n_rows * Hq * hd * BF16)
    return flops, nbytes


def prefill_attention(d: dict, start: int, n: int):
    """(flops, bytes) of the paged prefill attention of one layer for a
    chunk of ``n`` queries at positions start .. start+n-1, each
    attending every token up to and including its own."""
    Hq, hd = d["Hq"], d["hd"]
    keys = n * start + n * (n + 1) // 2       # sum over queries of p + 1
    flops = 4 * Hq * hd * keys
    nbytes = ((start + n) * kv_bytes_per_token_layer(d)
              + 2 * n * Hq * hd * BF16)
    return flops, nbytes


def decode_step_flops(d: dict, n_rows: int, ctx_sum: int) -> int:
    """Model flops of one decode step: every layer's matmuls and
    attention, and the output head, for each row."""
    mm = 2 * n_rows * (d["L"] * layer_matmul_params(d) + d["D"] * d["V"])
    return mm + d["L"] * decode_attention(d, n_rows, ctx_sum)[0]


def prefill_chunk_flops(d: dict, start: int, n: int) -> int:
    """Model flops of one prefill chunk: every layer's matmuls and
    attention for ``n`` tokens (a chunk computes no logits)."""
    mm = 2 * n * d["L"] * layer_matmul_params(d)
    return mm + d["L"] * prefill_attention(d, start, n)[0]
