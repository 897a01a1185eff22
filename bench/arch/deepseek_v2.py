"""DeepSeek-V2 (MLA attention, DeepSeekMoE after leading dense layers):
seeded weights, the plain reference, the operations and bytes of its
kernels and steps, and the names of those kernels in the device trace.

Nothing here imports the program.  ``init_weights`` makes the weights
from the seed in one jitted call, on the device and in bfloat16, in the
layout the program's MoE decoder takes: ``embed`` [V, D]; ``layers``
stacked over all L layers with ``attn`` (wq [D, H, hd + dr], w_dkv
[D, dl], w_kr [D, dr], kv_norm [dl], w_uk and w_uv [dl, H, hd], wo
[H, hd, D]) and ``ln1``/``ln2`` [D]; ``dense`` stacked over the leading
dense layers (w_gate/w_up [D, F], w_down [F, D]); ``moe`` stacked over
the Lm MoE layers (router [D, E], w_gate/w_up [E, D, Fe], w_down
[E, Fe, D], ``shared`` w_gate/w_up [D, Fs], w_down [Fs, D]); ``ln_f``
[D]; ``head`` [D, V].

``logits`` is the forward pass of HF ``modeling_deepseek.py``
(DeepseekV2) written out in float32 at ``highest`` matmul precision, in
the naive form: per layer RMSNorm; q = h wq split into its nope and
rope parts; the latent c_kv = RMSNorm(h w_dkv) and the rope key h w_kr;
per-head keys and values expanded from c_kv through w_uk and w_uv;
YaRN RoPE (its own frequencies; its cos/sin factor, mscale over
mscale_all_dim, is 1 wherever the two are equal, as in every
configuration the program takes) on the rope parts in half-split form,
as the program rotates them; causal softmax attention
at ``(hd + dr) ** -0.5`` times the square of YaRN's ``mscale_all_dim``
factor; the output projection; RMSNorm and either the dense SwiGLU or
the MoE: softmax router scores in float32, the top-k experts by score,
their gate weights renormalised only where the config says so (the
published ``routed_scaling_factor`` is 1), each expert a SwiGLU computed over
every token and masked by its gate weight, plus the shared experts'
SwiGLU.  The routing is the reference's own, from its own float32
scores.  It runs layer by layer and attends and routes in blocks of
tokens, so a 4096-token sequence fits beside the weights.
``quant="fp8"`` rounds every matmul weight, the experts' and the
router's included, to float8 (e4m3, one scale per output column) first:
the control, one precision below the bfloat16 the configuration is
served in.

The counts take what a call must compute and move: for attention, the
latent of each active row's valid tokens (``dl + dr`` bf16 values per
token and layer) and the queries and outputs; for a step, the active
parameters (attention, the dense FFN, the shared and the k routed
experts of each MoE layer, the router, the head).  ``d`` holds the
sizes of ``dims``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from archlib import BF16, HIGHEST, _rms, _w, weight_key

DECODE_KERNEL = "_mla_decode_pallas"     # op kinds in the device trace
PREFILL_KERNEL = "_mla_prefill"          # (a pattern: any op holding it)
EXPERT_KERNEL = "gmm"                    # the experts' grouped matmul
Q_BLOCK = 512          # query rows per attention block, tokens per
#                        expert block
BUCKET = 1024          # sequences are padded to a multiple of this
EMBED_STD = 0.25       # below the layers' outputs, so the context counts
# Routing.  Served in bfloat16, a token whose sixth and seventh router
# scores nearly tie goes to one expert in the program and the other in
# the float32 reference, several tokens in a hundred per layer; such a
# token's output moves by its sixth gate times the two experts'
# difference, and that jump, not rounding, sets the served gap's tail.
# Router scores of spread 4 make the choice decisive (the first gate
# about 0.58 on average, the sixth 0.02), and the routed experts'
# down-projection at half the fan-in scale keeps the routed sum at the
# spread it has under scores of spread 2 (about a third of the shared
# experts' output).  A flip then moves a token a quarter as far, while
# fp8 weights move every token as before.
ROUTER_STD = 4.0
ROUTED_OUT = 0.5


def dims(model: dict) -> dict:
    """Sizes from a configuration file's ``model`` block."""
    n_dense = model.get("n_dense_layers", 0)
    return dict(
        L=model["n_layers"], D=model["d_model"], H=model["n_heads"],
        hd=model["head_dim"], dl=model["d_latent"], dr=model["d_rope"],
        F=model["d_ff"], Fe=model["expert_d_ff"], E=model["n_experts"],
        k=model["top_k"], Fs=model["n_shared_experts"] * model["expert_d_ff"],
        n_dense=n_dense, Lm=model["n_layers"] - n_dense,
        V=model["vocab_size"], theta=float(model["rope_theta"]),
        eps=float(model["norm_eps"]),
        yarn=(float(model.get("yarn_factor", 0.0)),
              int(model.get("yarn_original_max", 4096)),
              float(model.get("yarn_beta_fast", 32.0)),
              float(model.get("yarn_beta_slow", 1.0)),
              float(model.get("yarn_mscale_all_dim", 0.0))),
        norm_topk=bool(model.get("norm_topk_prob", True)))


def init_weights(d: dict, seed: int):
    L, D, H, hd, dl, dr = (d[k] for k in ("L", "D", "H", "hd", "dl", "dr"))
    F, Fe, Fs, E, V = (d[k] for k in ("F", "Fe", "Fs", "E", "V"))
    nd, Lm = d["n_dense"], d["Lm"]
    # fan-in scaling, so that activations, attention scores and logits
    # have about unit spread at any width; residual branches are scaled
    # down with depth
    sd = 1.0 / math.sqrt(D)
    so = 1.0 / math.sqrt(2 * L)
    shapes = {
        "embed": ((V, D), EMBED_STD), "head": ((D, V), sd),
        "ln_f": ((D,), None),
        "layers": {
            "attn": {"wq": ((L, D, H, hd + dr), sd),
                     "w_dkv": ((L, D, dl), sd), "w_kr": ((L, D, dr), sd),
                     "kv_norm": ((L, dl), None),
                     "w_uk": ((L, dl, H, hd), 1.0 / math.sqrt(dl)),
                     "w_uv": ((L, dl, H, hd), 1.0 / math.sqrt(dl)),
                     "wo": ((L, H, hd, D), so / math.sqrt(H * hd))},
            "ln1": ((L, D), None), "ln2": ((L, D), None)},
        "moe": {"router": ((Lm, D, E), ROUTER_STD * sd),
                "w_gate": ((Lm, E, D, Fe), sd), "w_up": ((Lm, E, D, Fe), sd),
                "w_down": ((Lm, E, Fe, D), ROUTED_OUT * so / math.sqrt(Fe)),
                "shared": {"w_gate": ((Lm, D, Fs), sd),
                           "w_up": ((Lm, D, Fs), sd),
                           "w_down": ((Lm, Fs, D), so / math.sqrt(Fs))}}}
    if nd:
        shapes["dense"] = {"w_gate": ((nd, D, F), sd),
                           "w_up": ((nd, D, F), sd),
                           "w_down": ((nd, F, D), so / math.sqrt(F))}
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
def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope_tables(d: dict, pos):
    """cos and sin [S, 1, dr/2] of the rope part at YaRN's inverse
    frequencies (fast dimensions kept, slow ones divided by the factor,
    a linear ramp between the correction dimensions of beta_fast and
    beta_slow rotations)."""
    dr, theta = d["dr"], d["theta"]
    factor, orig, b_fast, b_slow, _ = d["yarn"]
    inv = 1.0 / theta ** (np.arange(0, dr, 2, dtype=np.float64) / dr)
    if factor > 1:
        def corr(rot):
            return dr * math.log(orig / (rot * 2 * math.pi)) / (
                2 * math.log(theta))
        lo = max(math.floor(corr(b_fast)), 0)
        hi = min(math.ceil(corr(b_slow)), dr - 1)
        if lo == hi:
            hi += 0.001
        ramp = np.clip((np.arange(dr // 2) - lo) / (hi - lo), 0.0, 1.0)
        inv = inv / factor * ramp + inv * (1.0 - ramp)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    return jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]


def _rope(x, cos, sin):
    """x [S, H, dr]; rotate pairs (i, i + dr/2)."""
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def softmax_scale(d: dict) -> float:
    factor, _, _, _, msc_all = d["yarn"]
    scale = 1.0 / math.sqrt(d["hd"] + d["dr"])
    if factor > 1 and msc_all:
        scale *= _yarn_mscale(factor, msc_all) ** 2
    return scale


def _swiglu(h, wg, wu, wd, quant):
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    a = mm("sd,df->sf", h, _w(wg, quant, 0))
    u = mm("sd,df->sf", h, _w(wu, quant, 0))
    return mm("sf,fd->sd", jax.nn.silu(a) * u, _w(wd, quant, 0))


def _attention(x, at, d, quant):
    S = x.shape[0]
    hd = d["hd"]
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    pos = jnp.arange(S)
    cos, sin = _rope_tables(d, pos)
    q = mm("sd,dhk->shk", x, _w(at["wq"], quant, 0))
    q_nope, q_rope = q[..., :hd], _rope(q[..., hd:], cos, sin)
    c_kv = _rms(mm("sd,dl->sl", x, _w(at["w_dkv"], quant, 0)),
                at["kv_norm"], d["eps"])
    k_rope = _rope(mm("sd,dr->sr", x, _w(at["w_kr"], quant, 0))[:, None],
                   cos, sin)                               # [S, 1, dr]
    k_nope = mm("sl,lhk->shk", c_kv, _w(at["w_uk"], quant, 0))
    v = mm("sl,lhk->shk", c_kv, _w(at["w_uv"], quant, 0))
    scale = softmax_scale(d)
    blocks = []
    for b0 in range(0, S, Q_BLOCK):
        qn, qr = q_nope[b0:b0 + Q_BLOCK], q_rope[b0:b0 + Q_BLOCK]
        s = (mm("qhk,shk->hqs", qn, k_nope)
             + mm("qhr,sr->hqs", qr, k_rope[:, 0])) * scale
        mask = (b0 + jnp.arange(qn.shape[0]))[:, None] >= pos[None, :]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        blocks.append(mm("hqs,shk->qhk", p, v))
    o = jnp.concatenate(blocks, 0)
    return mm("shk,hkd->sd", o, _w(at["wo"], quant, (0, 1)))


def _moe(h, mp, d, quant):
    """Routed experts (every expert over every token, masked by its gate
    weight) plus the shared experts."""
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    E, k = d["E"], d["k"]
    scores = jax.nn.softmax(mm("sd,de->se", h, _w(mp["router"], quant, 0)),
                            axis=-1)
    top_p, top_i = jax.lax.top_k(scores, k)
    if d["norm_topk"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(top_i, E) * top_p[..., None], axis=1)
    wg = _w(mp["w_gate"], quant, 1)
    wu = _w(mp["w_up"], quant, 1)
    wd = _w(mp["w_down"], quant, 1)
    outs = []
    for b0 in range(0, h.shape[0], Q_BLOCK):
        hb = h[b0:b0 + Q_BLOCK]
        a = mm("sd,edf->sef", hb, wg)
        u = mm("sd,edf->sef", hb, wu)
        y = mm("sef,efd->sed", jax.nn.silu(a) * u, wd)
        outs.append(jnp.einsum("sed,se->sd", y, gate[b0:b0 + Q_BLOCK]))
    sp = mp["shared"]
    return (jnp.concatenate(outs, 0)
            + _swiglu(h, sp["w_gate"], sp["w_up"], sp["w_down"], quant))


@functools.partial(jax.jit, static_argnames=("d_items", "quant"))
def _dense_layer(x, layers, dense, i, d_items, quant):
    d = dict(d_items)
    lp = jax.tree.map(lambda a: a[i], layers)
    fp = jax.tree.map(lambda a: a[i], dense)
    x = x + _attention(_rms(x, lp["ln1"], d["eps"]), lp["attn"], d, quant)
    h = _rms(x, lp["ln2"], d["eps"])
    return x + _swiglu(h, fp["w_gate"], fp["w_up"], fp["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("d_items", "quant"))
def _moe_layer(x, layers, moe, i, j, d_items, quant):
    d = dict(d_items)
    lp = jax.tree.map(lambda a: a[i], layers)
    mp = jax.tree.map(lambda a: a[j], moe)
    x = x + _attention(_rms(x, lp["ln1"], d["eps"]), lp["attn"], d, quant)
    return x + _moe(_rms(x, lp["ln2"], d["eps"]), mp, d, quant)


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
    items = tuple(sorted(d.items()))
    for i in range(d["L"]):
        if i < d["n_dense"]:
            x = _dense_layer(x, params["layers"], params["dense"], i, items,
                             quant)
        else:
            x = _moe_layer(x, params["layers"], params["moe"], i,
                           i - d["n_dense"], items, quant)
    out = _head(x, jnp.asarray(np.asarray(rows, np.int32)), params["ln_f"],
                params["head"], d["eps"], quant)
    return np.asarray(out, np.float32)


# ---------------------------------------------------------------------------
# operations and bytes, from shapes alone
# ---------------------------------------------------------------------------
def attention_params(d: dict) -> int:
    """Weights one token multiplies by in one layer's attention: q,
    kv_a (the latent and the rope key), kv_b (w_uk and w_uv), o."""
    D, H, hd, dl, dr = (d[k] for k in ("D", "H", "hd", "dl", "dr"))
    return D * H * (hd + dr) + D * (dl + dr) + 2 * dl * H * hd + H * hd * D


def active_ffn_params(d: dict) -> int:
    """FFN weights one token multiplies by, over all layers: the dense
    SwiGLUs, and per MoE layer the router, the shared experts and k
    routed experts."""
    D = d["D"]
    return (d["n_dense"] * 3 * D * d["F"]
            + d["Lm"] * (D * d["E"] + 3 * D * d["Fs"]
                         + d["k"] * 3 * D * d["Fe"]))


def latent_bytes_per_token_layer(d: dict) -> int:
    return (d["dl"] + d["dr"]) * BF16


def _attn_flops_per_key(d: dict) -> int:
    # per head: scores over dl + dr dims, the context over dl
    return 2 * d["H"] * (2 * d["dl"] + d["dr"])


def decode_attention(d: dict, n_rows: int, ctx_sum: int):
    """(flops, bytes) of the paged MLA decode attention of one layer for
    ``n_rows`` rows that attend ``ctx_sum`` tokens together: the latent
    of every attended token, and each row's absorbed query and latent
    output."""
    H, dl, dr = d["H"], d["dl"], d["dr"]
    flops = _attn_flops_per_key(d) * ctx_sum
    nbytes = (ctx_sum * latent_bytes_per_token_layer(d)
              + n_rows * H * ((dl + dr) + dl) * BF16)
    return flops, nbytes


def prefill_attention(d: dict, start: int, n: int):
    """(flops, bytes) of the paged MLA prefill attention of one layer
    for a chunk of ``n`` queries at positions start .. start+n-1, each
    attending every token up to and including its own."""
    H, dl, dr = d["H"], d["dl"], d["dr"]
    keys = n * start + n * (n + 1) // 2
    flops = _attn_flops_per_key(d) * keys
    nbytes = ((start + n) * latent_bytes_per_token_layer(d)
              + n * H * ((dl + dr) + dl) * BF16)
    return flops, nbytes


def decode_step_flops(d: dict, n_rows: int, ctx_sum: int) -> int:
    """Model flops of one decode step: the active parameters of every
    layer, attention and the head, for each row."""
    mm = 2 * n_rows * (d["L"] * attention_params(d) + active_ffn_params(d)
                       + d["D"] * d["V"])
    return mm + d["L"] * decode_attention(d, n_rows, ctx_sum)[0]


def prefill_chunk_flops(d: dict, start: int, n: int) -> int:
    """Model flops of one prefill chunk: every layer's active parameters
    and attention for ``n`` tokens (a chunk computes no logits)."""
    mm = 2 * n * (d["L"] * attention_params(d) + active_ffn_params(d))
    return mm + d["L"] * prefill_attention(d, start, n)[0]


def expected_experts(d: dict, t: int) -> float:
    """Distinct experts that ``t`` tokens hit in one MoE layer, expected
    under uniform routing: E (1 - (1 - k/E)^t)."""
    return d["E"] * (1.0 - (1.0 - d["k"] / d["E"]) ** t)


def expert_ffn(d: dict, t: int):
    """(flops, bytes) of one MoE layer's routed-expert grouped matmuls
    (gate, up, down) for ``t`` tokens.  Flops are exact: 2 * 3 * D * Fe
    per token-expert assignment, t * k of them.  Bytes are the
    assignments' activations (rows in and out of each matmul) and the
    weights of the experts hit, of which only the expected number is
    known here (``expected_experts``)."""
    D, Fe, k = d["D"], d["Fe"], d["k"]
    rows = t * k
    flops = 2 * 3 * D * Fe * rows
    nbytes = (rows * (3 * D + 3 * Fe) * BF16
              + expected_experts(d, t) * 3 * D * Fe * BF16)
    return flops, nbytes
