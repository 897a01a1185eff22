"""Mixture-of-Experts FFN with dropless top-k routing (granite-moe and
DeepSeek-V2 configurations).

Every token reaches each of its top-k experts: there is no capacity, so
a token's output never depends on which other tokens share its step.
The router scores in float32; the top-k gate weights are renormalised
where the config says so (``norm_topk_prob``).  The token-expert
assignments are sorted by expert and each expert's rows go through a
grouped matmul (``kernels/ops.py`` ``expert_matmul``: the megablox
grouped-matmul Pallas kernel on TPU, ``lax.ragged_dot`` elsewhere).
Always-on shared experts (DeepSeekMoE) run as one SwiGLU of their
summed width and add to the routed sum.

XLA cannot partition the Pallas call, so on a mesh the routed experts
run under ``shard_map`` (``MeshSharding.expert_map``): each shard
computes its own tokens against the experts and hidden slice it holds,
and the partial sums add up over the axes that split them.  Expert
weights are never gathered.

The MoE layers' weights are stacked [Lm, ...] apart from the layer
scan's per-layer slices, and ``moe_ffn`` takes the MoE layer's index:
the TPU kernel then reads that layer's experts where they lie, with no
sliced copy of them per layer.  Rows that ``valid`` masks out (idle
decode rows, chunk padding) are neither computed nor counted.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.common import NOSHARD, PSpec, swiglu


def moe_pspecs(cfg: ModelConfig) -> Dict[str, PSpec]:
    """One MoE layer's parameters (the model stacks them over its MoE
    layers)."""
    d, e, f = cfg.d_model, cfg.layout_n_experts, cfg.moe_ff
    down = 0.02 / math.sqrt(2 * max(cfg.n_layers, 1))
    out = {
        "router": PSpec((d, e), ("embed", None)),
        "w_gate": PSpec((e, d, f), ("experts", "embed", "mlp")),
        "w_up": PSpec((e, d, f), ("experts", "embed", "mlp")),
        "w_down": PSpec((e, f, d), ("experts", "mlp", "embed"), scale=down),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        out["shared"] = {
            "w_gate": PSpec((d, fs), ("embed", "mlp")),
            "w_up": PSpec((d, fs), ("embed", "mlp")),
            "w_down": PSpec((fs, d), ("mlp", "embed"), scale=down),
        }
    return out


def route(router: jax.Array, x: jax.Array, cfg: ModelConfig
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x [T, D] -> (gate weights [T, k] f32, experts [T, k], probs [T, E])."""
    logits = jnp.einsum("td,de->te", x, router,
                        preferred_element_type=jnp.float32)
    e = logits.shape[-1]
    if e != cfg.n_experts:
        # padded experts (expert-parallel layout): never routable
        logits = logits + jnp.where(jnp.arange(e) < cfg.n_experts, 0.0,
                                    -1e9)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_i, probs


def routed_sum(xt: jax.Array, top_i: jax.Array, top_p: jax.Array,
               valid: jax.Array, w_gate: jax.Array, w_up: jax.Array,
               w_down: jax.Array, layer: jax.Array, e0=0,
               backend: str = None) -> jax.Array:
    """The gate-weighted sum [T, D] float32 of each token's routed
    experts, over the experts ``e0 .. e0 + E'`` that the stacks
    ``w_*`` [Lm, E', ...] hold (all of them on one device; a shard's
    own on a mesh, whose partial sums then add up)."""
    from repro.kernels import ops

    t, k = top_i.shape
    e = w_gate.shape[1]
    mine = valid[:, None] & (top_i >= e0) & (top_i < e0 + e)   # [T, k]
    # assignments sorted by expert; the others (masked rows, experts
    # held elsewhere) sort last, past every group, so the grouped
    # matmul never computes them
    flat = jnp.where(mine, top_i - e0, e).reshape(-1)           # [T*k]
    sizes = jnp.sum(jax.nn.one_hot(flat, e, dtype=jnp.int32), axis=0)
    order = jnp.argsort(flat, stable=True)
    xs = xt[order // k]                                         # [T*k, D]
    mm = lambda a, w: ops.expert_matmul(  # noqa: E731
        a, w, sizes, layer, backend=backend)
    g = mm(xs, w_gate)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * mm(xs, w_up)
    ys = mm(h, w_down)                                          # [T*k, D]
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    y = ys[inv].reshape(t, k, -1)
    w = jnp.where(mine, top_p, 0.0)
    y = jnp.where(mine[..., None], y.astype(jnp.float32), 0.0)
    return jnp.einsum("tkd,tk->td", y, w)


def moe_ffn(p: Dict, x: jax.Array, cfg: ModelConfig, layer: jax.Array,
            valid: Optional[jax.Array] = None, backend: str = None,
            shd=NOSHARD) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x [..., D] through MoE layer ``layer`` of the stacked params ``p``
    -> (out [..., D], load-balance aux loss, tokens routed to each
    expert [E] int32).  ``valid`` [...] bool masks rows out.  On a
    mesh, ``shd.expert_map`` runs the routed experts on each shard's
    own experts and hidden slice, and sums the partial outputs."""
    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    t, e = xt.shape[0], cfg.layout_n_experts
    valid = (jnp.ones((t,), bool) if valid is None
             else jnp.broadcast_to(valid, shape[:-1]).reshape(t))
    at = lambda a: jax.lax.dynamic_index_in_dim(a, layer,  # noqa: E731
                                                keepdims=False)
    top_p, top_i, probs = route(at(p["router"]), xt, cfg)
    hit = jax.nn.one_hot(top_i, e, dtype=jnp.int32) * valid[:, None, None]
    counts = jnp.sum(hit, axis=(0, 1))                       # [E]
    out = shd.expert_map(
        functools.partial(routed_sum, backend=backend), xt, top_i, top_p,
        valid, p["w_gate"], p["w_up"], p["w_down"], layer).astype(x.dtype)
    if "shared" in p:
        sp = jax.tree.map(at, p["shared"])
        out = out + swiglu(xt, sp["w_gate"], sp["w_up"], sp["w_down"])
    # load-balance auxiliary loss (Switch eq. 4), over the valid rows
    n = jnp.maximum(jnp.sum(valid), 1)
    frac_tokens = counts / n
    frac_probs = jnp.sum(probs * valid[:, None], axis=0) / n
    aux = e * jnp.sum(frac_tokens * frac_probs) / cfg.top_k
    return out.reshape(shape), aux, counts
