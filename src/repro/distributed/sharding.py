"""Logical-axis sharding rules: DP / TP / SP / EP mapping onto the mesh.

Mesh axes:  ("data", "model") single-pod, ("pod", "data", "model")
multi-pod.  The "pod" axis is an outer data-parallel axis (gradient
all-reduce crosses the pod boundary once per step; everything else stays
pod-local).

Logical activation/param axes -> mesh axes (baseline rules):

    batch     -> ("pod", "data")      DP
    vocab     -> "model"              TP embedding / logits
    heads     -> "model"              TP attention (q heads)
    kv_heads  -> "model"              TP KV projections (GSPMD pads when
                                      h_kv < model-axis size)
    mlp       -> "model"              TP FFN
    inner     -> "model"              TP Mamba2 d_inner / SSM heads
    experts   -> None (weights)       experts live on every TP shard;
                                      per-expert hidden dim is TP-sharded
    kv_seq    -> "model"              decode KV caches: sequence-sharded
                                      (flash-decoding; see DESIGN.md)
    layers    -> None                 scan axis, never sharded
    embed     -> None                 activations replicated over model

``long_500k`` (batch=1) overrides kv_seq -> ("data", "model") so a single
sequence's state spreads over all chips.

ZeRO-1 (optimizer state sharding over the data axis) is applied on top of
the param rules by ``zero1_spec``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.common import PSpec

Rules = Dict[str, Any]

BASE_RULES: Rules = {
    "batch": ("pod", "data"),
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "inner": "model",
    "experts": None,
    "kv_seq": "model",
    "seq": None,
    "layers": None,
    "embed": None,
    "embed_out": None,
    "latent": None,
    "int": None,
}

BASE_RULES["seq_res"] = None      # residual-stream seq dim (SP when set)

LONG_CONTEXT_RULES: Rules = dict(BASE_RULES, kv_seq=("data", "model"))

# Expert parallelism: experts sharded over the model axis (per-expert
# hidden dim whole per shard), so the cross-shard reductions of the MoE
# layer move token-space tensors rather than per-expert ones.
EP_RULES: Rules = dict(BASE_RULES, experts="model", mlp=None)

# Sequence parallelism: the residual stream between blocks is sharded on
# seq over the model axis — GSPMD turns per-layer activation all-reduces
# into reduce-scatter + all-gather pairs (half the wire) and remat-saved
# layer inputs shrink by the TP degree.
SP_RULES: Rules = dict(BASE_RULES, seq_res="model")


def _filter_axes(rules: Rules, mesh: Mesh) -> Rules:
    """Drop mesh axes that don't exist (e.g. 'pod' on the single-pod
    mesh, or everything on a 1-device test mesh)."""
    names = set(mesh.axis_names)
    out: Rules = {}
    for k, v in rules.items():
        if v is None:
            out[k] = None
        elif isinstance(v, tuple):
            kept = tuple(a for a in v if a in names)
            out[k] = kept if kept else None
        else:
            out[k] = v if v in names else None
    return out


def logical_to_pspec(axes: Tuple[Optional[str], ...], rules: Rules) -> P:
    parts = []
    for a in axes:
        r = rules.get(a) if a is not None else None
        parts.append(r)
    # trim trailing Nones for tidiness
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def _evenly_shardable(pspec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """pjit *arguments* need exact divisibility (internal constraints may
    pad, args may not): replicate any dim that doesn't divide evenly."""
    parts = list(pspec) + [None] * (len(shape) - len(pspec))
    out = []
    for p, s in zip(parts, shape):
        if p is None:
            out.append(None)
            continue
        axes = p if isinstance(p, tuple) else (p,)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        out.append(p if (s % n == 0 and s >= n) else None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def tree_pspecs(spec_tree: Any, rules: Rules) -> Any:
    return jax.tree.map(
        lambda p: logical_to_pspec(p.axes, rules), spec_tree,
        is_leaf=lambda x: isinstance(x, PSpec))


def tree_shardings(spec_tree: Any, mesh: Mesh,
                   rules: Optional[Rules] = None) -> Any:
    rules = _filter_axes(rules or BASE_RULES, mesh)

    def f(p: PSpec):
        ps = logical_to_pspec(p.axes, rules)
        return NamedSharding(mesh, _evenly_shardable(ps, p.shape, mesh))

    return jax.tree.map(f, spec_tree,
                        is_leaf=lambda x: isinstance(x, PSpec))


def batch_shardings(struct_tree: Any, mesh: Mesh,
                    rules: Optional[Rules] = None) -> Any:
    """Shard the leading (batch) dim of each ShapeDtypeStruct leaf,
    replicating when the batch doesn't divide the dp axes."""
    frules = _filter_axes(rules or BASE_RULES, mesh)
    b_axes = frules.get("batch")

    def f(s):
        ps = P(*((b_axes,) + (None,) * (len(s.shape) - 1)))
        return NamedSharding(mesh, _evenly_shardable(ps, s.shape, mesh))

    return jax.tree.map(f, struct_tree)


# ---------------------------------------------------------------------------
# activation sharding hook (passed into models as `shd`)
# ---------------------------------------------------------------------------
class MeshSharding:
    """Callable applied to activations inside model code:
    ``shd(x, "batch", "seq", "heads", None)``."""

    def __init__(self, mesh: Mesh, rules: Optional[Rules] = None):
        self.mesh = mesh
        self.rules = _filter_axes(rules or BASE_RULES, mesh)

    def __call__(self, x, *axes):
        if self.mesh.empty or np.prod(self.mesh.devices.shape) == 1:
            return x
        ps = logical_to_pspec(tuple(axes[:x.ndim]), self.rules)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, ps))

    def dp_size(self) -> int:
        n = 1
        for a in ("pod", "data"):
            if a in self.mesh.axis_names:
                n *= self.mesh.shape[a]
        return n

    def pspec(self, axes: Tuple[Optional[str], ...]) -> P:
        return logical_to_pspec(axes, self.rules)

    def named(self, *axes) -> NamedSharding:
        return NamedSharding(self.mesh, self.pspec(tuple(axes)))

    # -- embedding lookup against a vocab-sharded table -----------------
    def embed_lookup(self, emb, tokens):
        """Decode-path embedding gather: a plain gather against a vocab-
        sharded table makes XLA all-gather the entire table (~1 GB wire
        per decode step).  Instead: shard_map'd local gather + mask +
        psum over the vocab shards — O(B*D) wire."""
        v_axis = self.rules.get("vocab")
        if (self.mesh.empty or v_axis is None
                or emb.shape[0] % self.mesh.shape[v_axis] != 0):
            return emb[tokens]
        b_axes = self.rules.get("batch")
        tok_ps = _evenly_shardable(P(b_axes), tokens.shape, self.mesh)

        def lookup(e, tok):
            vshard = e.shape[0]
            lo = jax.lax.axis_index(v_axis) * vshard
            local = jnp.clip(tok - lo, 0, vshard - 1)
            x = e[local]
            mask = ((tok >= lo) & (tok < lo + vshard))[:, None]
            return jax.lax.psum(jnp.where(mask, x, jnp.zeros_like(x)),
                                v_axis)

        out_ps = P(*(tuple(tok_ps) + (None,)))
        return jax.shard_map(
            lookup, mesh=self.mesh,
            in_specs=(P(v_axis, None), tok_ps),
            out_specs=out_ps, check_vma=False)(emb, tokens)


    # -- routed experts, each shard on its own ----------------------------
    def expert_map(self, fn, xt, top_i, top_p, valid, w_gate, w_up,
                   w_down, layer):
        """``fn`` (``moe.routed_sum``) under shard_map: each shard takes
        its batch rows of the tokens and routing, and the experts
        (``experts`` rule) and hidden slice (``mlp`` rule) it holds of
        the stacked weights [Lm, E, ...]; the partial sums add up over
        the axes that split experts or hidden.  XLA cannot partition
        the grouped-matmul kernel, and would otherwise gather every
        expert's weights onto every device."""
        if self.mesh.empty or np.prod(self.mesh.devices.shape) == 1:
            return fn(xt, top_i, top_p, valid, w_gate, w_up, w_down, layer)
        mesh = self.mesh
        tok = _evenly_shardable(P(self.rules.get("batch")), xt.shape[:1],
                                mesh)
        b_ax = tuple(tok)[0] if tuple(tok) else None
        up = _evenly_shardable(P(None, self.rules.get("experts"), None,
                                 self.rules.get("mlp")), w_gate.shape, mesh)
        up = tuple(up) + (None,) * (4 - len(tuple(up)))
        e_ax, f_ax = up[1], up[3]
        down = P(None, e_ax, f_ax, None)
        summed = tuple(a for ax in (e_ax, f_ax) if ax is not None
                       for a in (ax if isinstance(ax, tuple) else (ax,)))

        def body(xt, top_i, top_p, valid, wg, wu, wd, layer):
            e0 = (jax.lax.axis_index(e_ax) * wg.shape[1]
                  if e_ax is not None else 0)
            out = fn(xt, top_i, top_p, valid, wg, wu, wd, layer, e0)
            return jax.lax.psum(out, summed) if summed else out

        rows = P(b_ax, None)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(rows, rows, rows, P(b_ax), P(*up), P(*up), down, P()),
            out_specs=rows, check_vma=False)(
                xt, top_i, top_p, valid, w_gate, w_up, w_down, layer)


# ---------------------------------------------------------------------------
# ZeRO-1: optimizer state sharded over the data axis on top of TP
# ---------------------------------------------------------------------------
def zero1_spec(pspec: P, shape: Tuple[int, ...], mesh: Mesh,
               axis: str = "data") -> P:
    """Additionally shard the largest currently-unsharded dim of an
    optimizer-state tensor over the data axis (divisibility required)."""
    if axis not in mesh.axis_names:
        return pspec
    n = mesh.shape[axis]
    parts = list(pspec) + [None] * (len(shape) - len(pspec))
    best, best_size = None, 0
    for i, (p, s) in enumerate(zip(parts, shape)):
        if p is None and s % n == 0 and s >= n and s > best_size:
            best, best_size = i, s
    if best is None:
        return pspec
    parts[best] = axis
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def zero1_shardings(spec_tree: Any, mesh: Mesh,
                    rules: Optional[Rules] = None) -> Any:
    rules = _filter_axes(rules or BASE_RULES, mesh)
    pspecs = tree_pspecs(spec_tree, rules)

    def f(p: PSpec, ps: P):
        ps = _evenly_shardable(ps, p.shape, mesh)
        return NamedSharding(mesh, zero1_spec(ps, p.shape, mesh))

    return jax.tree.map(f, spec_tree, pspecs,
                        is_leaf=lambda x: isinstance(x, (PSpec, P)))
