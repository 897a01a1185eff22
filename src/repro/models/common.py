"""Shared model-building blocks: param-spec trees, norms, RoPE, MLPs.

Params are plain nested dicts of jnp arrays.  Every parameter is declared
once as a ``PSpec`` (shape + logical axes + init); the same declaration
materializes the weights, produces the ``PartitionSpec`` tree for pjit,
and yields ``ShapeDtypeStruct`` trees for the dry-run — so sharding can
never drift out of sync with the parameter structure.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Parameter declaration
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class PSpec:
    """Declarative parameter: shape, logical axis names, init scale."""
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"            # normal | zeros | ones | small
    scale: float = 0.02
    dtype: Optional[str] = None     # None -> the tree's default dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


ParamTree = Any     # nested dict of PSpec / jnp arrays


def stack_specs(tree: ParamTree, n: int) -> ParamTree:
    """Add a leading 'layers' axis to every PSpec (for lax.scan stacks)."""
    def f(p: PSpec) -> PSpec:
        return PSpec((n,) + p.shape, ("layers",) + p.axes, p.init, p.scale,
                     p.dtype)
    return jax.tree.map(f, tree, is_leaf=lambda x: isinstance(x, PSpec))


def _dt(p: PSpec, default):
    return jnp.dtype(p.dtype) if p.dtype else default


def materialize(tree: ParamTree, rng: jax.Array, dtype=jnp.bfloat16) -> ParamTree:
    leaves, treedef = jax.tree.flatten(
        tree, is_leaf=lambda x: isinstance(x, PSpec))
    keys = jax.random.split(rng, len(leaves))
    out = []
    for k, p in zip(keys, leaves):
        dt = _dt(p, dtype)
        if p.init == "zeros":
            out.append(jnp.zeros(p.shape, dt))
        elif p.init == "ones":
            out.append(jnp.ones(p.shape, dt))
        else:
            scale = p.scale if p.init == "normal" else p.scale * 0.1
            out.append((jax.random.normal(k, p.shape, jnp.float32)
                        * scale).astype(dt))
    return jax.tree.unflatten(treedef, out)


def abstract(tree: ParamTree, dtype=jnp.bfloat16) -> ParamTree:
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, _dt(p, dtype)),
        tree, is_leaf=lambda x: isinstance(x, PSpec))


# ---------------------------------------------------------------------------
# Sharding hook: models call shd(x, *logical_axes) on activations.
# distributed/sharding.py supplies a real implementation; default no-op.
# ---------------------------------------------------------------------------
class NoSharding:
    def __call__(self, x, *axes):
        return x

    def embed_lookup(self, emb, tokens):
        return emb[tokens]

    def expert_map(self, fn, xt, top_i, top_p, valid, w_gate, w_up,
                   w_down, layer):
        """``fn`` (``moe.routed_sum``) over every expert, here."""
        return fn(xt, top_i, top_p, valid, w_gate, w_up, w_down, layer)

    def dp_size(self) -> int:
        return 1


NOSHARD = NoSharding()


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(dt) * gamma


def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(dt) * gamma + beta


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array, shd=NOSHARD) -> jax.Array:
    g = shd(jnp.einsum("...d,df->...f", x, w_gate), "batch", "seq", "mlp")
    u = shd(jnp.einsum("...d,df->...f", x, w_up), "batch", "seq", "mlp")
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, w_down)


def gelu_mlp(x: jax.Array, w_in: jax.Array, b_in: jax.Array,
             w_out: jax.Array, b_out: jax.Array, shd=NOSHARD) -> jax.Array:
    h = jnp.einsum("...d,df->...f", x, w_in) + b_in
    h = shd(jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype),
            "batch", "seq", "mlp")
    return jnp.einsum("...f,fd->...d", h, w_out) + b_out


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------
def rope_freqs(dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def yarn_freqs(dim: int, theta: float, factor: float, original_max: int,
               beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies [dim/2] (DeepSeek-V2
    ``DeepseekV2YarnRotaryEmbedding``): the fast-rotating dimensions keep
    their frequency, the slow ones are divided by ``factor``, with a
    linear ramp between the correction dimensions of ``beta_fast`` and
    ``beta_slow`` rotations over ``original_max`` positions."""
    def corr_dim(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = extra / factor
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp                     # 1 where the frequency is kept
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs=None) -> jax.Array:
    """x [..., seq, heads, head_dim]; positions broadcastable to [..., seq].
    ``freqs`` [head_dim/2] replaces the plain inverse frequencies (YaRN)."""
    dim = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(dim, theta)                   # [dim/2]
    angles = positions[..., None].astype(jnp.float32) * freqs   # [..., s, dim/2]
    cos = jnp.cos(angles)[..., None, :]                  # [..., s, 1, dim/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, dim: int) -> jax.Array:
    pos = np.arange(n)[:, None]
    i = np.arange(dim // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / dim)
    out = np.concatenate([np.sin(angle), np.cos(angle)], axis=-1)
    return jnp.asarray(out, dtype=jnp.float32)


# ---------------------------------------------------------------------------
# Softmax cross-entropy over (possibly vocab-sharded) logits
# ---------------------------------------------------------------------------
def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None,
                  z_loss: float = 1e-4) -> jax.Array:
    """logits [..., V] fp-any, labels [...] int32.  Stable fp32 math; the
    vocab reductions lower to all-reduces under vocab-sharded logits."""
    lg = logits.astype(jnp.float32)
    m = jnp.max(lg, axis=-1, keepdims=True)
    shifted = lg - jax.lax.stop_gradient(m)
    sumexp = jnp.sum(jnp.exp(shifted), axis=-1)
    logz = jnp.log(sumexp)
    gold = jnp.take_along_axis(shifted, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if z_loss:
        nll = nll + z_loss * jnp.square(logz)
    if mask is not None:
        nll = nll * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
