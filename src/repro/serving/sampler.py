"""Token sampling: greedy / temperature / top-k / top-p, jit-friendly."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.serving.request import SamplingParams


def sample(logits: jax.Array, rng: jax.Array, *, temperature: float = 0.0,
           top_k: int = 0, top_p: float = 1.0) -> jax.Array:
    """logits [B, V] -> tokens [B]."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = logits.astype(jnp.float32) / temperature
    if top_k > 0:
        kth = jnp.sort(lg, axis=-1)[:, -top_k][:, None]
        lg = jnp.where(lg < kth, -jnp.inf, lg)
    if top_p < 1.0:
        sorted_lg = jnp.sort(lg, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_lg, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_lg, cutoff_idx[:, None],
                                     axis=-1)
        lg = jnp.where(lg < cutoff, -jnp.inf, lg)
    return jax.random.categorical(rng, lg, axis=-1).astype(jnp.int32)


def sample_batched(logits: jax.Array, rng: jax.Array,
                   temperature: jax.Array, top_k: jax.Array,
                   top_p: jax.Array) -> jax.Array:
    """Per-row sampling for the whole decode batch in one traced op:
    logits [B, V] + per-row ``temperature``/``top_k``/``top_p`` arrays
    [B] -> tokens [B].  This is the fused step closure's sampler — the
    unfused path issues one ``sample`` dispatch (plus one device sync)
    per request instead.

    Row semantics match ``sample``: temperature <= 0 is greedy (rng
    unused, so fused and unfused greedy decode are token-identical);
    top_k <= 0 and top_p >= 1 disable those filters.  Stochastic rows
    draw from ``jax.random.fold_in(rng, row)`` — a different key stream
    than the unfused path's sequential splits, same distribution.

    Only a batch with a stochastic row runs the filters and the draw
    (``lax.cond`` on the device); an all-greedy batch returns the
    ``argmax`` alone.
    """
    B, V = logits.shape
    lg32 = logits.astype(jnp.float32)
    greedy = jnp.argmax(lg32, axis=-1).astype(jnp.int32)

    def stochastic(lg32, greedy):
        # greedy rows divide by 1e-6 here and are overridden below;
        # logits are O(10) so the scaled values stay finite
        lg = lg32 / jnp.maximum(temperature, 1e-6)[:, None]
        # top-k: drop everything below the kth-largest (k = V disables)
        k = jnp.clip(jnp.where(top_k > 0, top_k, V), 1, V)
        kth = jnp.take_along_axis(jnp.sort(lg, axis=-1), (V - k)[:, None],
                                  axis=-1)
        lg = jnp.where(lg < kth, -jnp.inf, lg)
        # top-p: drop everything below the nucleus cutoff (p >= 1 keeps
        # all mass — the cutoff lands at the smallest kept value)
        sorted_lg = jnp.sort(lg, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_lg, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.minimum(jnp.sum(cum < top_p[:, None], axis=-1),
                                 V - 1)
        cutoff = jnp.take_along_axis(sorted_lg, cutoff_idx[:, None], axis=-1)
        lg = jnp.where(lg < cutoff, -jnp.inf, lg)
        keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(B))
        sampled = jax.vmap(jax.random.categorical)(keys, lg).astype(jnp.int32)
        return jnp.where(temperature <= 0.0, greedy, sampled)

    # idle slots are greedy too; the predicate stays on the device, so
    # there is no sync and the program stays one compiled variant
    return jax.lax.cond(jnp.any(temperature > 0.0), stochastic,
                        lambda lg32, greedy: greedy, lg32, greedy)


def make_sampler(params: SamplingParams):
    def f(logits, rng):
        return sample(logits, rng, temperature=params.temperature,
                      top_k=params.top_k, top_p=params.top_p)
    return f
