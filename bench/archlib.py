"""What every architecture module under ``arch/`` shares: the seeded
weight key, the fp8 rounding of the control, RMSNorm, the roofline
time of a call, and the served-token gap that decides ``correct``.

Nothing here imports the program.  An architecture module imports these
instead of copying them, so that two architectures round, normalise and
judge alike."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BF16 = 2               # bytes of a bfloat16 activation or cache value


def weight_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed, including those past 32
    bits."""
    return jax.random.PRNGKey(
        int(np.random.default_rng(seed).integers(0, 2**31 - 1)))


def _fp8(w: jax.Array, axis) -> jax.Array:
    """Round to float8 e4m3 with one scale per output column (``axis``
    are the contracted axes)."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _w(w, quant, axis):
    """A weight in float32, rounded to fp8 first under ``quant="fp8"``."""
    w = w.astype(jnp.float32)
    return _fp8(w, axis) if quant == "fp8" else w


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_s"])


def served_gaps(ref: np.ndarray, tokens) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best, in units of that position's logit spread."""
    ref = np.asarray(ref, np.float64)
    tok = np.asarray(tokens)
    best = ref.max(axis=1)
    got = ref[np.arange(len(tok)), tok]
    return (best - got) / ref.std(axis=1)
