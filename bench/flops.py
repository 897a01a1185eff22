"""Operations and bytes that the algorithm needs, from shapes alone.

The counts take what a call must compute and move, not what a kernel
happens to fetch: the keys and values of each active row's valid tokens,
one read of each weight per program.  A kernel that reads more than it
needs therefore shows as a share of its roofline below 100%, and no
share can pass 100% unless the time leaves out part of the work.

``d`` holds the sizes of ``reference.dims``: L layers, width D, Hq query
and Hkv KV heads of hd, feed-forward F, vocabulary V.  Activations and
the KV cache are bfloat16 (2 bytes).
"""
from __future__ import annotations

BF16 = 2


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


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_s"])


# ---------------------------------------------------------------------------
# shares read from a traced slice (window.Ctx)
# ---------------------------------------------------------------------------
def step_mfu(ctx):
    """Model flops of the slice's decode steps and prefill chunks over
    the slice's length times the bf16 peak, in percent."""
    tr = ctx.device_trace()
    if tr is None or not (ctx.decode_calls or ctx.prefill_calls):
        return None
    f = sum(decode_step_flops(ctx.d, r, c) for r, c in ctx.decode_calls)
    f += sum(prefill_chunk_flops(ctx.d, s, n) for s, n in ctx.prefill_calls)
    return 100.0 * f / (tr.window_s * ctx.peaks["bf16_flops"])


def _roofline(ctx, kernel, calls, cost):
    tr = ctx.device_trace()
    if tr is None or not calls:
        return None
    secs, n = tr.sum_ops(*kernel)
    if not n:
        return None
    need = sum(ctx.d["L"] * roofline_s(*cost(ctx.d, *c), ctx.peaks)
               for c in calls)
    return 100.0 * need / secs


def decode_roofline(ctx, kernel):
    """Paged decode attention: roofline time of the slice's decode steps
    (every layer) over the kernel's device time, in percent."""
    return _roofline(ctx, kernel, ctx.decode_calls, decode_attention)


def prefill_roofline(ctx, kernel):
    """Paged prefill attention: the same over the slice's prefill
    chunks."""
    return _roofline(ctx, kernel, ctx.prefill_calls, prefill_attention)
