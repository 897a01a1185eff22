"""The serving engine's own measurement: host spans on the profiler's
clock, named step programs, request lifecycle stamps, and the counters
of decode work and of block copies between device and host."""
import glob
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import reduce_config
from repro.configs import get_config
from repro.core import sizing
from repro.serving import EngineConfig, SamplingParams, ServingEngine, spans
from repro.serving.request import Phase

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
import tracing  # noqa: E402

CFG = reduce_config(get_config("llama3.2-1b"))
BT = sizing.block_tokens(CFG)
# a block's payload: keys and values of every layer, bfloat16
BLOCK_BYTES = 2 * CFG.n_layers * BT * CFG.n_kv_heads * CFG.hd * 2


def _engine():
    return ServingEngine(CFG, EngineConfig(
        max_len=512, kv_budget_bytes=32e6, prefill_chunk_tokens=32,
        max_step_tokens=96))


def _prompt(seed, n):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(0, CFG.vocab_size, size=n)]


def _sp(n):
    return SamplingParams(max_new_tokens=n)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
PROMPT = 2 * BT + 40
NEW = 3


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Engine spans, with their args, of one request served under the
    profiler (programs compiled by an earlier request, outside it)."""
    from jax.profiler import ProfileData

    eng = _engine()
    eng.submit(_prompt(0, 2 * BT + 1), params=_sp(2))
    eng.run()
    d = str(tmp_path_factory.mktemp("trace"))
    eng.submit(_prompt(1, PROMPT), params=_sp(NEW))
    jax.profiler.start_trace(d)
    eng.run()
    jax.profiler.stop_trace()
    eng.shutdown()
    path, = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
    events = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("engine.")]
    # the benchmark's reader finds the same spans
    _, host = tracing.read_xplane(d)
    assert sorted(n for n, _, _ in host if n.startswith("engine.")) == \
        sorted(n for n, _, _, _ in events)
    return events


def test_every_span_appears(traced):
    assert {n for n, _, _, _ in traced} == set(spans.ALL)
    assert all(n.startswith(tracing.SPAN_PREFIX) for n in spans.ALL)


@pytest.mark.parametrize("child, parent", [
    (spans.PREFILL_CHUNK, spans.PREFILL),
    (spans.BLOCK_TO_HOST, spans.REGISTER_BLOCKS),
    (spans.DECODE_PREPARE, spans.DECODE),
    (spans.DECODE_DISPATCH, spans.DECODE),
    (spans.DECODE_WAIT, spans.DECODE),
    (spans.DECODE_FINISH, spans.DECODE),
])
def test_spans_nest_inside_the_iteration(traced, child, parent):
    def inside(name, outer):
        return [(a, b) for n, a, b, _ in traced if n == name
                and not any(oa <= a and b <= ob
                            for m, oa, ob, _ in traced if m == outer)]
    assert any(n == child for n, _, _, _ in traced)
    assert inside(child, parent) == []
    assert inside(child, spans.ITERATION) == []
    assert inside(parent, spans.ITERATION) == []


def test_span_args(traced):
    chunks = [s for n, _, _, s in traced if n == spans.PREFILL_CHUNK]
    # the prompt less its last token, from position 0, in 32-token chunks
    assert sum(s["tokens"] for s in chunks) == PROMPT - 1
    assert sorted(s["start"] for s in chunks) == list(range(0, PROMPT - 1,
                                                            32))
    assert len({s["rid"] for s in chunks}) == 1
    decodes = [s for n, _, _, s in traced if n == spans.DECODE]
    assert len(decodes) == NEW and all(s["rows"] == 1 for s in decodes)
    assert [s["ctx_tokens"] for s in decodes] == [PROMPT, PROMPT + 1,
                                                  PROMPT + 2]
    rebuilt = [s["rebuilt"] for n, _, _, s in traced
               if n == spans.DECODE_PREPARE]
    assert rebuilt[0] == 1 and rebuilt[1:] == [0] * (NEW - 1)
    sampled = [s["sampled"] for n, _, _, s in traced
               if n == spans.DECODE_PREPARE]
    assert sampled == [0] * NEW             # greedy: no stochastic branch
    reg, = [s for n, _, _, s in traced if n == spans.REGISTER_BLOCKS]
    assert reg["blocks"] == 2
    assert sum(n == spans.BLOCK_TO_HOST for n, _, _, _ in traced) == 2


# ---------------------------------------------------------------------------
# program names
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def lowering_args():
    eng = _engine()
    kv, C = eng.kv, eng.ecfg.prefill_chunk_tokens
    sa = eng.scheduler.step_arrays([], kv.n_slots)
    z = jnp.zeros((1, C), jnp.int32)
    args = {
        "decode": (eng.params, kv.decode_state([]),
                   jnp.asarray(sa["tokens"])),
        "fused_decode": (eng.params, kv.decode_state([]),
                         jnp.asarray(sa["tokens"]),
                         jnp.asarray(sa["active"]), jax.random.PRNGKey(0),
                         jnp.asarray(sa["temperature"]),
                         jnp.asarray(sa["top_k"]), jnp.asarray(sa["top_p"])),
        "prefill": (eng.params, {"tokens": z}),
        "prefill_chunk": (eng.params, kv.chunk_state(0), z,
                          jnp.zeros((1,), jnp.int32)),
        "prefill_chunk_seg": (eng.params, kv.chunk_state(0), z,
                              jnp.full((1, C), -1, jnp.int32)),
    }
    yield eng, args
    eng.shutdown()


@pytest.mark.parametrize("key", ["decode", "fused_decode", "prefill",
                                 "prefill_chunk", "prefill_chunk_seg"])
def test_step_programs_are_named_by_their_recompiles_key(lowering_args, key):
    eng, args = lowering_args
    assert key in eng.recompiles()
    fn = {"decode": eng._decode, "fused_decode": eng._fused_decode,
          "prefill": eng._prefill, "prefill_chunk": eng._prefill_chunk,
          "prefill_chunk_seg": eng._prefill_chunk_seg}[key]
    text = fn.lower(*args[key]).as_text()
    assert text.startswith(f"module @jit_{key} ")


# ---------------------------------------------------------------------------
# request stamps and counters
# ---------------------------------------------------------------------------
def test_stamps_in_order_through_cache_hits_and_preemption():
    eng = _engine()
    base = _prompt(2, 2 * BT)
    first = eng.submit(base + [7], params=_sp(3))
    eng.run()
    # the second turn's prompt less its last token is two cached blocks
    cached = eng.submit(base + [9], params=_sp(3))
    pre = eng.submit(_prompt(3, 2 * BT + 50), params=_sp(4))
    while not (pre.phase is Phase.PREFILL and pre.prefill_pos > 0):
        eng.step()
    kept = (pre.t_slot, pre.t_prefill_start)
    assert None not in kept and pre.t_prefill_done is None
    h2d = eng.kv.h2d_bytes
    eng.preempt(pre)
    eng.run()
    eng.shutdown()

    assert cached.prefix_hit_blocks == 2
    assert cached.t_prefill_start == cached.t_prefill_done
    assert cached.prefill_steps == 0
    assert (pre.t_slot, pre.t_prefill_start) == kept
    assert pre.prefill_steps >= 3           # 305 tokens at most 96 a step
    assert eng.kv.h2d_bytes > h2d           # the restore injected its KV
    for r in (first, cached, pre):
        assert r.phase is Phase.DONE
        assert (r.arrival <= r.t_slot <= r.t_prefill_start
                <= r.t_prefill_done <= r.t_first_token)
    r = pre
    r.reset_for_redispatch()
    assert (r.t_slot, r.t_prefill_start, r.t_prefill_done,
            r.prefill_steps) == (None, None, None, 0)


def test_block_copies_and_decode_work_are_counted():
    eng = _engine()
    lens = [2 * BT + 1, 2 * BT + 30, BT + 70]
    new = [5, 3, 4]
    reqs = [eng.submit(_prompt(10 + i, n), params=_sp(m))
            for i, (n, m) in enumerate(zip(lens, new))]
    eng.run()
    eng.shutdown()
    st = eng.stats()
    blocks = sum(len(r.block_ids) for r in reqs)
    assert blocks == 5
    assert st["d2h_bytes"] == blocks * BLOCK_BYTES
    assert st["d2h_copy_s"] > 0 and st["d2h_wait_s"] >= 0
    assert st["h2d_bytes"] == 0             # nothing was reused
    # each decode step's row attends its context and the new token
    assert st["decode_rows"] == sum(new)
    assert st["decode_ctx_tokens"] == sum(
        m * n + m * (m - 1) // 2 for n, m in zip(lens, new))
