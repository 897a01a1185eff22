"""DeepSeek-V2 on the paged path against ``arch/deepseek_v2.py``'s float32
naive-form reference, on the CPU at a tiny size: chunked prefill then
paged decode, a CoW-shared prefix, dropless routing, the latent norm,
YaRN and mscale; and the configuration file against the published
widths."""
import functools
import json
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import harness

from repro.core import sizing
from repro.models.model import build_model
from repro.serving import SamplingParams, ServingEngine
from repro.serving.kvcache import PagedKVCache

TINY = json.loads((bench_tiny.HERE / "tiny_mla.json").read_text())
SEED = 2**31 + 29
CHUNK, PAGE = 32, 16
# Program and reference in float32 (weights cast up, a float32 pool):
# the two differ only by the order of float32 sums and by the program's
# absorbed attention, about 1e-6 of the logits' spread here.  A program
# that rounds to bfloat16 anywhere errs by 1e-3 and more, and one that
# drops the latent norm, YaRN or the mscale by far more.
F32_TOL = 1e-4
# As served, in bfloat16 weights, activations and pool: the widest gap
# by which a served token's reference logit lies below the reference's
# best, in logit spreads (the harness's ``logit_gap``).  The program
# reads 0.003 here, the fp8 control 0.14.
SERVED_TOL = 0.06


@pytest.fixture(scope="module")
def arch():
    return harness.load_arch("deepseek_v2")


@pytest.fixture(scope="module")
def tiny(arch):
    d = arch.dims(TINY["model"])
    return d, arch.init_weights(d, SEED), harness.model_config(TINY)


def _f32(params):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(1, TINY["model"]
                                                 ["vocab_size"], n)]


def direct(cfg, params, prompts, steps, *, n_slots=4, dtype=jnp.float32,
           forced=None):
    """Greedy decode of each prompt (slot i holds prompt i) through the
    program's own functions: ``prefill_chunk`` in CHUNK-token chunks into
    a paged pool, then ``decode_step_paged`` over an ``n_slots``-row
    batch.  ``forced`` (per prompt) feeds those tokens instead of the
    argmax.  Returns per prompt the [steps, V] float32 logits."""
    model = build_model(cfg)
    kv = PagedKVCache(model, n_slots, max(map(len, prompts)) + steps + PAGE,
                      page_tokens=PAGE, dtype=dtype)
    prefill = jax.jit(functools.partial(model.prefill_chunk, backend="xla"))
    decode = jax.jit(functools.partial(model.decode_step_paged,
                                       backend="xla"))
    slots, toks = [], []
    for i, p in enumerate(prompts):
        s = kv.acquire(i, 0)
        eff = p[:-1]
        for start in range(0, len(eff), CHUNK):
            n = min(CHUNK, len(eff) - start)
            arr = jnp.asarray([eff[start:start + n] + [0] * (CHUNK - n)],
                              jnp.int32)
            st = prefill(params, kv.chunk_state(s, n), arr,
                         jnp.asarray([start], jnp.int32))
            kv.write_chunk(s, st, start, n)
        slots.append(s)
        toks.append(p[-1])
    out = [[] for _ in prompts]
    for t in range(steps):
        step = np.zeros((n_slots,), np.int32)
        for s, tok in zip(slots, toks):
            step[s] = tok
        lg, new = decode(params, kv.decode_state(slots), jnp.asarray(step))
        kv.absorb(new)
        lg = np.asarray(lg, np.float32)
        for i, s in enumerate(slots):
            kv.advance(s)
            out[i].append(lg[s])
            toks[i] = (int(forced[i][t]) if forced is not None
                       else int(np.argmax(lg[s])))
    return [np.stack(o) for o in out], kv


def reference(arch, d, params, prompt, steps_logits):
    """The reference's logits at the positions each decode step read."""
    served = [int(np.argmax(r)) for r in steps_logits]
    seq = list(prompt) + served[:-1]
    return arch.logits(params, d, seq, range(len(prompt) - 1, len(seq)))


def _rel(a, b):
    return float(np.max(np.abs(a - b) / b.std(axis=1, keepdims=True)))


def test_chunked_prefill_then_paged_decode_match_reference(arch, tiny):
    d, params, cfg = tiny
    prompt = _prompt(90)            # three chunks, the last one partial
    (got,), _ = direct(cfg, _f32(params), [prompt], 6)
    ref = reference(arch, d, params, prompt, got)
    assert _rel(got, ref) < F32_TOL


def test_whole_prefill_matches_reference(arch, tiny):
    """The whole-sequence forward (training and one-shot prefill) over
    two 512-token query chunks: its last logits are the reference's."""
    d, params, cfg = tiny
    prompt = _prompt(1024, seed=7)
    logits, _ = jax.jit(build_model(cfg).prefill)(
        _f32(params), {"tokens": jnp.asarray([prompt], jnp.int32)})
    ref = arch.logits(params, d, prompt, [len(prompt) - 1])
    assert _rel(np.asarray(logits, np.float32), ref) < F32_TOL


@pytest.mark.parametrize("drop", ["latent_norm", "yarn", "mscale"])
def test_dropping_a_mechanism_breaks_the_match(arch, tiny, drop):
    """Each of the latent norm, YaRN and YaRN's mscale changes the
    logits by far more than ``F32_TOL``: a program without it fails the
    test above."""
    d, params, cfg = tiny
    changed = {"latent_norm": dict(latent_norm=False),
               "yarn": dict(yarn_factor=0.0),
               "mscale": dict(yarn_mscale_all_dim=0.0)}[drop]
    prompt = _prompt(90)
    (good,), _ = direct(cfg, _f32(params), [prompt], 3)
    ref = reference(arch, d, params, prompt, good)
    (bad,), _ = direct(replace(cfg, **changed), _f32(params), [prompt], 3,
                       forced=[[int(np.argmax(r)) for r in good]])
    assert _rel(bad, ref) > 100 * F32_TOL


def test_routing_is_dropless(tiny):
    """A request's logits alone and in a full batch of requests that
    send every token to the same experts are equal: no token is dropped
    for lack of room and no row's output depends on another's."""
    d, params, cfg = tiny
    prompt = _prompt(40, seed=1)
    (alone,), _ = direct(cfg, params, [prompt], 4, dtype=jnp.bfloat16)
    batch, _ = direct(cfg, params, [prompt] * 3 + [_prompt(40, seed=2)], 4,
                      dtype=jnp.bfloat16)
    for got in batch[:3]:
        np.testing.assert_array_equal(got, alone)


def _served(params, cfg, prompts, max_new=6):
    eng = ServingEngine(cfg, harness.engine_config(TINY), params=params)
    try:
        out = []
        for p in prompts:       # one after another, so later ones reuse
            req = eng.submit(p, params=SamplingParams(max_new_tokens=max_new),
                             retain_blocks=True)
            eng.run()
            out.append(req)
        return out, eng.stats()
    finally:
        eng.shutdown()


def test_cow_shared_prefix_matches_reference(arch, tiny):
    """A second request that resends the first one's prompt and reply
    reuses its blocks by CoW; its served tokens are the ones it is served
    alone, and each lies within ``SERVED_TOL`` of the reference's best."""
    from archlib import served_gaps
    d, params, cfg = tiny
    first = _prompt(600, seed=3)     # one whole 512-token MLA block
    (a,), _ = _served(params, cfg, [first])
    second = first + list(a.generated) + _prompt(40, seed=4)
    (_, b), stats = _served(params, cfg, [first, second])
    assert b.prefix_hit_blocks > 0 and stats["cow_share_hits"] > 0
    (alone,), _ = _served(params, cfg, [second])
    assert list(b.generated) == list(alone.generated)
    seq = second + list(b.generated)[:-1]
    ref = arch.logits(params, d, seq, range(len(second) - 1, len(seq)))
    assert float(served_gaps(ref, b.generated).max()) < SERVED_TOL


def test_expert_counter_sums_and_reads_without_recompile(tiny):
    """``moe_expert_tokens`` counts k experts per token of each MoE layer,
    prefilled and decoded alike, and reading it compiles nothing."""
    d, params, cfg = tiny
    eng = ServingEngine(cfg, harness.engine_config(TINY), params=params)
    try:
        reqs = [eng.submit(_prompt(n, seed=n),
                           params=SamplingParams(max_new_tokens=m))
                for n, m in ((70, 5), (33, 9), (130, 3))]
        eng.run()
        rc = eng.recompiles()
        counts = np.asarray(eng.stats()["moe_expert_tokens"])
        assert eng.recompiles() == rc
        # the first decode step consumes each prompt's last token
        tokens = sum(len(r.prompt) - 1 + len(r.generated) for r in reqs)
        assert counts.shape == (d["Lm"], d["E"])
        assert (counts.sum(axis=1) == tokens * d["k"]).all()
        more = eng.submit(_prompt(20, seed=5),
                          params=SamplingParams(max_new_tokens=4))
        eng.run()
        assert eng.recompiles() == rc
        again = np.asarray(eng.stats()["moe_expert_tokens"])
        assert ((again - counts).sum(axis=1)
                == (19 + len(more.generated)) * d["k"]).all()
    finally:
        eng.shutdown()


def test_chunk_without_n_valid_computes_every_row(tiny):
    """A caller that gives ``chunk_state`` no ``n_valid`` gets every row
    of the chunk through the experts (and counted), never none."""
    d, params, cfg = tiny
    model = build_model(cfg)
    kv = PagedKVCache(model, 2, 4 * CHUNK, page_tokens=PAGE,
                      dtype=jnp.bfloat16)
    s = kv.acquire(0, 0)
    prefill = jax.jit(functools.partial(model.prefill_chunk, backend="xla"))
    arr = jnp.asarray([_prompt(CHUNK, seed=8)], jnp.int32)
    off = jnp.asarray([0], jnp.int32)
    full = prefill(params, kv.chunk_state(s), arr, off)["expert_tokens"]
    some = prefill(params, kv.chunk_state(s, 5), arr, off)["expert_tokens"]
    assert (np.asarray(full).sum(axis=1) == CHUNK * d["k"]).all()
    assert (np.asarray(some).sum(axis=1) == 5 * d["k"]).all()


def test_gqa_logits_are_the_parents():
    """The dense GQA path is untouched: the tiny cell's logits, whole
    prefill and chunked prefill then paged decode, are bit-equal to what
    the program gave before MoE models got a layer index and an expert
    counter (digests recorded from that program)."""
    import hashlib
    conf = json.loads((bench_tiny.HERE / "tiny.json").read_text())
    cfg = harness.model_config(conf)
    params = harness.load_arch("gqa").init_weights(
        harness.load_arch("gqa").dims(conf["model"]), SEED)
    prompt = _prompt(90, seed=6)
    model = build_model(cfg)
    whole, _ = jax.jit(model.prefill)(params, {
        "tokens": jnp.asarray([prompt], jnp.int32)})
    (steps,), _ = direct(cfg, params, [prompt], 3, dtype=jnp.bfloat16)
    digest = {k: hashlib.sha256(np.ascontiguousarray(
        np.asarray(v, np.float32)).tobytes()).hexdigest()[:16]
        for k, v in (("prefill", whole), ("paged", steps))}
    assert digest == GQA_DIGESTS


GQA_DIGESTS = {"prefill": "754bf17830096ba3", "paged": "3a632ce7671969e3"}


# ---------------------------------------------------------------------------
# the configuration file
# ---------------------------------------------------------------------------
CONF = harness.load_config("deepseek-v2-lite")


def test_config_keeps_the_published_widths():
    c, m = CONF, CONF["model"]
    assert (c["hidden_size"], m["d_model"]) == (2048, 2048)
    assert (c["num_attention_heads"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"]) == (16, 128, 64, 128)
    assert (m["n_heads"], m["head_dim"], m["d_rope"]) == (16, 128, 64)
    assert c["kv_lora_rank"] == m["d_latent"] == 512
    assert c["q_lora_rank"] is None
    assert (c["n_routed_experts"], c["num_experts_per_tok"],
            c["n_shared_experts"]) == (64, 6, 2)
    assert (m["n_experts"], m["top_k"], m["n_shared_experts"]) == (64, 6, 2)
    assert c["moe_intermediate_size"] == m["expert_d_ff"] == 1408
    assert c["intermediate_size"] == m["d_ff"] == 10944
    assert c["vocab_size"] == m["vocab_size"] == 102400
    assert c["first_k_dense_replace"] == m["n_dense_layers"] == 1
    assert c["norm_topk_prob"] is m["norm_topk_prob"] is False
    assert c["routed_scaling_factor"] == 1     # the program scales none
    y = c["rope_scaling"]
    assert (m["yarn_factor"], m["yarn_original_max"], m["yarn_beta_fast"],
            m["yarn_beta_slow"], m["yarn_mscale_all_dim"]) == (
        y["factor"], y["original_max_position_embeddings"], y["beta_fast"],
        y["beta_slow"], y["mscale_all_dim"])
    # YaRN's cos/sin factor is mscale over mscale_all_dim: 1 here, and
    # the program has no field for a configuration where it is not
    assert y["mscale"] == y["mscale_all_dim"]
    with pytest.raises(TypeError):
        harness.model_config({**c, "model": {**m, "yarn_mscale": 1.0}})
    assert c["num_hidden_layers"] == m["n_layers"] == 7
    assert list(c["reduced"]) == ["num_hidden_layers"]


def test_config_kv_bytes_are_the_sizing_engines():
    cfg = harness.model_config(CONF)
    assert cfg.attention_variant == "mla"
    per_token = sizing.seq_bytes(cfg, 1)
    assert per_token == CONF["reckoning"]["kv_bytes_per_token"] == 8064
    assert CONF["engine"]["kv_budget_bytes"] == 64 * 4096 * per_token


def test_reference_weights_fit_the_programs_layout(arch):
    d = arch.dims(CONF["model"])
    made = jax.eval_shape(lambda: arch.init_weights(d, SEED))
    want = build_model(harness.model_config(CONF)).abstract_params()
    shapes = lambda t: jax.tree.map(  # noqa: E731
        lambda a: (a.shape, str(a.dtype)), t)
    assert shapes(made) == shapes(want)
    n = sum(a.size for a in jax.tree.leaves(made))
    assert abs(n * 2 / 1e9 - 8.02) < 0.01     # the reckoning's 8.02 GB
