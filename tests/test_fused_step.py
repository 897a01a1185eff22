"""Fused jitted step loop (PR 7): token-identity vs the unfused A/B
path (greedy, across backends, mid-prefill admission, CoW-shared
prefixes, dense layout), the batched sampler's per-row semantics and
its greedy-only branch, the steady-state recompile gate, and
device-state reuse accounting."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import reduce_config
from repro.configs import get_config
from repro.serving import EngineConfig, SamplingParams, ServingEngine
from repro.serving import sampler as sampler_mod
from repro.serving.request import Phase


def _cfg():
    return reduce_config(get_config("llama3.2-1b"))


def _run_ab(make_engine, submit, max_steps=2000):
    """Run the same workload fused and unfused; return generated tokens
    per request per mode plus the engines' stats."""
    outs, stats = {}, {}
    for fused in (True, False):
        eng = make_engine(fused)
        assert eng.fused == fused
        reqs = submit(eng)
        eng.run(max_steps=max_steps)
        eng.shutdown()
        outs[fused] = [list(r.generated) for r in reqs]
        stats[fused] = eng.stats()
    return outs, stats


# ---------------------------------------------------------------------------
# A/B token identity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_fused_vs_unfused_identical_tokens(backend):
    """Acceptance: greedy decode through the fused closure is
    token-identical to the per-request-sampling path, per backend."""
    cfg = _cfg()

    def make(fused):
        return ServingEngine(cfg, EngineConfig(
            max_len=128, kv_budget_bytes=5e5, fused_step=fused,
            kernel_backend=backend, page_tokens=32,
            prefill_chunk_tokens=32, max_step_tokens=96))

    def submit(eng):
        rng = np.random.default_rng(11)
        n_new = 4 if backend == "interpret" else 8
        return [eng.submit([int(t) for t in rng.integers(0, 250, size=40)],
                           params=SamplingParams(max_new_tokens=n_new))
                for _ in range(3)]

    outs, stats = _run_ab(make, submit)
    assert outs[True] == outs[False]
    assert all(g for g in outs[True])
    assert stats[True]["fused"] and not stats[False]["fused"]


def test_fused_identical_with_midstream_prefill():
    """A long prompt admitted while other requests are decoding (mixed
    prefill+decode steps force state rebuilds) stays token-identical."""
    cfg = _cfg()

    def make(fused):
        return ServingEngine(cfg, EngineConfig(
            max_len=640, kv_budget_bytes=2e6, fused_step=fused,
            page_tokens=32, prefill_chunk_tokens=64, max_step_tokens=128))

    def submit(eng):
        rng = np.random.default_rng(5)
        reqs = [eng.submit([int(t) for t in rng.integers(0, 250, size=24)],
                           params=SamplingParams(max_new_tokens=20))
                for _ in range(2)]
        for _ in range(3):        # established decodes
            eng.step()
        assert any(r.phase is Phase.DECODE for r in reqs)
        reqs.append(eng.submit(
            [int(t) for t in rng.integers(0, 250, size=500)],
            params=SamplingParams(max_new_tokens=6)))
        return reqs

    outs, _ = _run_ab(make, submit)
    assert outs[True] == outs[False]


def test_fused_identical_with_cow_shared_prefix():
    """Requests sharing a CoW-mapped prefix (same pool pages in several
    block tables; private-page copies on the decode boundary) decode the
    same tokens fused and unfused, and sharing actually engaged."""
    cfg = _cfg()

    def make(fused):
        return ServingEngine(cfg, EngineConfig(
            max_len=512, kv_budget_bytes=4e6, fused_step=fused,
            page_tokens=32, prefill_chunk_tokens=64, max_step_tokens=256))

    def submit(eng):
        # seed the radix index: a retained first turn makes its prefix
        # blocks pool-resident and shareable
        bt = eng.manager.block_tokens
        shared = [(7 * i + 3) % 250 for i in range(2 * bt)]
        seed = eng.submit(shared, retain_blocks=True,
                          params=SamplingParams(max_new_tokens=2))
        eng.run(max_steps=500)
        assert seed.phase is Phase.DONE
        rng = np.random.default_rng(9)
        reqs = []
        for i in range(3):
            tail = [int(t) for t in rng.integers(0, 250, size=12)]
            reqs.append(eng.submit(shared + tail,
                                   params=SamplingParams(max_new_tokens=6)))
        return reqs

    outs, stats = _run_ab(make, submit)
    assert outs[True] == outs[False]
    assert stats[True]["cow_share_hits"] > 0
    assert stats[False]["cow_share_hits"] == stats[True]["cow_share_hits"]


def test_fused_dense_layout_identical():
    """The dense (paged=False) fallback fuses decode+sampling too."""
    cfg = _cfg()

    def make(fused):
        return ServingEngine(cfg, EngineConfig(
            max_len=96, kv_budget_bytes=5e5, fused_step=fused,
            paged=False))

    def submit(eng):
        assert not eng.paged
        rng = np.random.default_rng(2)
        return [eng.submit([int(t) for t in rng.integers(0, 250, size=20)],
                           params=SamplingParams(max_new_tokens=6))
                for _ in range(3)]

    outs, _ = _run_ab(make, submit)
    assert outs[True] == outs[False]


# ---------------------------------------------------------------------------
# batched sampler semantics
# ---------------------------------------------------------------------------
def test_sample_batched_matches_per_row_semantics():
    """Deterministic rows (greedy / top_k=1 / tiny top_p) must equal the
    per-row ``sample`` results exactly; filters are per-row."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    key = jax.random.PRNGKey(0)
    temps = jnp.asarray([0.0, 1.0, 0.7, 1.3], jnp.float32)
    top_ks = jnp.asarray([0, 1, 0, 5], jnp.int32)
    top_ps = jnp.asarray([1.0, 1.0, 1e-6, 1.0], jnp.float32)
    toks = np.asarray(sampler_mod.sample_batched(
        logits, key, temps, top_ks, top_ps))
    argmax = np.asarray(jnp.argmax(logits, axis=-1))
    assert toks[0] == argmax[0]            # greedy row
    assert toks[1] == argmax[1]            # top_k=1 collapses to argmax
    assert toks[2] == argmax[2]            # top_p -> 0 collapses to argmax
    # top_k=5 row: sampled token must be inside the top-5 support
    top5 = set(np.asarray(jnp.argsort(logits[3])[-5:]).tolist())
    assert int(toks[3]) in top5


def test_sample_batched_jit_stable():
    """One compiled variant regardless of the per-row param values."""
    f = jax.jit(sampler_mod.sample_batched)
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.standard_normal((3, 32)), jnp.float32)
    key = jax.random.PRNGKey(1)
    for t in ((0.0, 0.0, 0.0), (1.0, 0.5, 0.0), (2.0, 0.0, 0.9)):
        f(logits, key, jnp.asarray(t, jnp.float32),
          jnp.zeros((3,), jnp.int32), jnp.ones((3,), jnp.float32))
    assert f._cache_size() == 1


def _sample_batched_ungated(logits, rng, temperature, top_k, top_p):
    """``sample_batched`` as it was before the greedy-only branch: the
    filters and the draw run on every batch.  The reference for the
    gated sampler's tokens."""
    B, V = logits.shape
    lg32 = logits.astype(jnp.float32)
    greedy = jnp.argmax(lg32, axis=-1).astype(jnp.int32)
    # greedy rows divide by 1e-6 here and are overridden below; logits
    # are O(10) so the scaled values stay finite
    lg = lg32 / jnp.maximum(temperature, 1e-6)[:, None]
    # top-k: drop everything below the kth-largest (k = V disables)
    k = jnp.clip(jnp.where(top_k > 0, top_k, V), 1, V)
    kth = jnp.take_along_axis(jnp.sort(lg, axis=-1), (V - k)[:, None],
                              axis=-1)
    lg = jnp.where(lg < kth, -jnp.inf, lg)
    # top-p: drop everything below the nucleus cutoff (p >= 1 keeps all
    # mass — the cutoff lands at the smallest kept value)
    sorted_lg = jnp.sort(lg, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_lg, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.minimum(jnp.sum(cum < top_p[:, None], axis=-1), V - 1)
    cutoff = jnp.take_along_axis(sorted_lg, cutoff_idx[:, None], axis=-1)
    lg = jnp.where(lg < cutoff, -jnp.inf, lg)
    keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(B))
    sampled = jax.vmap(jax.random.categorical)(keys, lg).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


# per-row (temperature, top_k, top_p) of a 6-row batch
_BATCHES = {
    "greedy": [(0.0, 0, 1.0)] * 6,
    "mixed": [(0.0, 0, 1.0), (0.8, 0, 1.0), (0.0, 0, 1.0), (1.0, 20, 1.0),
              (0.7, 0, 0.9), (1.3, 50, 0.8)],
    "stochastic": [(0.8, 0, 1.0), (1.0, 5, 1.0), (0.5, 0, 0.5),
                   (1.2, 30, 0.95), (2.0, 0, 1.0), (0.9, 100, 0.7)],
}


@pytest.mark.parametrize("batch", sorted(_BATCHES))
def test_sample_batched_bit_identical_to_ungated(batch):
    """Gating the filters on a stochastic row changes no token: for a
    fixed key, every batch samples exactly what the ungated sampler
    did, over repeated keys."""
    temps, ks, ps = (jnp.asarray(c) for c in zip(*_BATCHES[batch]))
    ks = ks.astype(jnp.int32)
    rng = np.random.default_rng(3)
    logits = jnp.asarray(4 * rng.standard_normal((6, 1000)), jnp.float32)
    gated = jax.jit(sampler_mod.sample_batched)
    ungated = jax.jit(_sample_batched_ungated)
    for seed in range(8):
        key = jax.random.PRNGKey(seed)
        want = np.asarray(ungated(logits, key, temps, ks, ps))
        np.testing.assert_array_equal(
            np.asarray(gated(logits, key, temps, ks, ps)), want)
    if batch == "greedy":
        np.testing.assert_array_equal(want, np.argmax(logits, axis=-1))


def _hlo_computations(text):
    """HLO module text -> ({computation: body}, entry computation)."""
    comps, entry, name = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(2)
            comps[name] = []
            if head.group(1):
                entry = name
        elif line == "}":
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps, entry


def _called(line, branches):
    """Computations one HLO instruction calls; a conditional's branches
    only when ``branches``."""
    out = re.findall(r"(?:to_apply|calls|condition|body)=%?([\w.\-]+)",
                     line)
    for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
        if branches:
            out += [c.strip().lstrip("%") for c in group.split(",")]
    return out


def test_sample_batched_sorts_only_in_the_stochastic_branch():
    """The sorts sit inside the conditional's branch computation: the
    part of the program every step runs sorts nothing."""
    B, V = 4, 64
    text = jax.jit(sampler_mod.sample_batched).lower(
        jnp.zeros((B, V), jnp.float32), jax.random.PRNGKey(0),
        jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
        jnp.ones((B,), jnp.float32)).as_text(dialect="hlo")
    comps, entry = _hlo_computations(text)
    assert entry is not None

    def reach(roots, branches):
        seen, todo = set(), list(roots)
        while todo:
            c = todo.pop()
            if c not in seen:
                seen.add(c)
                todo += [n for line in comps[c]
                         for n in _called(line, branches)]
        return seen

    def sorts(names):
        return [c for c in names
                if any(re.search(r"\ssort\(", line) for line in comps[c])]

    always = reach([entry], branches=False)
    branch_roots = [n for c in always for line in comps[c]
                    if " conditional(" in line
                    for n in _called(line, branches=True)
                    if n not in _called(line, branches=False)]
    assert branch_roots, "no conditional on the path every step runs"
    assert sorts(always) == []
    assert sorts(reach(branch_roots, branches=True)) != []


# ---------------------------------------------------------------------------
# recompilation + device-state reuse gates
# ---------------------------------------------------------------------------
def test_zero_recompiles_and_state_reuse_in_steady_decode():
    """Steady-state decode must not grow any jit cache (zero recompiles)
    and must mostly reuse the cached device state instead of rebuilding
    block tables."""
    cfg = _cfg()
    eng = ServingEngine(cfg, EngineConfig(
        max_len=256, kv_budget_bytes=2e6, fused_step=True,
        page_tokens=32, prefill_chunk_tokens=32, max_step_tokens=96))
    rng = np.random.default_rng(4)
    reqs = [eng.submit([int(t) for t in rng.integers(0, 250, size=40)],
                       params=SamplingParams(max_new_tokens=60))
            for _ in range(4)]
    # warm up until everyone decodes (prefill + first fused compile)
    for _ in range(200):
        eng.step()
        if all(r.phase is Phase.DECODE for r in reqs):
            break
    eng.step()
    baseline = eng.recompiles()
    assert baseline["fused_decode"] == 1
    reuses0, rebuilds0 = eng.kv.state_reuses, eng.kv.state_rebuilds
    for _ in range(20):
        eng.step()
    after = eng.recompiles()
    assert after == baseline, f"recompiled in steady state: {baseline} -> {after}"
    reuse_delta = eng.kv.state_reuses - reuses0
    rebuild_delta = eng.kv.state_rebuilds - rebuilds0
    # page-boundary crossings force occasional rebuilds; steady decode
    # must still be reuse-dominated
    assert reuse_delta > rebuild_delta, (reuse_delta, rebuild_delta)
    assert eng.stats()["decode_state_reuses"] == eng.kv.state_reuses
    eng.shutdown()


def test_decode_sampled_steps_counts_steps_with_a_sampled_row():
    """Greedy traffic never takes the stochastic branch; one request at
    temperature 0.8 counts exactly the fused steps it decoded in, and
    the greedy requests beside it decode the tokens they decode alone."""
    cfg = _cfg()
    rng = np.random.default_rng(8)
    prompts = [[int(t) for t in rng.integers(0, 250, size=n)]
               for n in (40, 33, 57)]

    def serve(temps):
        eng = ServingEngine(cfg, EngineConfig(
            max_len=128, kv_budget_bytes=5e5, fused_step=True,
            page_tokens=32, prefill_chunk_tokens=32, max_step_tokens=96))
        reqs = [eng.submit(p, params=SamplingParams(
                    max_new_tokens=6 + 3 * i, temperature=t))
                for i, (p, t) in enumerate(zip(prompts, temps))]
        eng.run(max_steps=500)
        eng.shutdown()
        assert all(r.phase is Phase.DONE for r in reqs)
        return reqs, eng.stats()

    greedy, st = serve([0.0, 0.0, 0.0])
    assert st["decode_sampled_steps"] == 0
    assert st["decode_rows"] == sum(len(r.generated) for r in greedy)
    mixed, st = serve([0.0, 0.8, 0.0])
    # one decode step per generated token, the first one included
    assert st["decode_sampled_steps"] == len(mixed[1].generated) == 9
    for i in (0, 2):
        assert mixed[i].generated == greedy[i].generated


def test_state_cache_invalidated_on_mutation():
    """Any host-side table mutation (here: a release) must force a
    rebuild — the cached device state is never served stale."""
    cfg = _cfg()
    eng = ServingEngine(cfg, EngineConfig(
        max_len=128, kv_budget_bytes=1e6, fused_step=True,
        page_tokens=32, prefill_chunk_tokens=32, max_step_tokens=96))
    rng = np.random.default_rng(6)
    reqs = [eng.submit([int(t) for t in rng.integers(0, 250, size=33)],
                       params=SamplingParams(max_new_tokens=8 + 8 * i))
            for i in range(3)]
    eng.run(max_steps=500)
    eng.shutdown()
    assert all(r.phase is Phase.DONE for r in reqs)
    assert all(len(r.generated) == 8 + 8 * i for i, r in enumerate(reqs))
    # the staggered finishes changed the decode set twice: each change
    # must have produced at least one rebuild
    assert eng.kv.state_rebuilds >= 3
