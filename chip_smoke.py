"""Chip smoke test: serve llama3.2-1b at full width on TPU chips.

    python3 chip_smoke.py             # one chip: engine + correctness
    python3 chip_smoke.py --chips 4   # four replicas, one per chip

Run from the root of a checkout; the weights are random, made from
``--seed``.  One process drives every chip it uses.

One chip (the default) runs two phases:

* engine: ``ServingEngine`` with its defaults (paged pool, chunked
  prefill, segment reuse, async tier transfers; the kernel backend
  resolves to compiled Pallas on TPU) serves two identical waves of
  requests that share a multi-page system prompt, hold a long prompt
  and diverge mid-prompt, then one solo request;
* correctness: the solo prompt goes through the engine's model functions
  directly (``prefill_chunk_seg`` and ``decode_step_paged`` on the
  Pallas backend) and each decode step's logits are compared with a
  float32 teacher-forced forward of the same parameters; the engine's
  greedy tokens must equal the direct path's.

``--chips 4`` runs only the replica phase: four replicas behind the
``affine`` router, each asserted to hold its arrays on its own chip,
against the same requests through one replica; greedy tokens must match
per request.

The last line of standard output is ``{"ok": true, "device": {...}}``.
On any failure, and whenever JAX finds no TPU, the script exits non-zero
and prints no such line.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "llama3.2-1b"
MAX_LEN = 2048
KV_BUDGET = 3 * 2**30       # pool of ~3.2 GB beside 2.5 GB of bf16 params,
#                             leaving room for the decode step's temporaries
MAX_NEW = 16
SOLO_LEN = 300              # solo prompt: five prefill chunks
SOLO_STEPS = 8              # decode steps compared against the reference
# Logit tolerance: the served path keeps parameters, activations and KV
# in bf16 (8 significant bits, relative rounding 2^-9 per value) and the
# reference is float32 at "highest" matmul precision.  The error of a
# step is max |logits - reference| over the vocabulary divided by the
# reference's standard deviation.  On a CPU, a 4-layer d_model-512
# variant of this model with random weights shows 0.03 from those
# roundings, while changing 64 tokens of its context moves the logits by
# 0.9 and dropping one 64-token page of it by 4.4: a tolerance of 0.25
# leaves the rounding error of 16 layers room and still catches a
# masking or paging bug.
LOGIT_TOL = 0.25


class SmokeError(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def _tokens(rng: np.random.Generator, vocab: int, n: int) -> list:
    return [int(t) for t in rng.integers(1, vocab, size=n)]


def engine_prompts(vocab: int, block: int, seed: int):
    """Wave prompts: three sharing a 3-block system prompt, one long
    prompt, and one that keeps the first two system blocks, diverges
    for a block, then resumes three of the long prompt's blocks at their
    own positions (the segment path).  Plus the solo prompt."""
    rng = np.random.default_rng(seed)
    system = _tokens(rng, vocab, 3 * block)
    shared = [system + _tokens(rng, vocab, 100) for _ in range(3)]
    long_ = _tokens(rng, vocab, 1200)
    diverge = (system[:2 * block] + _tokens(rng, vocab, block)
               + long_[3 * block:6 * block] + _tokens(rng, vocab, 50))
    solo = _tokens(rng, vocab, SOLO_LEN)
    return shared + [long_], diverge, solo


def _run_all(eng, prompts, max_new: int) -> list:
    from repro.serving import SamplingParams
    reqs = [eng.submit(p, params=SamplingParams(max_new_tokens=max_new),
                       session_id=f"s{i}", block_type="system_prompt")
            for i, p in enumerate(prompts)]
    eng.run()
    return reqs


def run_wave(eng, first, diverge) -> list:
    """One wave: the first prompts run to completion, then the divergent
    one, so its resumed blocks are registered when it is admitted."""
    return (_run_all(eng, first, MAX_NEW)
            + _run_all(eng, [diverge], MAX_NEW))


def engine_phase(cfg, ecfg, seed: int) -> dict:
    """Serve two identical waves and a solo request; returns the
    engine's parameters, the solo tokens and the figures printed."""
    from repro.core import sizing
    from repro.serving import ServingEngine
    from repro.serving.request import Phase

    first, diverge, solo = engine_prompts(cfg.vocab_size,
                                          sizing.block_tokens(cfg), seed)
    t0 = time.perf_counter()
    eng = ServingEngine(cfg, ecfg)
    jax.block_until_ready(eng.params)
    t_build = time.perf_counter() - t0
    try:
        print(f"kernel_backend: {eng.kernel_backend}")
        t0 = time.perf_counter()
        wave1 = run_wave(eng, first, diverge)
        t_wave1 = time.perf_counter() - t0
        rc1 = eng.recompiles()
        seg_hits = eng.segment_share_hits + eng.segment_inject_hits
        seg_chunks = eng.segment_chunks
        t0 = time.perf_counter()
        wave2 = run_wave(eng, first, diverge)
        t_wave2 = time.perf_counter() - t0
        rc2 = eng.recompiles()
        (solo_req,) = _run_all(eng, [solo], SOLO_STEPS)
        reqs = wave1 + wave2 + [solo_req]
        st = eng.stats()
        done = sum(r.phase is Phase.DONE for r in reqs)
        prefix_hits = eng.cow_share_hits + eng.inject_hits
        failed = st["async_transfers"]["failed"]
        recomputes = st["cache"]["fetch_recomputes"]
        print(f"requests done: {done}/{len(reqs)}")
        print(f"prefix hits: {prefix_hits} blocks "
              f"(cow {eng.cow_share_hits}, inject {eng.inject_hits}); "
              f"segment hits: {seg_hits} blocks in wave 1, "
              f"{seg_chunks} segment chunks")
        print(f"recompiles after wave 1: {rc1}")
        print(f"recompiles after wave 2: {rc2}")
        print(f"failed transfers: {failed}; fetch_recomputes: {recomputes}")
        print(f"set-up: engine build {t_build:.2f} s, wave 1 (cold, "
              f"compiles) {t_wave1:.2f} s; run: wave 2 (warm) "
              f"{t_wave2:.2f} s")
        check(eng.kernel_backend == "pallas",
              f"kernel backend {eng.kernel_backend!r}, not 'pallas'")
        check(done == len(reqs), f"{done}/{len(reqs)} requests done")
        check(all(len(r.generated) == r.params.max_new_tokens
                  for r in reqs), "a request stopped short")
        check(prefix_hits > 0, "no prefix hits")
        check(seg_hits > 0 and seg_chunks > 0, "segment path not taken")
        check(rc2 == rc1, f"recompiled in wave 2: {rc1} -> {rc2}")
        check(failed == 0, f"{failed} failed transfers")
        check(recomputes == 0, f"{recomputes} fetch recomputes")
        return {"params": eng.params, "n_slots": eng.kv.n_slots,
                "solo": solo, "solo_tokens": list(solo_req.generated),
                "backend": eng.kernel_backend}
    finally:
        eng.shutdown()


def direct_path(model, params, prompt, n_steps: int, n_slots: int,
                backend: str, chunk: int, page: int):
    """Greedy decode of ``prompt`` through the engine's model functions,
    called directly: chunked ``prefill_chunk_seg`` into a paged pool,
    then ``decode_step_paged`` with the prompt in slot 0 of an
    ``n_slots``-row batch.  Returns (tokens, per-step f32 logits)."""
    from repro.serving.kvcache import PagedKVCache

    kv = PagedKVCache(model, n_slots, len(prompt) + n_steps + page,
                      page_tokens=page)
    kv.acquire(0, len(prompt))
    prefill = jax.jit(functools.partial(model.prefill_chunk_seg,
                                        backend=backend))
    decode = jax.jit(functools.partial(model.decode_step_paged,
                                       backend=backend),
                     donate_argnums=(1,))
    eff = prompt[:-1]
    for start in range(0, len(eff), chunk):
        pos = list(range(start, min(start + chunk, len(eff))))
        pad = chunk - len(pos)
        toks = jnp.asarray([[eff[p] for p in pos] + [0] * pad], jnp.int32)
        cpos = jnp.asarray([pos + [-1] * pad], jnp.int32)
        kv.write_chunk_positions(0, prefill(params, kv.chunk_state(0),
                                            toks, cpos), pos)
    kv.set_length(0, len(eff))
    tok, tokens, logits = prompt[-1], [], []
    for _ in range(n_steps):
        step_tokens = np.zeros((n_slots,), np.int32)
        step_tokens[0] = tok
        lg, new_state = decode(params, kv.decode_state([0]),
                               jnp.asarray(step_tokens))
        kv.absorb(new_state)
        kv.advance(0)
        row = np.asarray(lg[0], np.float32)
        logits.append(row)
        tok = int(np.argmax(row))
        tokens.append(tok)
    return tokens, np.stack(logits)


def reference_logits(model, params, prompt, tokens) -> np.ndarray:
    """Teacher-forced float32 forward (the pass ``Model.prefill`` runs)
    over prompt + tokens[:-1]; logits at the positions whose next token
    each decode step predicted."""
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    seq = jnp.asarray([list(prompt) + list(tokens[:-1])], jnp.int32)
    first = len(prompt) - 1

    @jax.jit
    def fwd(p, toks):
        x, _, _ = model._forward_full(p, toks)
        return model._logits(p, x[0, first:])

    with jax.default_matmul_precision("highest"):
        out = np.asarray(fwd(p32, seq), np.float32)
    del p32
    return out


def correctness_phase(cfg, res: dict, ecfg) -> None:
    from repro.models.model import build_model

    model = build_model(cfg)
    tokens, logits = direct_path(
        model, res["params"], res["solo"], SOLO_STEPS, res["n_slots"],
        res["backend"], ecfg.prefill_chunk_tokens, ecfg.page_tokens)
    ref = reference_logits(model, res["params"], res["solo"], tokens)
    check(logits.shape == ref.shape == (SOLO_STEPS, cfg.vocab_size),
          f"logit shapes {logits.shape} vs {ref.shape}")
    check(bool(np.isfinite(logits).all() and np.isfinite(ref).all()),
          "non-finite logits")
    err = np.abs(logits - ref).max(axis=1) / ref.std(axis=1)
    print("logit error per step (max|diff| / std(ref)): "
          + " ".join(f"{e:.4f}" for e in err)
          + f"; tolerance {LOGIT_TOL}")
    print(f"direct tokens: {tokens}")
    print(f"engine tokens: {res['solo_tokens']}")
    check(float(err.max()) <= LOGIT_TOL,
          f"logit error {err.max():.4f} > {LOGIT_TOL}")
    check(tokens == res["solo_tokens"],
          "engine greedy tokens differ from the direct path's")


def replica_prompts(vocab: int, seed: int, n: int = 8) -> list:
    """Short unique prompts: each prefills in one chunk at offset 0 and
    shares nothing, so a request's arithmetic does not depend on which
    requests run beside it."""
    rng = np.random.default_rng(seed + 1)
    return [_tokens(rng, vocab, 20 + 2 * i) for i in range(n)]


def _on_device(tree, device) -> bool:
    return all(a.devices() == {device} for a in jax.tree.leaves(tree))


def replica_phase(cfg, ecfg, seed: int, n_chips: int) -> None:
    from repro.serving import SamplingParams, ServingEngine
    from repro.serving.cluster import ReplicaCluster

    devices = jax.devices()
    check(len(devices) == n_chips,
          f"{len(devices)} devices, expected {n_chips}")
    prompts = replica_prompts(cfg.vocab_size, seed)
    greedy = SamplingParams(max_new_tokens=MAX_NEW)

    t0 = time.perf_counter()
    one = ServingEngine(cfg, ecfg, device=devices[0])
    try:
        single = [one.submit(p, params=greedy) for p in prompts]
        one.run()
        single = [list(r.generated) for r in single]
    finally:
        one.shutdown()
    print(f"one replica on device {devices[0].id}: build and serve "
          f"{time.perf_counter() - t0:.2f} s")
    check(all(len(t) == MAX_NEW for t in single),
          "a request stopped short on one replica")
    # every replica gets the same seeded weights: initialize them once and
    # copy them to each replica's chip instead of once per chip
    params = one.params
    del one
    gc.collect()

    t0 = time.perf_counter()
    cluster = ReplicaCluster(
        n_replicas=n_chips, routing="affine",
        engine_factory=lambda device: ServingEngine(
            cfg, ecfg, params=params, device=device))
    try:
        routed = [cluster.dispatch(p, session_id=f"s{i}", params=greedy)
                  for i, p in enumerate(prompts)]
        cluster.run()
        print(f"{n_chips} replicas: build and serve "
              f"{time.perf_counter() - t0:.2f} s")
        for i, (name, eng) in enumerate(sorted(cluster.engines.items())):
            dev = devices[i]
            placed = (_on_device(eng.params, dev)
                      and _on_device(eng.kv.pools, dev))
            n_req = sum(target == name for target, _ in routed)
            peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
            print(f"{name}: device {dev.id} ({dev.device_kind}), "
                  f"{n_req} requests, arrays on own device: {placed}, "
                  f"peak_bytes_in_use {peak}")
            check(placed, f"{name} holds arrays off device {dev.id}")
        check(len({t for t, _ in routed}) > 1,
              "the router sent every request to one replica")
        tokens = [list(r.generated) for _, r in routed]
    finally:
        cluster.shutdown()
    same = sum(a == b for a, b in zip(tokens, single))
    print(f"greedy tokens equal to one replica's: {same}/{len(prompts)}")
    check(same == len(prompts), "replica tokens differ from one replica's")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-replica phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"FAIL: JAX finds no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import EngineConfig

    print(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH)
    ecfg = EngineConfig(max_len=MAX_LEN, kv_budget_bytes=KV_BUDGET,
                        seed=args.seed)
    print(f"model: {ARCH}, {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads, vocab {cfg.vocab_size}")
    try:
        if args.chips == 4:
            replica_phase(cfg, ecfg, args.seed, 4)
        else:
            res = engine_phase(cfg, ecfg, args.seed)
            gc.collect()            # the engine's pool goes before the
            #                         float32 reference copy comes
            stats = dev.memory_stats() or {}
            print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
            t0 = time.perf_counter()
            correctness_phase(cfg, res, ecfg)
            print(f"correctness phase: {time.perf_counter() - t0:.2f} s")
    except SmokeError as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    devices = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
