"""Benchmark harness — one function per paper table (+ kernel/system
micro-benchmarks).  Prints ``name,value,paper_value`` CSV rows so every
reproduced number sits next to the paper's.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--table N]
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List

import numpy as np

from repro.launch.compile_cache import enable_compile_cache

PAPER = {
    # Table I: (mha_bytes, actual_bytes, ratio)
    "table1": {"deepseek-v3": (65536, 1152, 57), "llama-3-70b": (32768, 4096, 8),
               "mixtral-8x22b": (24576, 4096, 6), "qwen-2.5-72b": (32768, 4096, 8)},
    # Table III: (status_quo_batch, arch_aware_batch)
    "table3": {"deepseek-v3": (14, 104), "llama-3-70b": (22, 22),
               "mixtral-8x22b": (42, 31), "qwen-2.5-72b": (22, 22)},
    # Table IV: (capacity_label, ttft_p99_s, tput)
    "table4": [("GPU-only", 40, 4.2, 1450), ("+ CPU DRAM", 200, 2.8, 2100),
               ("+ CXL 3.0", 712, 1.8, 2850), ("+ NVMe (GDS)", 4813, 1.5, 3200),
               ("+ RDMA Pool", 38912, 1.1, 3950),
               ("Full system", 38912, 1.1, 4150)],
    # Table V: workload -> (lru, ema, bayes)
    "table5": {"sharegpt": (59.5, 59.5, 69.8), "lmsys": (77.8, 77.8, 84.2),
               "agentic": (66.5, 66.5, 80.5)},
    # Table VI: model -> (raw MB/1k tok, deduped MB, savings %)
    "table6": {"llama-3-70b": (327.7, 251.7, 23.2),
               "deepseek-v3": (70.3, 49.5, 29.6),
               "mixtral-8x22b": (229.4, 205.5, 10.4)},
    # Table VII: system -> (ttft_p50, ttft_p99, tbt_p99_ms, tput, cost)
    "table7": {"vLLM 0.19": (1.2, 4.2, 48, 1450, 0.82),
               "SGLang 0.5.9": (0.9, 3.1, 42, 1850, 0.68),
               "TensorRT-LLM": (0.8, 2.8, 35, 2100, 0.61),
               "FlexGen": (3.2, 12.1, 180, 650, 1.85),
               "Ours (projected)": (0.4, 1.1, 32, 4150, 0.43)},
    # Table VIII: component -> degradation % (L3-70B column)
    "table8": {"arch-aware sizing": -73.8, "bayesian prediction": -28.6,
               "multi-tier placement": -31.2, "head-granular eviction": -8.9,
               "deduplication": -4.2, "rope prefetching": -5.1},
}


def _row(name: str, value, paper=None) -> None:
    pv = "" if paper is None else paper
    print(f"{name},{value},{pv}")


# ---------------------------------------------------------------------------
def table_i() -> None:
    """KV bytes/token/layer: MHA-equivalent vs architecture-aware."""
    from repro.configs.paper_models import PAPER_MODELS
    from repro.core import sizing
    print("# Table I — per-token-per-layer KV bytes (ours vs paper)")
    for name, cfg in PAPER_MODELS.items():
        r = sizing.sizing_report(cfg)
        exp = PAPER["table1"][name]
        _row(f"table1.{name}.mha_bytes", int(r.mha_equivalent), exp[0])
        _row(f"table1.{name}.actual_bytes", int(r.per_token_layer), exp[1])
        _row(f"table1.{name}.ratio", round(r.compression, 1), exp[2])


def table_iii() -> None:
    """Max batch size, status-quo vs arch-aware sizing."""
    from repro.configs.paper_models import PAPER_MODELS
    from repro.core import sizing
    print("# Table III — max batch @30GB KV, n_max=4096, 8-way TP")
    for name, cfg in PAPER_MODELS.items():
        exp = PAPER["table3"][name]
        sq = sizing.status_quo_max_batch(cfg, 30e9, 4096, tp=8)
        aa = sizing.max_batch(cfg, 30e9, 4096)
        _row(f"table3.{name}.status_quo", sq, exp[0])
        _row(f"table3.{name}.arch_aware", aa, exp[1])
        _row(f"table3.{name}.tput_gain", round(aa / max(sq, 1), 1),
             round(exp[1] / exp[0], 1))


def table_v(fast: bool = False) -> Dict[str, float]:
    """Trace-replay hit rates: LRU / EMA / Bayesian x 3 workloads."""
    from repro.traces.replay import run_table_v
    print("# Table V — cache hit rates via trace replay (mean±std)")
    seeds = (0, 1) if fast else (0, 1, 2, 3, 4)
    n_sessions = 60 if fast else 100
    rows = run_table_v(n_sessions=n_sessions, seeds=seeds)
    out = {}
    for r in rows:
        exp = PAPER["table5"][r["workload"]]
        idx = {"lru": 0, "ema": 1, "bayesian": 2}[r["policy"]]
        _row(f"table5.{r['workload']}.{r['policy']}",
             f"{100 * r['hit_mean']:.1f}±{100 * r['hit_std']:.1f}",
             exp[idx])
        out[f"{r['workload']}.{r['policy']}"] = r["hit_mean"]
    return out


def table_vi() -> None:
    """Checkpoint dedup savings per 1,000 cached tokens.

    Scenario: checkpoint the live KV of all concurrent sessions to Tier 5
    (warm-start persistence).  Blocks shared across sessions (system
    prompts / templates / tool contexts) are stored once — the delta
    manifest references them by hash.  Raw sizes are exact per-model
    (eq. 3 x n_layers); the dedup ratio comes from the workload snapshot
    (paper band: 10-30%, varying with the shared-prompt share).
    """
    from collections import defaultdict
    from repro.configs.paper_models import PAPER_MODELS
    from repro.core import sizing
    from repro.core.dedup import ContentStore, content_hash, delta_checkpoint
    from repro.traces import GENERATORS, TraceConfig
    print("# Table VI — KV checkpoint dedup (per 1,000 tokens)")
    # model -> workload snapshot (paper reports one workload mix; we pair
    # each model with a plausible deployment mix and report the band)
    pairing = {"llama-3-70b": "lmsys", "deepseek-v3": "agentic",
               "mixtral-8x22b": "sharegpt"}
    for name, wl in pairing.items():
        cfg = PAPER_MODELS[name]
        per_tok = sizing.per_token_layer_bytes(cfg) * cfg.n_layers
        raw_mb = per_tok * 1000 / 1e6
        trace = GENERATORS[wl](TraceConfig(n_sessions=64, seed=0,
                                           concurrency=32))
        # snapshot: every session's distinct context blocks
        per_session = defaultdict(list)
        for ev in trace:
            if ev.content_id not in per_session[ev.session]:
                per_session[ev.session].append(ev.content_id)
        store = ContentStore()
        blocks = []
        for sid, ids in per_session.items():
            for cid in ids:
                blocks.append((content_hash(cid, salt=name), per_tok * 128))
        manifest = delta_checkpoint(blocks, store)
        savings = manifest.savings
        dedup_mb = raw_mb * (1 - savings)
        exp = PAPER["table6"][name]
        _row(f"table6.{name}.raw_mb", round(raw_mb, 1), exp[0])
        _row(f"table6.{name}.dedup_mb", round(dedup_mb, 1), exp[1])
        _row(f"table6.{name}.savings_pct", round(100 * savings, 1), exp[2])


def table_iv_vii_viii(hit_rates: Dict[str, float]) -> None:
    """Analytical projections: tier increments, end-to-end, ablations."""
    from repro.core.projection import Projector, WorkloadModel
    hit = hit_rates.get("lmsys.bayesian", 0.842)
    lru = hit_rates.get("lmsys.lru", 0.778)
    proj = Projector(wl=WorkloadModel(hit_rate_hot=hit))
    print("# Table IV — projected incremental tier impact (Llama-3-70B,"
          " LMSYS, 128K ctx)")
    for i, r in enumerate(proj.table_iv()):
        exp = PAPER["table4"][i]
        cap_gb = min(r.capacity_bytes, proj.capacity(5)) / 1024 ** 3
        _row(f"table4.{r.config}.capacity_gb", round(cap_gb), exp[1])
        _row(f"table4.{r.config}.ttft_p99_s", round(r.ttft_p99, 1), exp[2])
        _row(f"table4.{r.config}.tput", round(r.tput_tok_s_gpu), exp[3])

    print("# Table VII — projected end-to-end (ours vs published baselines)")
    ours = proj.project(6, name="ours")
    for sysname, exp in PAPER["table7"].items():
        if sysname.startswith("Ours"):
            _row("table7.ours.ttft_p50", round(ours.ttft_p50, 2), exp[0])
            _row("table7.ours.ttft_p99", round(ours.ttft_p99, 2), exp[1])
            _row("table7.ours.tput", round(ours.tput_tok_s_gpu), exp[3])
            _row("table7.ours.cost_mtok", round(ours.cost_per_mtok, 2),
                 exp[4])
    # FlexGen model: CPU+disk tiers only, reactive policy, LRU-grade
    # hits, and a non-paged allocator (0.45x the PagedAttention anchor's
    # achievable batch)
    flexgen = proj.project(4, name="flexgen-style", predictive=False,
                           hit_rate=lru, batch_factor=0.45)
    _row("table7.reactive_offload.tput", round(flexgen.tput_tok_s_gpu),
         PAPER["table7"]["FlexGen"][3])
    _row("table7.speedup_vs_reactive",
         round(ours.tput_tok_s_gpu / flexgen.tput_tok_s_gpu, 1), 6.4)

    print("# Table VIII — projected ablations (throughput delta %)")
    rows = proj.table_viii(lambda pol: hit_rates.get(f"lmsys.{pol}", lru))
    for r in rows:
        exp = PAPER["table8"].get(r["component"])
        _row(f"table8.{r['component']}.delta_pct",
             round(r["delta_pct"], 1), exp)


def table_ix(fast: bool = False) -> None:
    """Hyperparameter sensitivity via LMSYS replay."""
    from repro.configs.paper_models import LLAMA3_70B
    from repro.traces import TraceConfig, lmsys_trace
    from repro.traces.replay import REPLAY_HOT_BLOCKS, replay
    print("# Table IX — parameter sensitivity (LMSYS replay)")
    trace = lmsys_trace(TraceConfig(n_sessions=60 if fast else 100, seed=0))
    hot = REPLAY_HOT_BLOCKS["lmsys"]

    def run(**predictor_kwargs):
        r = replay(trace, LLAMA3_70B, policy="bayesian", workload="lmsys",
                   hot_blocks=hot,
                   predictor_kwargs=predictor_kwargs or None)
        return r.hit_rate

    base = run()
    # eviction recency-bias sweep (the policy's predicted-reuse horizon —
    # our analogue of the paper's EMA decay: how strongly recency is
    # discounted against predicted reuse)
    d_rates = []
    for h in (25.0, 50.0, 100.0, 200.0, 400.0):
        r = replay(trace, LLAMA3_70B, policy="bayesian", workload="lmsys",
                   hot_blocks=hot, policy_kwargs={"horizon": h})
        d_rates.append(r.hit_rate)
    _row("table9.ema_decay(recency_bias).variation_pct",
         round(100 * (max(d_rates) - min(d_rates)) / base, 2), "<5")
    p_rates = [run(prior_alpha=a, prior_beta=a) for a in (0.5, 1.0, 4.0)]
    _row("table9.beta_prior.variation_pct",
         round(100 * (max(p_rates) - min(p_rates)) / base, 2), "<2")
    c_rates = [run(confidence_k=k) for k in (5.0, 20.0, 80.0)]
    _row("table9.confidence_k.variation_pct",
         round(100 * (max(c_rates) - min(c_rates)) / base, 2), "<3")


def replay_benchmark(fast: bool = False, backend: str = None) -> None:
    """Table V at the serving layer: the ShareGPT / LMSYS / agentic
    session traces replayed end-to-end through the live ``ServingEngine``
    (paged pool, CoW prefix sharing, chunked prefill, async tier
    transfers) under a virtual clock — see
    ``src/repro/traces/serving_replay.py``.

    ``hit_pct`` is the engine-level tier-0/1 hit rate over previously-
    seen prompt blocks (the Table V definition measured at the engine);
    ``reuse_pct`` additionally counts blocks served from tiers 2+
    (compute still skipped, fetch paid).  TTFT/TBT/throughput are virtual
    -clock percentiles, where lower-tier fetches stall at paper-scale
    block sizes — the serving-layer coupling between hit rate and
    latency that block-level replay cannot show.
    """
    from repro.kernels.backend import resolve_backend
    from repro.traces.serving_replay import run_replay_serving_table
    print("# Table V (serving) — live-engine trace replay"
          + (" [fast]" if fast else "")
          + f" [kernel backend: {resolve_backend(backend)}]")
    rows = run_replay_serving_table(
        n_sessions=6 if fast else 12, max_turns=4 if fast else 6,
        kernel_backend=backend)
    for r in rows:
        exp = PAPER["table5"][r.workload]
        idx = {"lru": 0, "ema": 1, "bayesian": 2}[r.policy]
        key = f"replay.{r.workload}.{r.policy}"
        _row(f"{key}.hit_pct", round(100 * r.engine_hit_rate, 1), exp[idx])
        _row(f"{key}.reuse_pct", round(100 * r.reuse_rate, 1))
        _row(f"{key}.hits_t0_pool", r.hot_hits_t0)
        _row(f"{key}.hits_t1_dram", r.hot_hits_t1)
        _row(f"{key}.cow_share_hits", r.cow_share_hits)
        _row(f"{key}.inject_hits", r.inject_hits)
        _row(f"{key}.promotions", r.promotions)
        _row(f"{key}.ttft_p50_ms", round(1e3 * r.ttft_p50, 1))
        _row(f"{key}.ttft_p95_ms", round(1e3 * r.ttft_p95, 1))
        _row(f"{key}.tbt_p50_ms", round(1e3 * r.tbt_p50, 1))
        _row(f"{key}.tbt_p95_ms", round(1e3 * r.tbt_p95, 1))
        _row(f"{key}.virtual_tok_per_s", round(r.throughput_tok_s, 1))
        _row(f"{key}.requests", r.requests_done)
        _row(f"{key}.wall_s", round(r.wall_s, 1))


def cluster_benchmark(fast: bool = False, backend: str = None) -> None:
    """Fleet-level trace replay: the LMSYS trace through a multi-replica
    ``ReplicaCluster`` (``serving/cluster.py``), sweeping ``n_replicas x
    routing_policy`` under the shared virtual clock, plus one failover
    cell (2 replicas, kill one mid-replay).

    The headline question: does consistent-hash session affinity
    (``affine``) recover the single-engine hit rate that session-blind
    ``round_robin`` routing fragments across replica-private caches?
    The 1-replica affine cell should match ``--table replay``'s lmsys
    bayesian cell within noise (same harness, fleet of one); at n>=2
    affine must beat round-robin on fleet hit rate.  See
    ``docs/SERVING.md`` for the full column glossary.
    """
    from repro.kernels.backend import resolve_backend
    from repro.traces.serving_replay import (ClusterReplayConfig,
                                             run_cluster_replay,
                                             run_cluster_table)
    print("# Cluster — multi-replica LMSYS replay, n_replicas x routing"
          + (" [fast]" if fast else "")
          + f" [kernel backend: {resolve_backend(backend)}]")
    n_sessions = 6 if fast else 12
    max_turns = 4 if fast else 6
    exp = PAPER["table5"]["lmsys"][2]      # Table V lmsys bayesian
    rows = run_cluster_table(n_replicas=(1, 2) if fast else (1, 2, 4),
                             n_sessions=n_sessions, max_turns=max_turns,
                             kernel_backend=backend)
    for r in rows:
        key = f"cluster.lmsys.n{r.n_replicas}.{r.routing}"
        _row(f"{key}.fleet_hit_pct", round(100 * r.fleet_hit_rate, 1), exp)
        _row(f"{key}.fleet_reuse_pct", round(100 * r.fleet_reuse_rate, 1))
        for p in r.per_replica:
            _row(f"{key}.{p.name}.hit_pct", round(100 * p.hit_rate, 1))
            _row(f"{key}.{p.name}.requests", p.requests_done)
        _row(f"{key}.redispatched", r.redispatched, 0)
        _row(f"{key}.reprefill_tokens", r.reprefill_tokens, 0)
        _row(f"{key}.ttft_p50_ms", round(1e3 * r.ttft_p50, 1))
        _row(f"{key}.ttft_p95_ms", round(1e3 * r.ttft_p95, 1))
        _row(f"{key}.tbt_p95_ms", round(1e3 * r.tbt_p95, 1))
        _row(f"{key}.virtual_tok_per_s", round(r.throughput_tok_s, 1))
        _row(f"{key}.wall_s", round(r.wall_s, 1))
    # shared tier-4 cells: same sweep with the fleet-shared namespace —
    # the incl_shared column is the fleet hit counting cross-replica
    # tier-4 imports (a fabric fetch instead of a re-prefill); the
    # recovered points vs the replica-private cells above come at the
    # cost of shared_fetch stalls (fetched blocks)
    shared_rows = run_cluster_table(
        n_replicas=(1, 2) if fast else (1, 2, 4),
        n_sessions=n_sessions, max_turns=max_turns,
        kernel_backend=backend, shared_tier=True)
    for r in shared_rows:
        key = f"cluster.lmsys.shared.n{r.n_replicas}.{r.routing}"
        _row(f"{key}.fleet_hit_pct", round(100 * r.fleet_hit_rate, 1))
        _row(f"{key}.fleet_hit_incl_shared_pct",
             round(100 * r.fleet_hit_rate_incl_shared, 1), exp)
        _row(f"{key}.shared_fetch_blocks", r.shared_hit_blocks)
        _row(f"{key}.ttft_p95_ms", round(1e3 * r.ttft_p95, 1))
        _row(f"{key}.virtual_tok_per_s", round(r.throughput_tok_s, 1))
        _row(f"{key}.wall_s", round(r.wall_s, 1))
    # prefix-aware routing cell: probe every replica's radix tree and
    # route to the longest live prefix (shared tier on)
    pr = run_cluster_replay(ClusterReplayConfig(
        workload="lmsys", policy="bayesian", n_sessions=n_sessions,
        max_turns=max_turns, n_replicas=2, routing="prefix",
        kernel_backend=backend, shared_tier=True))
    key = "cluster.lmsys.shared.n2.prefix"
    _row(f"{key}.fleet_hit_pct", round(100 * pr.fleet_hit_rate, 1))
    _row(f"{key}.fleet_hit_incl_shared_pct",
         round(100 * pr.fleet_hit_rate_incl_shared, 1), exp)
    _row(f"{key}.shared_fetch_blocks", pr.shared_hit_blocks)
    _row(f"{key}.ttft_p95_ms", round(1e3 * pr.ttft_p95, 1))
    _row(f"{key}.wall_s", round(pr.wall_s, 1))
    # failover cell: 2 affine replicas, one killed mid-replay — the
    # graceful-degradation recomputation tax
    f = run_cluster_replay(ClusterReplayConfig(
        workload="lmsys", policy="bayesian", n_sessions=n_sessions,
        max_turns=max_turns, n_replicas=2, routing="affine",
        fail_replica_after_turns=max(2, n_sessions // 2),
        kernel_backend=backend))
    key = "cluster.lmsys.failover.n2.affine"
    _row(f"{key}.fleet_hit_pct", round(100 * f.fleet_hit_rate, 1))
    _row(f"{key}.redispatched", f.redispatched)
    _row(f"{key}.reprefill_tokens", f.reprefill_tokens)
    _row(f"{key}.failed_replicas", len(f.failed_replicas), 1)
    _row(f"{key}.ttft_p95_ms", round(1e3 * f.ttft_p95, 1))
    _row(f"{key}.requests", f.requests_done)
    _row(f"{key}.wall_s", round(f.wall_s, 1))
    # scale-out cells: third replica joins mid-replay, with and without
    # the warm-up push — the post-join TTFT spike the warm-up removes
    for warm in (False, True):
        j = run_cluster_replay(ClusterReplayConfig(
            workload="lmsys", policy="bayesian", n_sessions=n_sessions,
            max_turns=max_turns, n_replicas=2, routing="affine",
            add_replica_after_turns=max(2, n_sessions // 2),
            shared_tier=True, warmup_on_add=warm,
            kernel_backend=backend))
        key = ("cluster.lmsys.join.n2to3.warmup" if warm
               else "cluster.lmsys.join.n2to3.cold")
        _row(f"{key}.postjoin_ttft_p95_ms",
             round(1e3 * j.postjoin_ttft_p95, 1),
             "<=1.2x steady" if warm else None)
        _row(f"{key}.steady_ttft_p95_ms", round(1e3 * j.steady_ttft_p95, 1))
        _row(f"{key}.warmed_sessions", j.warmed_sessions)
        _row(f"{key}.warmed_blocks", j.warmed_blocks)
        _row(f"{key}.fleet_hit_incl_shared_pct",
             round(100 * j.fleet_hit_rate_incl_shared, 1))
        _row(f"{key}.wall_s", round(j.wall_s, 1))


def segments_benchmark(fast: bool = False, backend: str = None) -> None:
    """Segment-reuse A/B (``--table segments``): every workload replayed
    through the live engine twice on the same seeded trace — content-
    segment index on (mid-prompt blocks resumable beyond the contiguous
    radix prefix) vs the monolithic-radix baseline
    (``EngineConfig(segment_reuse=False)``).

    The headline cell is ShareGPT: its sessions truncate conversation
    history (oldest turns dropped), shifting the surviving turn blocks
    left by whole blocks — a radix tree loses everything past the first
    shifted block, while the content-segment index recovers the blocks
    at their new positions (position-independent reuse; resumed KV
    carries the RoPE/context of its original position — see
    docs/EVALUATION.md §7).  ``delta_pts`` is the engine hit-rate lift
    in percentage points; ``lookup_us_per_call`` is the measured
    segment-index probe cost the lift pays for.
    """
    from repro.kernels.backend import resolve_backend
    from repro.traces.serving_replay import (ServingReplayConfig,
                                             run_serving_replay)
    print("# Segments — segment-index vs monolithic-radix A/B"
          + (" [fast]" if fast else "")
          + f" [kernel backend: {resolve_backend(backend)}]")
    n_sessions = 6 if fast else 12
    max_turns = 4 if fast else 6
    for wl in ("sharegpt", "lmsys", "agentic"):
        rows = {}
        for seg in (False, True):
            rows[seg] = run_serving_replay(ServingReplayConfig(
                workload=wl, n_sessions=n_sessions, max_turns=max_turns,
                kernel_backend=backend, segment_reuse=seg))
        off, on = rows[False], rows[True]
        key = f"segments.{wl}"
        _row(f"{key}.hit_pct_radix", round(100 * off.engine_hit_rate, 1))
        _row(f"{key}.hit_pct_segments", round(100 * on.engine_hit_rate, 1))
        _row(f"{key}.delta_pts",
             round(100 * (on.engine_hit_rate - off.engine_hit_rate), 1),
             ">=5" if wl == "sharegpt" else None)
        _row(f"{key}.reuse_pct_radix", round(100 * off.reuse_rate, 1))
        _row(f"{key}.reuse_pct_segments", round(100 * on.reuse_rate, 1))
        _row(f"{key}.segment_hit_blocks", on.segment_hit_blocks)
        _row(f"{key}.segment_share_hits", on.segment_share_hits)
        _row(f"{key}.segment_inject_hits", on.segment_inject_hits)
        _row(f"{key}.segment_lookups", on.segment_lookups)
        us = (1e6 * on.segment_lookup_s / on.segment_lookups
              if on.segment_lookups else 0.0)
        _row(f"{key}.lookup_us_per_call", round(us, 1))
        _row(f"{key}.ttft_p95_ms_radix", round(1e3 * off.ttft_p95, 1))
        _row(f"{key}.ttft_p95_ms_segments", round(1e3 * on.ttft_p95, 1))
        _row(f"{key}.wall_s",
             round(off.wall_s + on.wall_s, 1))


def chaos_benchmark(fast: bool = False, backend: str = None) -> None:
    """Fault-injected serving replay (``--table chaos``): the LMSYS trace
    through the live engine under seeded tier-fault profiles
    (``core/faults.py``), sweeping fault severity:

      * ``control`` — no injector attached.  The fault path is inert, so
        this row must reproduce ``--table segments``'s lmsys
        segment-reuse cell exactly (same config, same seed).
      * ``pressure_control`` — fault-free baseline for every faulted
        row.  The default replay capacities never fill the paper-scale
        tiers 2-5 (no traffic, so no fault exposure); the faulted cells
        cap tiers 0-3 at 16 blocks each, cascading real demote/promote
        traffic into NVMe and the RDMA pool.
      * ``transient_1e-3`` / ``transient_1e-2`` — per-op transient
        read/write error rates on tiers 2-5, plus a 10x-lower payload
        corruption rate.  Transient errors are absorbed by bounded
        retries (``retries``); exhausted budgets escalate
        (``io_errors``) and the fetch converts to a recompute; corrupt
        payloads are caught by the crc gate (``integrity_failures``).
      * ``nvme_brownout`` — 25% of tier-3 ops land in a 10x latency
        brownout (inflation shows in TTFT p99 via the stall model, no
        errors).  ``rdma_flap`` — tier-4 ring nodes flap under
        in-flight ops, failing them transiently.

    Acceptance invariants asserted per row: zero hung requests
    (``turns_submitted == requests_done``) and every injected corruption
    caught by its crc32 check before any payload reaches a decode
    (``integrity_failures == injected_corruptions``).
    """
    from repro.core.faults import FaultProfile
    from repro.kernels.backend import resolve_backend
    from repro.traces.serving_replay import (ServingReplayConfig,
                                             run_serving_replay)
    print("# Chaos — fault-injected lmsys replay, severity sweep"
          + (" [fast]" if fast else "")
          + f" [kernel backend: {resolve_backend(backend)}]")
    n_sessions = 6 if fast else 12
    max_turns = 4 if fast else 6
    pressure = dict(hot_blocks=16, t1_blocks=16, t2_blocks=16,
                    t3_blocks=16)
    cells = [("control", None, {}),
             ("pressure_control", None, pressure)]
    for rate in (1e-3, 1e-2):
        cells.append((f"transient_{rate:g}",
                      {t: FaultProfile(read_error_rate=rate,
                                       write_error_rate=rate,
                                       corruption_rate=rate / 10)
                       for t in (2, 3, 4, 5)}, pressure))
    cells.append(("nvme_brownout",
                  {3: FaultProfile(brownout_rate=0.25,
                                   brownout_latency_mult=10.0)}, pressure))
    cells.append(("rdma_flap", {4: FaultProfile(flap_rate=0.05)},
                  pressure))
    baselines: Dict[str, object] = {}
    for name, profiles, extra in cells:
        r = run_serving_replay(ServingReplayConfig(
            workload="lmsys", policy="bayesian", n_sessions=n_sessions,
            max_turns=max_turns, kernel_backend=backend,
            fault_profiles=profiles, fault_seed=7, **extra))
        cfg_key = repr(sorted(extra.items()))
        if profiles is None:
            baselines[cfg_key] = r
        base = baselines.get(cfg_key)
        key = f"chaos.lmsys.{name}"
        hung = r.turns_submitted - r.requests_done
        corruptions = r.injected.get("injected_corruptions", 0)
        _row(f"{key}.hit_pct", round(100 * r.engine_hit_rate, 1))
        _row(f"{key}.ttft_p99_ms", round(1e3 * r.ttft_p99, 1))
        if profiles is not None and base is not None and base.ttft_p99 > 0:
            _row(f"{key}.ttft_p99_inflation_x",
                 round(r.ttft_p99 / base.ttft_p99, 2))
        _row(f"{key}.retries", r.retries)
        _row(f"{key}.io_errors", r.io_errors)
        _row(f"{key}.injected_corruptions", corruptions)
        _row(f"{key}.integrity_failures", r.integrity_failures, corruptions)
        _row(f"{key}.fetch_recomputes", r.fetch_recomputes)
        _row(f"{key}.retry_delay_ms", round(1e3 * r.retry_delay_s, 2))
        _row(f"{key}.unhealthy_tiers",
             sum(1 for s in r.tier_health.values() if s != "healthy"), 0)
        _row(f"{key}.hung_requests", hung, 0)
        _row(f"{key}.requests", r.requests_done)
        _row(f"{key}.wall_s", round(r.wall_s, 1))
        assert hung == 0, f"chaos {name}: {hung} hung requests"
        assert r.integrity_failures == corruptions, (
            f"chaos {name}: {corruptions} corruptions injected, "
            f"{r.integrity_failures} caught")


def micro_benchmarks() -> None:
    """System micro-benchmarks backing the paper's latency claims."""
    from repro.core.bayesian import BayesianReusePredictor
    from repro.core.dedup import RadixTree
    print("# Micro — component latencies")
    tree = RadixTree(128)
    rng = np.random.default_rng(0)
    seqs = [list(rng.integers(0, 1000, size=512)) for _ in range(200)]
    for i, s in enumerate(seqs):
        tree.insert(s, [f"b{i}-{j}" for j in range(4)])
    t0 = time.perf_counter()
    n = 0
    for s in seqs:
        tree.match(s)
        n += 4
    us = (time.perf_counter() - t0) / n * 1e6
    _row("micro.radix_lookup_us_per_block", round(us, 2), "<1")
    pred = BayesianReusePredictor()
    t0 = time.perf_counter()
    for i in range(20000):
        pred.observe("system_prompt", "same_tool_repeat", i % 3 != 0)
    us = (time.perf_counter() - t0) / 20000 * 1e6
    _row("micro.bayes_update_us", round(us, 2), "O(1)")
    t0 = time.perf_counter()
    for _ in range(20000):
        pred.reuse_probability("system_prompt", "same_tool_repeat")
    us = (time.perf_counter() - t0) / 20000 * 1e6
    _row("micro.bayes_query_us", round(us, 2), "O(1)")


def serving_benchmark(paged: bool, fast: bool = False,
                      backend: str = None) -> None:
    """Live-engine throughput through the paged block-table KV path
    (``--paged``, default) or the dense slot fallback (``--no-paged``),
    under the selected kernel backend (``--backend``; default: compiled
    xla off-TPU).

    The paged rows also report the async tier-transfer worker's stats:
    transfers complete off the step loop, so ``step_blocked_on_transfer``
    is structurally 0 — preemption demotions and RoPE prefetch
    promotions run on the worker thread while decode proceeds.
    """
    from repro.config import reduce_config
    from repro.configs import get_config
    from repro.serving import EngineConfig, SamplingParams, ServingEngine
    mode = "paged" if paged else "dense"
    cfg = reduce_config(get_config("llama3.2-1b"))
    eng = ServingEngine(cfg, EngineConfig(max_len=128,
                                          kv_budget_bytes=1e6,
                                          paged=paged,
                                          kernel_backend=backend))
    # the dense path never calls the paged ops (plain XLA attention),
    # so the backend knob only applies to the paged rows
    be_label = eng.kernel_backend if paged else "n/a (dense path)"
    print(f"# Serving — {mode} engine A/B (reduced llama3.2-1b, "
          f"kernel backend: {be_label})")
    rng = np.random.default_rng(0)
    templates = [[int(t) for t in rng.integers(0, 200, size=64)]
                 for _ in range(3)]
    n_req = 8 if fast else 16
    for i in range(n_req):
        user = [int(t) for t in rng.integers(0, 200, size=16)]
        eng.submit(templates[i % 3] + user,
                   params=SamplingParams(max_new_tokens=8),
                   session_id=f"s{i}", block_type="system_prompt")
    eng.step()                       # exclude jit compile from the timing
    warm_tokens = sum(len(r.generated) for r in eng.scheduler.done) + \
        sum(len(r.generated) for r in eng.scheduler.running.values())
    # separate timing windows: steps that ran prefill work (chunk grants,
    # or monolithic prefill at admission) vs pure-decode steps.  PR 2's
    # single window mixed interpret-mode chunk prefills into the decode
    # tok/s denominator, which read as a paged-decode regression on CPU.
    t_prefill = t_decode = 0.0
    prefill_window_tokens = decode_window_tokens = 0
    t0 = time.perf_counter()
    while eng.scheduler.has_work() and eng.steps < 10_000:
        running_before = set(eng.scheduler.running)
        done_before = len(eng.scheduler.done)
        ts = time.perf_counter()
        produced = eng.step()
        dt_step = time.perf_counter() - ts
        # admissions ran (monolithic) prefill this step: a request newly
        # in running — or admitted and finished within the step
        now_ids = set(eng.scheduler.running) | {
            r.request_id for r in eng.scheduler.done[done_before:]}
        admitted = bool(now_ids - running_before)
        if eng.last_step_prefill_tokens > 0 or admitted:
            t_prefill += dt_step
            prefill_window_tokens += eng.last_step_prefill_tokens
        else:
            t_decode += dt_step
            decode_window_tokens += produced
        if produced == 0 and not eng.scheduler.running \
                and eng.scheduler.blocked:
            eng.idle_transfer_waits += 1
            time.sleep(1e-3)
    dt = time.perf_counter() - t0
    stats = eng.stats()
    sch = stats["scheduler"]
    _row(f"serving.{mode}.kernel_backend", be_label)
    _row(f"serving.{mode}.done", sch["done"])
    _row(f"serving.{mode}.steps", stats["steps"])
    _row(f"serving.{mode}.tok_per_s",
         round((sch["generated_tokens"] - warm_tokens) / dt, 1))
    _row(f"serving.{mode}.prefill_window_s", round(t_prefill, 3))
    _row(f"serving.{mode}.decode_window_s", round(t_decode, 3))
    if t_decode > 0:
        # decode-phase throughput over pure-decode steps only — the
        # apples-to-apples paged-vs-dense decode comparison
        _row(f"serving.{mode}.decode_tok_per_s",
             round(decode_window_tokens / t_decode, 1))
    if t_prefill > 0:
        _row(f"serving.{mode}.prefill_tok_per_s",
             round(prefill_window_tokens / t_prefill, 1))
    _row(f"serving.{mode}.prefix_hit_blocks", sch["prefix_hit_blocks"])
    if stats.get("allocator"):
        al = stats["allocator"]
        _row(f"serving.{mode}.pages_peak", al["peak_in_use"])
        _row(f"serving.{mode}.cow_shares", al["shares"])
        _row(f"serving.{mode}.cow_copies", al["cow_copies"])
    aw = stats.get("async_transfers")
    if aw:
        _row(f"serving.{mode}.async_completed", aw["completed"])
        _row(f"serving.{mode}.async_max_inflight", aw["max_inflight"])
        _row(f"serving.{mode}.async_sim_time_s",
             round(aw["sim_time_total"], 6))
        # measured: run() iterations that had nothing to decode because
        # every live request was waiting on a KV fetch
        _row(f"serving.{mode}.idle_transfer_waits",
             stats["idle_transfer_waits"], 0)
    eng.shutdown()


def ttft_benchmark(chunked: bool, fast: bool = False,
                   backend: str = None) -> None:
    """TTFT under mixed load: short decode streams with long prompts
    arriving mid-stream, chunked vs monolithic prefill (``--chunked`` /
    ``--no-chunked`` A/B).

    The paper's headline serving claim is TTFT tail latency (Table VII
    projects 0.4s p50 / 1.1s p99 for the full system, a 1.4-2.1x
    reduction over baselines); the chunked rows show the structural
    mechanism — no single step prefills more than ``max_step_tokens``
    prompt tokens, so the worst-case step time (the inter-token stall
    running decodes see when a long prompt lands) stays bounded, where
    the monolithic path runs the whole prompt inline in one step.
    """
    from repro.config import reduce_config
    from repro.configs import get_config
    from repro.serving import EngineConfig, SamplingParams, ServingEngine
    mode = "chunked" if chunked else "monolithic"
    cfg = reduce_config(get_config("llama3.2-1b"))
    eng = ServingEngine(cfg, EngineConfig(
        max_len=640, kv_budget_bytes=2.5e6, max_step_tokens=96,
        prefill_chunk_tokens=32, chunked_prefill=chunked,
        kernel_backend=backend))
    print(f"# TTFT A/B — {mode} prefill, short decodes + mid-stream "
          f"long prompts (reduced llama3.2-1b, kernel backend: "
          f"{eng.kernel_backend})")
    rng = np.random.default_rng(0)

    def _prompt(n):
        return [int(t) for t in rng.integers(0, 250, size=n)]

    # warm the jit caches (prefill / chunk / decode) off the clock
    eng.submit(_prompt(40), params=SamplingParams(max_new_tokens=2))
    eng.submit(_prompt(500), params=SamplingParams(max_new_tokens=2))
    eng.run()
    eng.scheduler.done.clear()

    n_short = 4 if fast else 8
    n_long = 1 if fast else 2
    shorts = [eng.submit(_prompt(24),
                         params=SamplingParams(max_new_tokens=24))
              for _ in range(n_short)]
    for _ in range(3):
        eng.step()
    longs = [eng.submit(_prompt(480),
                        params=SamplingParams(max_new_tokens=8))
             for _ in range(n_long)]
    # tokens produced during the untimed ramp-up steps don't count
    warm_tokens = sum(len(r.generated) for r in shorts)
    t0 = time.perf_counter()
    step_max = 0.0
    while eng.scheduler.has_work():
        ts = time.perf_counter()
        eng.step()
        step_max = max(step_max, time.perf_counter() - ts)
    dt = time.perf_counter() - t0

    def pct(vals, p):
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(p * len(vals)))]

    short_ttfts = [r.ttft for r in shorts]
    long_ttfts = [r.ttft for r in longs]
    gen = sum(len(r.generated) for r in shorts + longs) - warm_tokens
    exp = PAPER["table7"]["Ours (projected)"]
    _row(f"ttft.{mode}.short_p50_ms", round(1e3 * pct(short_ttfts, .5), 1))
    _row(f"ttft.{mode}.short_p99_ms", round(1e3 * pct(short_ttfts, .99), 1))
    _row(f"ttft.{mode}.long_p50_ms", round(1e3 * pct(long_ttfts, .5), 1))
    _row(f"ttft.{mode}.paper_ttft_p50_s", "", exp[0])
    _row(f"ttft.{mode}.paper_ttft_p99_s", "", exp[1])
    _row(f"ttft.{mode}.tok_per_s", round(gen / dt, 1))
    _row(f"ttft.{mode}.max_step_ms", round(1e3 * step_max, 1))
    if chunked:
        _row(f"ttft.{mode}.max_step_prompt_tokens",
             eng.max_step_prefill_tokens, "<=96")
    eng.shutdown()


def steploop_benchmark(fast: bool = False, backend: str = None) -> None:
    """Step-loop dispatch-vs-compute microbench (``--table steploop``).

    Fused vs unfused rows per batch; the acceptance gate on the fused
    largest-batch row is host_overhead < kernel_time (the ROADMAP
    "host-overhead war" target: batch 16 on CPU-xla).  ``--fast``
    (CI smoke) shrinks batch and step counts to fit the smoke budget —
    the gate row is only meaningful at full scale.
    """
    from benchmarks.steploop_bench import run_steploop_table
    batches = (8,) if fast else (4, 16)
    steps = 10 if fast else 30
    print(f"# Step loop — dispatch vs compute per stage "
          f"(reduced llama3.2-1b){' [fast]' if fast else ''}")
    run_steploop_table(batches=batches, backend=backend, steps=steps)


def slo_benchmark(fast: bool = False, backend: str = None) -> None:
    """Latency-under-load curve (``--table slo``): offered-QPS sweep
    through the wall-clock ``ServingFrontend`` under open-loop Poisson
    load, with and without SLO admission control.

    The sweep runs the *real* front-end pump (admission, SLO queue,
    streaming delivery) over the live engine under a virtual clock with
    the per-step cost pinned to the fused-step latency measured by
    ``--table steploop`` (≈5 ms at batch 16 on CPU-xla) — so the table
    is deterministic and the rates are meaningful fractions of true
    engine capacity.  ``max_step_tokens`` is capped to put the knee of
    the curve inside the sweep.  At the top (overloaded) rate an
    uncontrolled A/B (infinite budget) shows p99 TTFT breaching the
    budget that the admission controller holds.
    """
    from repro.serving.frontend import ServingFrontend, SLOConfig, VirtualClock
    from repro.traces.loadgen import offered_summary, trace_load
    from repro.traces.serving_replay import ServingReplayConfig, build_engine

    step_s = 5e-3                   # --table steploop fused batch-16 CPU-xla
    budget_s = 0.150
    rates = (8.0, 32.0, 64.0) if fast else (8.0, 16.0, 32.0, 64.0)
    n_req = 40 if fast else 120
    workload = "lmsys"
    print(f"# SLO sweep — open-loop {workload} load through "
          f"ServingFrontend (ttft budget {budget_s * 1e3:.0f} ms, "
          f"virtual step {step_s * 1e3:.0f} ms)"
          f"{' [fast]' if fast else ''}")

    def run_rate(rate: float, budget: float) -> dict:
        rcfg = ServingReplayConfig(workload=workload, n_sessions=8,
                                   seed=0, async_transfers=False,
                                   kernel_backend=backend,
                                   max_step_tokens=32)
        fe = ServingFrontend(
            build_engine(rcfg), clock=VirtualClock(), step_time_s=step_s,
            slo=SLOConfig(ttft_budget_s=budget, action="shed"))
        arrivals = trace_load(workload, rate, n_requests=n_req, seed=7,
                              n_sessions=8, max_turns=3)
        fe.serve_schedule(arrivals)
        fe.check_ledger()
        st = fe.stats()
        st["offered_qps"] = offered_summary(arrivals)["offered_qps"]
        fe.stop()
        return st

    for rate in rates:
        st = run_rate(rate, budget_s)
        key = f"slo.qps{rate:g}"
        _row(f"{key}.offered_qps", round(st["offered_qps"], 1))
        _row(f"{key}.offered", st["offered"])
        _row(f"{key}.done", st["done"])
        _row(f"{key}.shed", st["shed"])
        _row(f"{key}.goodput", st["goodput"])
        _row(f"{key}.ttft_p50_ms", round(1e3 * st["ttft_p50"], 1))
        _row(f"{key}.ttft_p99_ms", round(1e3 * st["ttft_p99"], 1))
        _row(f"{key}.tbt_p50_ms", round(1e3 * st["tbt_p50"], 1))
        _row(f"{key}.tbt_p99_ms", round(1e3 * st["tbt_p99"], 1))
    # uncontrolled A/B at the top (shed-inducing) rate
    st = run_rate(rates[-1], float("inf"))
    key = f"slo.qps{rates[-1]:g}.uncontrolled"
    _row(f"{key}.done", st["done"])
    _row(f"{key}.shed", st["shed"])
    _row(f"{key}.ttft_p50_ms", round(1e3 * st["ttft_p50"], 1))
    _row(f"{key}.ttft_p99_ms", round(1e3 * st["ttft_p99"], 1))
    _row("slo.budget_ms", round(1e3 * budget_s, 1))


def kernel_benchmarks(backend: str = None, fast: bool = False) -> None:
    """Per-op kernel-backend microbenchmark (``--table kernels``).

    Times every paged op under each available backend — ``xla``
    (compiled jnp gathers, the off-TPU serving default) vs ``interpret``
    (the Pallas interpreter, the old off-TPU path) and ``pallas`` when
    running on a TPU — across decode/prefill x GQA/MQA/MLA shapes, so a
    backend regression is measurable in isolation from the engine.
    Also prints the xla-vs-oracle allclose gate per op (full sweeps in
    ``tests/test_xla_backend.py``).
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.kernels.backend import on_tpu

    backends = [backend] if backend else (
        ["xla", "interpret"] + (["pallas"] if on_tpu() else []))
    print(f"# Kernels — per-op latency by backend ({'/'.join(backends)}) "
          "+ xla-vs-oracle allclose")
    rng = np.random.default_rng(0)

    def arr(shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    def table(b, pages, n):
        return jnp.asarray(
            rng.permutation(n)[:b * pages].reshape(b, pages), jnp.int32)

    # decode: one query per request over 4 pages x 64 tokens
    b, page, pages, hd = 4, 64, 4, 64
    n = b * pages + 2
    cases = []
    for name, hq, hkv in (("decode_gqa", 8, 2), ("decode_mha", 4, 4),
                          ("decode_mqa", 8, 1)):
        # the ops take the stacked [L, N, page, Hkv, hd] pool and a layer
        q, kp, vp = arr((b, hq, hd)), arr((1, n, page, hkv, hd)), \
            arr((1, n, page, hkv, hd))
        bt = table(b, pages, n)
        ln = jnp.asarray(rng.integers(page, pages * page, size=b), jnp.int32)
        cases.append((name,
                      lambda be, q=q, kp=kp, vp=vp, bt=bt, ln=ln:
                      ops.paged_decode(q, kp, vp, bt, ln, 0, backend=be),
                      lambda q=q, kp=kp, vp=vp, bt=bt, ln=ln:
                      ops.paged_decode_ref(q, kp[0], vp[0], bt, ln)))
    dl, dr = 64, 16
    msc = (hd + dr) ** -0.5          # the query's nope width is hd
    ql, qr = arr((b, 8, dl)), arr((b, 8, dr))
    lat = arr((n, page, dl + dr))
    bt = table(b, pages, n)
    ln = jnp.asarray(rng.integers(page, pages * page, size=b), jnp.int32)
    cases.append(("decode_mla",
                  lambda be: ops.mla_decode(ql, qr, lat, bt, ln,
                                            d_latent=dl, scale=msc,
                                            backend=be),
                  lambda: ops.mla_decode_ref(ql, qr, lat, bt, ln, dl,
                                             scale=msc)))
    # prefill: a 64-token chunk over 3 resident pages
    c, ppages = 64, 3
    np_ = 2 * ppages + 2
    pq, pkc, pvc = arr((2, c, 8, hd)), arr((2, c, 2, hd)), arr((2, c, 2, hd))
    pkp, pvp = arr((1, np_, page, 2, hd)), arr((1, np_, page, 2, hd))
    pbt = table(2, ppages, np_)
    off = jnp.asarray([page * 2 + 11, 0], jnp.int32)
    cases.append(("prefill_gqa",
                  lambda be: ops.paged_prefill(pq, pkc, pvc, pkp, pvp,
                                               pbt, off, 0, backend=be),
                  lambda: ops.paged_prefill_ref(pq, pkc, pvc, pkp[0],
                                                pvp[0], pbt, off)))
    mql, mqr = arr((2, c, 8, dl)), arr((2, c, 8, dr))
    mlc, mlp = arr((2, c, dl + dr)), arr((np_, page, dl + dr))
    mbt = table(2, ppages, np_)
    cases.append(("prefill_mla",
                  lambda be: ops.mla_prefill(mql, mqr, mlc, mlp, mbt, off,
                                             d_latent=dl, scale=msc,
                                             backend=be),
                  lambda: ops.mla_prefill_ref(mql, mqr, mlc, mlp, mbt,
                                              off, dl, scale=msc)))

    for name, run, oracle in cases:
        err = float(jnp.max(jnp.abs(run("xla") - oracle())))
        _row(f"kernels.{name}.xla_vs_oracle_max_err", f"{err:.2e}", "<1e-4")
        lat_us = {}
        for be in backends:
            jax.block_until_ready(run(be))      # compile / first call
            iters = (3 if be == "interpret" else 20) * (1 if fast else 2)
            t0 = time.perf_counter()
            for _ in range(iters):
                out = run(be)
            jax.block_until_ready(out)
            lat_us[be] = (time.perf_counter() - t0) / iters * 1e6
            _row(f"kernels.{name}.{be}.us", round(lat_us[be], 1))
        if "xla" in lat_us and "interpret" in lat_us:
            _row(f"kernels.{name}.interpret_over_xla",
                 round(lat_us["interpret"] / lat_us["xla"], 1), ">1")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--table", default=None,
                    help="run one: 1,3,4,5,6,7,8,9,micro,kernels,serving,"
                         "ttft,replay,cluster,segments,chaos,steploop,slo")
    ap.add_argument("--paged", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serving benchmark: paged block-table KV path "
                         "(--no-paged = dense slot A/B fallback)")
    ap.add_argument("--chunked", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="TTFT benchmark: chunked token-budget prefill "
                         "(--no-chunked = monolithic prefill A/B)")
    ap.add_argument("--backend", default=None,
                    choices=("pallas", "interpret", "xla"),
                    help="kernel backend for the engine-driving tables "
                         "(serving/ttft/replay/cluster) and the kernels "
                         "microbench; default resolves via "
                         "REPRO_KERNEL_BACKEND, else pallas on TPU / "
                         "xla elsewhere")
    args = ap.parse_args()
    enable_compile_cache()
    t0 = time.time()
    sel = args.table
    hit_rates: Dict[str, float] = {}
    if sel in (None, "1"):
        table_i()
    if sel in (None, "3"):
        table_iii()
    if sel in (None, "5", "4", "7", "8"):
        hit_rates = table_v(fast=args.fast)
    if sel in (None, "6"):
        table_vi()
    if sel in (None, "4", "7", "8"):
        table_iv_vii_viii(hit_rates)
    if sel in (None, "9"):
        table_ix(fast=args.fast)
    if sel in (None, "micro"):
        micro_benchmarks()
    if sel in (None, "kernels"):
        kernel_benchmarks(backend=args.backend, fast=args.fast)
    if sel == "serving":
        # explicit A/B: both modes back to back
        serving_benchmark(paged=True, fast=args.fast, backend=args.backend)
        serving_benchmark(paged=False, fast=args.fast, backend=args.backend)
    elif sel is None:
        serving_benchmark(paged=args.paged, fast=args.fast,
                          backend=args.backend)
    if sel == "ttft":
        # explicit A/B: both prefill modes back to back
        ttft_benchmark(chunked=True, fast=args.fast, backend=args.backend)
        ttft_benchmark(chunked=False, fast=args.fast, backend=args.backend)
    elif sel is None:
        ttft_benchmark(chunked=args.chunked, fast=args.fast,
                       backend=args.backend)
    if sel == "replay":
        replay_benchmark(fast=args.fast, backend=args.backend)
    if sel == "cluster":
        cluster_benchmark(fast=args.fast, backend=args.backend)
    if sel == "segments":
        segments_benchmark(fast=args.fast, backend=args.backend)
    if sel == "chaos":
        chaos_benchmark(fast=args.fast, backend=args.backend)
    if sel == "steploop":
        steploop_benchmark(fast=args.fast, backend=args.backend)
    if sel == "slo":
        slo_benchmark(fast=args.fast, backend=args.backend)
    print(f"# done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
