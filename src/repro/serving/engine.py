"""The serving engine: a unified token-budget step loop mixing chunked
prefill with decode in one batch per step, over a paged block-table KV
cache (dense slots as ``paged=False`` fallback), with the paper's
predictive multi-tier cache manager on the prompt-block level and an
async tier-transfer worker off the step loop.

Per step:
  1. poll the async transfer worker: completed demotions release their
     staging buffers, completed fetches un-park restoring requests;
  2. admit waiting requests into free slots — radix-tree prefix match
     maps pool-resident prefix pages straight into the new request's
     block table (copy-on-write sharing; lower-tier blocks are copied
     from their payloads) and advances the prefill chunk cursor for
     free; the unmatched suffix enters ``Phase.PREFILL``;
  3. budget-select the mixed batch (``Scheduler.plan_step``): every
     decode stream gets one token, prefill chunks fill the rest of
     ``max_step_tokens`` — a 4k-token prompt never stalls running
     decodes;
  4. granted prefill chunks run through the block-table-aware Pallas
     flash-prefill kernels (causal within the chunk, full attention to
     prior pages) and scatter into the pool via
     ``PagedKVCache.write_chunk``; a request whose cursor reaches the
     prompt end transitions PREFILL -> DECODE;
  5. one batched decode over the decoding slots through the Pallas paged
     attention kernels (block-table indirection; MLA uses the absorbed
     latent kernel); sample next tokens;
  6. finished requests release their slot's page references (refcounted;
     manager-pinned prefix pages linger for cross-request reuse);
  7. RoPE prefetch promotions are submitted to the transfer worker
     instead of running inline;
  8. stragglers (per-phase deadline) are preempted: their KV payload is
     handed to the async worker for demotion (double-buffered — an
     immediate restore is served from the staging buffer; after the
     write lands, restore is an async fetch the scheduler waits on
     without blocking decode).

``EngineConfig(chunked_prefill=False)`` (and the dense ``paged=False``
layout, which has no paged pool to chunk into) falls back to the
original monolithic prefill-at-admission for A/B comparison.
"""
from __future__ import annotations

import contextlib
import functools
import time
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import MLA, ModelConfig
from repro.core import sizing
from repro.core.cache_manager import PredictiveCacheManager
from repro.core.tiers import (TPU_V5E_TIER_SPECS, AsyncTierTransferWorker,
                              TierSpec, TransferRequest)
from repro.kernels.backend import resolve_backend
from repro.models.model import build_model
from repro.serving import sampler as sampler_mod
from repro.serving import spans
from repro.serving.kvcache import PagedKVCache, SlotKVCache
from repro.serving.request import Phase, Request, SamplingParams
from repro.serving.scheduler import Scheduler, SchedulerConfig


@dataclass
class EngineConfig:
    max_len: int = 512
    kv_budget_bytes: float = float(1 << 30)
    policy: str = "bayesian"
    enable_dedup: bool = True
    enable_prefetch: bool = True
    enable_multi_tier: bool = True
    status_quo_sizing: bool = False
    deadline_s: float = 600.0
    seed: int = 0
    tier_specs: Tuple[TierSpec, ...] = TPU_V5E_TIER_SPECS
    pad_prefill_to: int = 32          # bucket suffix lengths (jit cache)
    paged: bool = True                # block-table KV pool (False: dense A/B)
    page_tokens: int = 64             # physical page size (kernel tile)
    async_transfers: bool = True      # tier moves off the step loop
    chunked_prefill: bool = True      # mixed token-budget batches
    #                                   (False: monolithic prefill A/B)
    prefill_chunk_tokens: int = 64    # kernel chunk size (jit cache)
    max_step_tokens: int = 256        # per-step token budget
    tier0_from_budget: bool = True    # rescale tier-0 capacity to
    #                                   kv_budget_bytes (False: trace replay
    #                                   keeps the pressure capacities of the
    #                                   supplied tier_specs verbatim)
    kernel_backend: Optional[str] = None   # paged-op backend: "pallas" |
    #                                   "interpret" | "xla"; None resolves
    #                                   via kernels/backend.py (the
    #                                   REPRO_KERNEL_BACKEND env var, else
    #                                   pallas on TPU / xla elsewhere)
    segment_reuse: bool = True        # content-segment index: resume
    #                                   pool-resident blocks mid-prompt
    #                                   beyond the contiguous radix prefix
    #                                   (False: monolithic-radix A/B)
    fault_injector: Optional[object] = None   # core/faults.FaultInjector:
    #                                   seeded per-tier fault model threaded
    #                                   through the manager's hierarchy and
    #                                   the transfer worker (None: the fault
    #                                   layer is completely inert)
    retry_policy: Optional[object] = None     # core/faults.RetryPolicy
    #                                   (None + injector -> defaults)
    transfer_timeout_s: float = 30.0  # async transfer wall deadline: a
    #                                   stalled transfer is shed as a failed
    #                                   TransferEvent after this long
    fused_step: bool = True           # decode attention + logits + sampling
    #                                   in ONE jitted closure with the KV
    #                                   state donated through it and the
    #                                   device block-table state cached
    #                                   across steady-state steps (False:
    #                                   per-request sampling dispatches, the
    #                                   pre-PR7 A/B path — greedy decode is
    #                                   token-identical either way)


def _named(name: str, fn, *args, **kwargs):
    """``functools.partial(fn, *args, **kwargs)`` named ``name``: jitted,
    its program is ``jit_<name>`` (an unnamed partial lowers to
    ``jit__unknown``)."""
    f = functools.partial(fn, *args, **kwargs)
    f.__name__ = name
    return f


def _fused_paged_step(model, backend, params, state, tokens, active, rng,
                      temperature, top_k, top_p):
    """One fused decode step over the paged pool: block-table gather,
    paged attention, logits projection and per-row sampling in a single
    jitted program (jitted with ``state`` donated — the KV pools are
    updated in place, never copied).

    ``active`` masks the rows actually decoding this step: masked rows'
    lengths stay put (the model returns +1 for every row), so the
    returned state is exactly next step's input when the decode set is
    unchanged — the engine hands it straight back without a host
    rebuild."""
    logits, new_state = model.decode_step_paged(params, state, tokens,
                                                backend=backend)
    new_state["lengths"] = state["lengths"] + active
    toks = sampler_mod.sample_batched(logits, rng, temperature, top_k,
                                      top_p)
    return toks, new_state


def _fused_dense_step(model, params, state, tokens, rng, temperature,
                      top_k, top_p):
    """Fused decode + sampling for the dense slot layout (lengths keep
    the unfused dense semantics: every row advances)."""
    logits, new_state = model.decode_step(params, state, tokens)
    toks = sampler_mod.sample_batched(logits, rng, temperature, top_k,
                                      top_p)
    return toks, new_state


class ServingEngine:
    """``device`` pins the replica to one accelerator: its parameters
    and KV pool are committed there, and every step runs with it as the
    default device, so the jitted steps execute on it (``None``: JAX's
    default device)."""

    def __init__(self, cfg: ModelConfig,
                 engine_cfg: Optional[EngineConfig] = None,
                 params=None, rng: Optional[jax.Array] = None,
                 device: Optional[jax.Device] = None):
        # a fresh EngineConfig per engine: a shared default instance
        # would leak config mutations across engines
        engine_cfg = EngineConfig() if engine_cfg is None else engine_cfg
        self.cfg = cfg
        self.ecfg = engine_cfg
        self.device = device
        self.model = build_model(cfg)
        rng = jax.random.PRNGKey(engine_cfg.seed) if rng is None else rng
        with self._on_device():
            params = params if params is not None else \
                self.model.init_params(rng)
        self.params = (params if device is None
                       else jax.device_put(params, device))
        self.scheduler = Scheduler(cfg, SchedulerConfig(
            kv_budget_bytes=engine_cfg.kv_budget_bytes,
            max_len=engine_cfg.max_len,
            deadline_s=engine_cfg.deadline_s,
            status_quo_sizing=engine_cfg.status_quo_sizing,
            max_step_tokens=engine_cfg.max_step_tokens))
        self.paged = engine_cfg.paged and self.model.supports_paged_decode()
        # resolved once per engine: the paged attention ops' backend
        # (compiled Pallas on TPU, jitted XLA gathers elsewhere; the env
        # var / config override is validated here, at construction)
        self.kernel_backend = resolve_backend(engine_cfg.kernel_backend)
        self.fused = engine_cfg.fused_step
        if self.paged:
            bt = sizing.block_tokens(cfg)
            if bt % engine_cfg.page_tokens != 0:
                raise ValueError(
                    f"page_tokens {engine_cfg.page_tokens} must divide the "
                    f"manager block size {bt}")
            self.kv = PagedKVCache(self.model, self.scheduler.n_slots,
                                   engine_cfg.max_len,
                                   page_tokens=engine_cfg.page_tokens,
                                   device=device)
            self._decode = jax.jit(
                _named("decode", self.model.decode_step_paged,
                       backend=self.kernel_backend),
                donate_argnums=(1,))
            self._fused_decode = jax.jit(
                _named("fused_decode", _fused_paged_step, self.model,
                       self.kernel_backend),
                donate_argnums=(1,))
        else:
            self.kv = SlotKVCache(self.model, self.scheduler.n_slots,
                                  engine_cfg.max_len, device=device)
            self._decode = jax.jit(_named("decode", self.model.decode_step),
                                   donate_argnums=(1,))
            self._fused_decode = jax.jit(
                _named("fused_decode", _fused_dense_step, self.model),
                donate_argnums=(1,))
        # scale tier-0 capacity to the configured budget so eviction and
        # tier demotion actually engage at live-test scale (replay passes
        # tier0_from_budget=False to keep its pressure capacities)
        specs = list(engine_cfg.tier_specs)
        if engine_cfg.tier0_from_budget:
            specs[0] = TierSpec(0, specs[0].name, specs[0].bandwidth,
                                specs[0].latency, specs[0].cost_per_gb_hour,
                                engine_cfg.kv_budget_bytes)
        self.manager = PredictiveCacheManager(
            cfg, specs=tuple(specs), policy=engine_cfg.policy,
            enable_dedup=engine_cfg.enable_dedup,
            enable_prefetch=engine_cfg.enable_prefetch,
            enable_multi_tier=engine_cfg.enable_multi_tier,
            fault_injector=engine_cfg.fault_injector,
            retry_policy=engine_cfg.retry_policy)
        self.worker = (AsyncTierTransferWorker(
            self.manager.hierarchy,
            default_timeout_s=engine_cfg.transfer_timeout_s)
            if engine_cfg.async_transfers else None)
        self.chunked = (engine_cfg.chunked_prefill and self.paged
                        and self.model.supports_chunked_prefill())
        self._rng = jax.random.PRNGKey(engine_cfg.seed + 1)
        self._prefill = jax.jit(_named("prefill", self.model.prefill))
        self._prefill_chunk = jax.jit(
            _named("prefill_chunk", self.model.prefill_chunk,
                   backend=self.kernel_backend))
        # segment reuse needs the chunked paged path: resumed mid-prompt
        # islands are CoW-mapped / injected into the block table and the
        # gaps between them prefill through the position-explicit kernel
        self.seg_enabled = engine_cfg.segment_reuse and self.chunked
        self._prefill_chunk_seg = (jax.jit(
            _named("prefill_chunk_seg", self.model.prefill_chunk_seg,
                   backend=self.kernel_backend))
            if self.chunked else None)
        # request_id -> [payload | None, length]; payload is the staging
        # buffer — dropped once the async demotion write lands
        self._preempted_payloads: Dict[int, list] = {}
        # request_id -> ticket of the *latest* demote: stale events from
        # an earlier preemption epoch of the same request are ignored
        self._demote_tickets: Dict[int, int] = {}
        self._inflight_prefetch: set = set()
        self._session_tool: Dict[str, Optional[str]] = {}
        # block-registration epoch: _extend_prefix only re-walks the
        # radix tree when new blocks appeared since the request's last
        # match (request_id -> epoch seen)
        self._block_epoch = 0
        self._prefix_checked: Dict[int, int] = {}
        # admission-time agentic transition, reused by mid-prefill
        # prefix accesses so the Bayesian posteriors see the right pair
        self._admit_transition: Dict[int, str] = {}
        self.steps = 0
        self.idle_transfer_waits = 0   # run() iterations with only
        #                                restores in flight (no decode work)
        self.prefill_chunks = 0        # kernel chunk calls
        self.prefill_tokens_total = 0  # prompt tokens through the chunk path
        self.cow_share_hits = 0        # prefix blocks served by CoW page map
        self.inject_hits = 0           # ... by tier payload injection
        self.shared_fetch_hits = 0     # ... imported from the fleet-shared
        #                                tier (content another replica
        #                                published; charged as tier-4 fetch)
        self.segment_share_hits = 0    # mid-prompt blocks resumed via the
        #                                segment index by CoW page map
        self.segment_inject_hits = 0   # ... by tier payload injection
        self.segment_chunks = 0        # position-explicit kernel chunks
        self.last_step_prefill_tokens = 0
        self.max_step_prefill_tokens = 0   # budget-compliance witness
        self.decode_rows = 0           # rows decoded, summed over steps
        self.decode_ctx_tokens = 0     # tokens those rows attended
        self.decode_sampled_steps = 0  # fused decode steps with a row at
        #                                temperature > 0 (the sampler's
        #                                stochastic branch ran)

    def _on_device(self):
        """Context in which arrays this engine creates land on its
        device (a no-op for an engine on the default device)."""
        return (contextlib.nullcontext() if self.device is None
                else jax.default_device(self.device))

    # ------------------------------------------------------------------
    def bind_fleet_store(self, store, owner: str) -> bool:
        """Bind this replica's tier 4 to the cluster's fleet-shared KV
        store (see ``core/tiers.FleetKVStore``); call before traffic."""
        return self.manager.bind_fleet_store(store, owner)

    # ------------------------------------------------------------------
    def submit(self, prompt: Sequence[int], *, params: SamplingParams = None,
               session_id: str = None, block_type: str = "user_context",
               tool: str = None, retain_blocks: bool = False,
               block_types: Sequence[str] = None) -> Request:
        req = Request(prompt=list(prompt),
                      params=params or SamplingParams(),
                      session_id=session_id, block_type=block_type,
                      tool=tool, retain_blocks=retain_blocks,
                      block_types=list(block_types) if block_types else None)
        if self.chunked:
            # chunked prefill writes only valid tokens (no pad rounding)
            need = req.prompt_len + req.params.max_new_tokens + 1
        else:
            pad = self.ecfg.pad_prefill_to
            need = ((req.prompt_len + pad - 1) // pad) * pad \
                + req.params.max_new_tokens + 1
        if need > self.ecfg.max_len:
            raise ValueError(
                f"request needs {need} cache slots > max_len "
                f"{self.ecfg.max_len} (prompt {req.prompt_len} + "
                f"max_new {req.params.max_new_tokens})")
        self.scheduler.submit(req)
        return req

    # ------------------------------------------------------------------
    # admission: prefix reuse + suffix prefill
    # ------------------------------------------------------------------
    def _admit(self, req: Request, slot: int) -> None:
        mgr = self.manager
        bt = mgr.block_tokens
        transition = "reasoning_step"
        if req.tool is not None:
            prev = self._session_tool.get(req.session_id)
            transition = mgr.on_tool_switch(prev, req.tool,
                                            kv_bytes=sizing.decode_state_bytes(
                                                self.cfg, req.prompt_len))
            self._session_tool[req.session_id] = req.tool

        # restore a preempted request wholesale (step() guarantees the
        # payload is present — buffer-less restores go through the async
        # fetch path before re-admission)
        if req.request_id in self._preempted_payloads:
            payload, length = self._preempted_payloads.pop(req.request_id)
            self.kv.restore_slot(slot, payload, length)
            self._drop_tier_copy(req.request_id)
            if req.prefill_left > 0:
                # preempted mid-prompt: the restored KV covers the chunk
                # cursor; resume chunked prefill where it left off
                self.scheduler.start_prefill(req, slot)
            else:
                self.scheduler.start(req, slot)
            return

        # prefill covers tokens[:-1]; the first decode step consumes the
        # final token (so prefill logits are never needed and pad
        # positions never produce the sampled token).  ``generated`` is
        # non-empty only on lost-payload recovery, where the whole
        # context is re-prefilled.
        tokens_all = list(req.prompt) + list(req.generated)
        effective = tokens_all[:-1]
        req.prefill_tokens, req.prefill_pos = None, 0
        matched = mgr.match_prefix(effective)
        prefix_len, n_hit = 0, 0
        for bid in matched:
            res = mgr.access(bid, transition=transition)
            if res.recomputed:
                break                      # payload lost -> recompute rest
            if res.hit:
                req.hot_hit_blocks += 1
            if self.paged and self.kv.can_share(bid):
                # pool-resident block: CoW-map its physical pages
                self.kv.share_block(slot, bid, prefix_len)
                self.cow_share_hits += 1
            else:
                pl = mgr._payloads.get(bid)
                if pl is None:
                    break
                self.kv.inject_block(slot, pl, prefix_len)
                self.inject_hits += 1
            prefix_len += bt
            n_hit += 1
        # fleet-shared tier probe: past the local radix match, content
        # ANOTHER replica published extends the prefix via a tier-4
        # fetch + payload injection — paid as a fetch stall, but far
        # cheaper than re-prefilling the blocks (and once imported the
        # blocks are local + hot for the session's next turn)
        if mgr.fleet_bound:
            while prefix_len + bt <= len(effective):
                blk = effective[prefix_len:prefix_len + bt]
                btype = req.block_type
                if req.block_types is not None and \
                        prefix_len // bt < len(req.block_types):
                    btype = req.block_types[prefix_len // bt]
                got = mgr.import_shared_block(
                    blk, block_type=btype,
                    recompute_cost=self._block_recompute_cost(),
                    positions=(prefix_len, prefix_len + bt))
                if got is None:
                    break
                bid, pl = got
                self.kv.inject_block(slot, pl, prefix_len)
                self.shared_fetch_hits += 1
                req.shared_hit_blocks += 1
                prefix_len += bt
                n_hit += 1
        req.prefix_hit_blocks = n_hit

        # segment reuse: beyond the contiguous prefix, the content-
        # segment index finds pool/tier-resident runs of full blocks at
        # matching positions mid-prompt (e.g. a ShareGPT history whose
        # head was truncated away, shifting the surviving turns left by
        # whole blocks).  Each resumed block is a priced ``mgr.access``
        # (tier fetch / posterior update, hot hits counted exactly like
        # prefix hits) and is materialized as a CoW page map or a
        # payload injection; the gaps between resumed spans prefill
        # through the position-explicit segment kernel.
        if self.seg_enabled:
            seg_spans = []
            req.segment_hit_blocks = 0     # lost-payload re-admission
            for seg in mgr.match_segments(effective, start_block=n_hit):
                run_start, run_len = seg.start_block, 0
                for j, bid in enumerate(seg.block_ids):
                    res = mgr.access(bid, transition=transition)
                    ok = not res.recomputed
                    if ok:
                        start = (seg.start_block + j) * bt
                        if self.paged and self.kv.can_share(bid):
                            self.kv.share_block(slot, bid, start)
                            self.segment_share_hits += 1
                        else:
                            pl = mgr._payloads.get(bid)
                            if pl is None:
                                ok = False
                            else:
                                self.kv.inject_block(slot, pl, start)
                                self.segment_inject_hits += 1
                    if ok:
                        if res.hit:
                            req.hot_hit_blocks += 1
                        if run_len == 0:
                            run_start = seg.start_block + j
                        run_len += 1
                        req.segment_hit_blocks += 1
                    else:
                        if run_len:
                            seg_spans.append((run_start, run_len))
                        run_len = 0
                if run_len:
                    seg_spans.append((run_start, run_len))
            req.seg_spans = seg_spans

        if self.chunked:
            # token-budget path: prefix-hit blocks advance the chunk
            # cursor for free; the suffix streams through plan_step()
            req.prefill_tokens = effective
            req.prefill_pos = prefix_len
            self._prefix_checked[req.request_id] = self._block_epoch
            self._admit_transition[req.request_id] = transition
            self.kv.set_length(slot, prefix_len)
            self.scheduler.start_prefill(req, slot)
            if req.seg_spans:
                # a resumed span adjacent to the prefix frontier moves
                # the chunk cursor past it immediately
                self._skip_resumed(req)
            if req.prefill_left == 0:
                self._finish_prefill(req)
            return

        # monolithic fallback (dense layout / --no-chunked A/B): prefill
        # the whole unmatched suffix in one forward
        if req.t_prefill_start is None:
            req.t_prefill_start = time.monotonic()
        suffix = list(effective[prefix_len:])
        pad = self.ecfg.pad_prefill_to
        padded_len = max(pad, ((len(suffix) + pad - 1) // pad) * pad)
        toks = jnp.asarray(
            [suffix + [0] * (padded_len - len(suffix))], jnp.int32)
        if prefix_len == 0:
            logits, state1 = self._prefill(self.params, {"tokens": toks})
            self.kv.write_prefill(slot, state1, padded_len)
        else:
            prefix_kv = self.kv.prefix_kv(slot, prefix_len)
            logits, suffix_kv = self.model.prefill_suffix(
                self.params, {"tokens": toks}, prefix_kv, prefix_len)
            state1 = (dict(latent=suffix_kv[0])
                      if self.cfg.attention_variant == MLA
                      else dict(k=suffix_kv[0], v=suffix_kv[1]))
            self.kv.write_range(slot, state1, prefix_len, padded_len)
        # true sequence length (padding tokens are masked by length)
        self.kv.set_length(slot, len(effective))
        self._stamp_prefill_done(req)
        self._register_prompt_blocks(req, slot, effective)
        self.scheduler.start(req, slot)

    def _register_prompt_blocks(self, req: Request, slot: int,
                                effective: Sequence[int]) -> None:
        """Register the prompt's full blocks with the cache manager and
        pin their pool pages for cross-request reuse."""
        mgr = self.manager
        bt = mgr.block_tokens
        n_full = (len(effective) // bt) * bt
        with spans.span(spans.REGISTER_BLOCKS, rid=req.request_id,
                        blocks=n_full // bt):
            new_ids = mgr.register_sequence(
                list(effective[:n_full]), block_type=req.block_type,
                block_types=req.block_types,
                recompute_cost_per_block=self._block_recompute_cost())
            for i, bid in enumerate(new_ids):
                if bid not in mgr._payloads:
                    with spans.span(spans.BLOCK_TO_HOST):
                        pl = self.kv.extract_block(slot, i * bt, bt)
                    mgr._payloads[bid] = pl
                    # registration admitted the block metadata-first; give
                    # its tier copy the real bytes so demotions checksum
                    # and move actual payloads, not placeholders
                    mgr.hierarchy.attach_payload(bid, pl)
                if self.paged:
                    self.kv.register_block_pages(bid, slot, i * bt, bt)
                if mgr.fleet_bound:
                    # publish-on-register: the block (payload included)
                    # joins the fleet-shared tier so sibling replicas can
                    # import it
                    mgr.publish_block(bid)
        req.block_ids = new_ids
        if new_ids:
            self._block_epoch += 1

    # ------------------------------------------------------------------
    # chunked prefill (token-budget mixed batches)
    # ------------------------------------------------------------------
    @staticmethod
    def _stamp_prefill_done(req: Request) -> None:
        """The first time ``req``'s prompt is wholly in the cache; a
        prompt served from cache alone started its prefill then too."""
        if req.t_prefill_done is None:
            req.t_prefill_done = time.monotonic()
            if req.t_prefill_start is None:
                req.t_prefill_start = req.t_prefill_done

    def _finish_prefill(self, req: Request) -> None:
        """Chunk cursor reached the prompt end: register prompt blocks
        and transition PREFILL -> DECODE."""
        self._stamp_prefill_done(req)
        self._register_prompt_blocks(req, req.slot, req.prefill_tokens)
        self._prefix_checked.pop(req.request_id, None)
        self._admit_transition.pop(req.request_id, None)
        self.scheduler.begin_decode(req)

    def _extend_prefix(self, req: Request) -> int:
        """Mid-prefill prefix extension: blocks registered since this
        request was admitted (e.g. by a sibling sharing the same system
        prompt, finished earlier this step) advance the chunk cursor for
        free — zero prompt tokens spent from the step budget."""
        mgr, bt = self.manager, self.manager.block_tokens
        if req.prefill_pos % bt != 0:
            return 0
        if self._prefix_checked.get(req.request_id) == self._block_epoch:
            return 0               # nothing registered since last match
        self._prefix_checked[req.request_id] = self._block_epoch
        transition = self._admit_transition.get(req.request_id,
                                                "reasoning_step")
        matched = mgr.match_prefix(req.prefill_tokens)
        covered = set()
        for (s, n) in req.seg_spans:
            covered.update(range(s, s + n))
        advanced = 0
        for i in range(req.prefill_pos // bt, len(matched)):
            if i in covered:
                # block already resident via a resumed segment (counted
                # at admission) — the prefix walk just steps over it
                req.prefill_pos += bt
                self.kv.set_length(req.slot, req.prefill_pos)
                continue
            bid = matched[i]
            res = mgr.access(bid, transition=transition)
            if res.recomputed:
                break                  # payload lost -> compute the rest
            if res.hit:
                req.hot_hit_blocks += 1
            if self.kv.can_share(bid):
                self.kv.share_block(req.slot, bid, i * bt)
                self.cow_share_hits += 1
            else:
                pl = mgr._payloads.get(bid)
                if pl is None:
                    break
                if req.seg_spans:
                    # a resumed span above may have advanced the mapped
                    # frontier past this block's pages, leaving them
                    # table holes the contiguous allocator would skip
                    pg = self.kv.page
                    self.kv.ensure_pages_at(
                        req.slot,
                        list(range(i * bt // pg, (i * bt + bt) // pg)))
                self.kv.inject_block(req.slot, pl, i * bt)
                self.inject_hits += 1
            req.prefill_pos += bt
            req.prefix_hit_blocks += 1
            advanced += bt
            self.kv.set_length(req.slot, req.prefill_pos)
        return advanced

    def _skip_resumed(self, req: Request) -> None:
        """Jump the chunk cursor over resumed segments that touch it.
        Spans are ascending and never adjacent (a failed or unmatched
        block always separates them), so one pass suffices."""
        bt = self.manager.block_tokens
        for (s, n) in req.seg_spans:
            if s * bt == req.prefill_pos:
                req.prefill_pos = (s + n) * bt
                self.kv.set_length(req.slot, req.prefill_pos)

    def _gap_positions(self, req: Request, n: int) -> list:
        """The next <= ``n`` unfilled prompt positions at/after the
        chunk cursor, skipping resumed spans — ascending, so every
        position below the last one is either already resident or in
        the returned list (the segment kernel's contract)."""
        bt = self.manager.block_tokens
        spans = [(s * bt, (s + k) * bt) for (s, k) in req.seg_spans]
        out = []
        p = req.prefill_pos
        L = len(req.prefill_tokens)
        while len(out) < n and p < L:
            inside = next((e for (s, e) in spans if s <= p < e), None)
            if inside is not None:
                p = inside
                continue
            out.append(p)
            p += 1
        return out

    def _run_seg_chunk(self, req: Request, n_tokens: int) -> int:
        """One position-explicit prefill chunk over the next gap tokens
        (may span several gaps around resumed islands).  Pad positions
        are -1: the kernel masks them out and RoPE sees position 0."""
        C = self.ecfg.prefill_chunk_tokens
        positions = self._gap_positions(req, min(C, n_tokens))
        n = len(positions)
        if n == 0:
            return 0
        toks = req.prefill_tokens
        with spans.span(spans.PREFILL_CHUNK, rid=req.request_id,
                        start=positions[0], tokens=n):
            if req.t_prefill_start is None:
                req.t_prefill_start = time.monotonic()
            chunk = [toks[p] for p in positions]
            arr = jnp.asarray([chunk + [0] * (C - n)], jnp.int32)
            cpos = jnp.asarray([positions + [-1] * (C - n)], jnp.int32)
            state1 = self._prefill_chunk_seg(
                self.params, self.kv.chunk_state(req.slot, n), arr, cpos)
            self.kv.write_chunk_positions(req.slot, state1, positions)
        # every gap below positions[-1] is now filled, so the contiguous
        # frontier advances to just past it (then over any island there)
        req.prefill_pos = positions[-1] + 1
        self.kv.set_length(req.slot, req.prefill_pos)
        self._skip_resumed(req)
        self.prefill_chunks += 1
        self.segment_chunks += 1
        return n

    def _run_prefill_chunks(self, req: Request, n_tokens: int) -> int:
        """Advance ``req``'s chunk cursor by up to ``n_tokens`` prompt
        tokens in fixed-size kernel chunks (prefix-hit blocks at the
        cursor advance it for free); returns budget tokens consumed."""
        C = self.ecfg.prefill_chunk_tokens
        toks = req.prefill_tokens
        done = 0
        self._extend_prefix(req)
        self._skip_resumed(req)
        while done < n_tokens and req.prefill_pos < len(toks):
            if req.seg_spans:
                # resumed islands ahead (or table holes below them):
                # position-explicit chunks over the gap tokens, written
                # through the hole-aware scatter
                n = self._run_seg_chunk(req, n_tokens - done)
                if n == 0:
                    break
                done += n
            else:
                n = min(C, n_tokens - done, len(toks) - req.prefill_pos)
                with spans.span(spans.PREFILL_CHUNK, rid=req.request_id,
                                start=req.prefill_pos, tokens=n):
                    if req.t_prefill_start is None:
                        req.t_prefill_start = time.monotonic()
                    chunk = list(toks[req.prefill_pos:req.prefill_pos + n])
                    arr = jnp.asarray([chunk + [0] * (C - n)], jnp.int32)
                    off = jnp.asarray([req.prefill_pos], jnp.int32)
                    state1 = self._prefill_chunk(
                        self.params, self.kv.chunk_state(req.slot, n), arr,
                        off)
                    self.kv.write_chunk(req.slot, state1, req.prefill_pos,
                                        n)
                req.prefill_pos += n
                done += n
                self.prefill_chunks += 1
            self._extend_prefix(req)
            self._skip_resumed(req)
        if req.prefill_left == 0:
            self._finish_prefill(req)
        return done

    def _block_recompute_cost(self) -> float:
        """Seconds to re-prefill one block on the target chip."""
        flops = 2 * self.cfg.active_param_count() * self.manager.block_tokens
        return flops / 197e12

    # ------------------------------------------------------------------
    # async transfer bookkeeping
    # ------------------------------------------------------------------
    def _drop_tier_copy(self, request_id: int) -> None:
        bid = f"preempt-{request_id}"
        loc = self.manager.hierarchy.locate(bid)
        if loc is not None:
            self.manager.hierarchy[loc].evict(bid)

    def _poll_transfers(self) -> None:
        for ev in self.scheduler.poll_transfers(self.worker):
            req = ev.request
            if req.kind == "demote" and req.tag:
                rid = int(req.tag)
                if self._demote_tickets.get(rid) != req.ticket:
                    continue           # stale epoch: a newer demote (FIFO
                    #                    after this one) owns the tier copy
                self._demote_tickets.pop(rid, None)
                ent = self._preempted_payloads.get(rid)
                if ent is not None and ev.ok:
                    ent[0] = None          # staging buffer released
                elif ent is None:
                    # restored from the buffer before the write landed:
                    # the tier copy is stale
                    self._drop_tier_copy(rid)
            elif req.kind == "fetch" and req.tag:
                rid = int(req.tag)
                ent = self._preempted_payloads.get(rid)
                if ev.ok and ev.payload is not None and ent is not None:
                    ent[0] = ev.payload
                else:
                    # payload lost (exhausted retries, corrupt copy, or
                    # transfer timeout): recovery re-prefills the full
                    # context — the blocked request unblocks instead of
                    # hanging on a dead tier
                    if ent is not None and not ev.ok:
                        self.manager.stats.fetch_recomputes += 1
                    self._preempted_payloads.pop(rid, None)
                self.scheduler.on_transfer_complete(rid)
            elif req.tag == "prefetch":
                self._inflight_prefetch.discard(req.block_id)

    def _begin_async_restore(self, req: Request) -> None:
        bid = f"preempt-{req.request_id}"
        loc = self.manager.hierarchy.locate(bid)
        if loc is None:
            # demoted copy lost entirely: recompute path
            self._preempted_payloads.pop(req.request_id, None)
            self._admit(req, self._acquire_slot(req))
            return
        self.scheduler.block_on_transfer(req)
        self.worker.submit(TransferRequest(
            bid, loc, 0, kind="fetch", evict_src=True,
            tag=str(req.request_id)))

    def _submit_prefetch(self, block_ids: Sequence[str],
                         position: int) -> None:
        if self.worker is None:
            self.manager.prefetch_for_position(block_ids, position)
            return
        for bid, loc in self.manager.plan_prefetch(block_ids, position):
            if bid in self._inflight_prefetch:
                continue
            self._inflight_prefetch.add(bid)
            self.worker.submit(TransferRequest(
                bid, loc, 0, kind="custom", tag="prefetch",
                execute=(lambda h, b=bid, l=loc:
                         (self.manager.promote_async(b, l), None))))

    def _submit_prefetch_many(self, items) -> None:
        """Batched prefetch for the fused step: plan every decoding
        request's window under one manager lock, then submit."""
        if not items:
            return
        if self.worker is None:
            for block_ids, position in items:
                self.manager.prefetch_for_position(block_ids, position)
            return
        for bid, loc in self.manager.plan_prefetch_many(items):
            if bid in self._inflight_prefetch:
                continue
            self._inflight_prefetch.add(bid)
            self.worker.submit(TransferRequest(
                bid, loc, 0, kind="custom", tag="prefetch",
                execute=(lambda h, b=bid, l=loc:
                         (self.manager.promote_async(b, l), None))))

    # ------------------------------------------------------------------
    # batched decode: fused (default) and per-request-sampling A/B paths
    # ------------------------------------------------------------------
    def _decode_fused(self, decode_reqs) -> int:
        """One fused jitted call for the whole decode batch — block-table
        gather, paged attention, logits and per-row sampling — with the
        KV state donated through the closure and ONE device->host sync
        for the sampled tokens.  In steady-state decode the device state
        from the previous step is reused verbatim (no table rebuild, no
        upload); any host-side mutation (admission, prefill write, CoW
        copy, release, page-boundary crossing) triggers a rebuild via
        ``PagedKVCache.state_version``."""
        with spans.span(spans.DECODE_PREPARE) as sp:
            sa = self.scheduler.step_arrays(decode_reqs, self.kv.n_slots)
            # idle slots hold temperature 0, so this is "some active row
            # samples": the step takes the sampler's stochastic branch
            sampled = bool((sa["temperature"] > 0.0).any())
            self.decode_sampled_steps += sampled
            self._rng, step_key = jax.random.split(self._rng)
            sampling = (step_key, jnp.asarray(sa["temperature"]),
                        jnp.asarray(sa["top_k"]), jnp.asarray(sa["top_p"]))
            tokens = jnp.asarray(sa["tokens"])
            if self.paged:
                slots = [r.slot for r in decode_reqs]
                rebuilds = self.kv.state_rebuilds
                state = self.kv.decode_state(slots, reuse=True)
                active = jnp.asarray(sa["active"])
                sp.set_metadata(
                    rebuilt=int(self.kv.state_rebuilds > rebuilds))
            sp.set_metadata(sampled=int(sampled))
        with spans.span(spans.DECODE_DISPATCH):
            if self.paged:
                toks, new_state = self._fused_decode(
                    self.params, state, tokens, active, *sampling)
                self.kv.absorb(new_state, decode_slots=slots)
            else:
                toks, self.kv.state = self._fused_decode(
                    self.params, self.kv.state, tokens, *sampling)
        with spans.span(spans.DECODE_WAIT):
            out = np.asarray(toks)     # single sync point for the step
        with spans.span(spans.DECODE_FINISH):
            now = time.monotonic()
            produced = 0
            prefetch = []
            for req in sorted(decode_reqs, key=lambda r: r.slot):
                req.generated.append(int(out[req.slot]))
                if req.t_first_token is None:
                    req.t_first_token = now
                produced += 1
                self.kv.advance(req.slot)
                if req.block_ids:
                    prefetch.append((req.block_ids,
                                     self.kv.slots[req.slot].length))
            # RoPE prefetch promotions, planned once per step under one
            # manager lock (async when the transfer worker is on)
            self._submit_prefetch_many(prefetch)
            self._finish_rows(decode_reqs)
        return produced

    def _decode_unfused(self, decode_reqs) -> int:
        """Pre-PR7 A/B path: one decode dispatch, then one sampling
        dispatch + device sync per request."""
        tokens = np.zeros((self.kv.n_slots,), np.int32)
        for req in decode_reqs:
            last = (req.generated[-1] if req.generated
                    else req.prompt[-1])
            tokens[req.slot] = last
        # advance the stream once per step (per-request sampling keys
        # are split below)
        self._rng, _ = jax.random.split(self._rng)
        if self.paged:
            state = self.kv.decode_state([r.slot for r in decode_reqs])
            logits, new_state = self._decode(self.params, state,
                                             jnp.asarray(tokens))
            self.kv.absorb(new_state)
        else:
            logits, self.kv.state = self._decode(
                self.params, self.kv.state, jnp.asarray(tokens))
        now = time.monotonic()
        produced = 0
        # per-request sampling (params differ per request)
        for req in sorted(decode_reqs, key=lambda r: r.slot):
            slot = req.slot
            self._rng, r = jax.random.split(self._rng)
            tok = sampler_mod.sample(
                logits[slot:slot + 1], r,
                temperature=req.params.temperature,
                top_k=req.params.top_k, top_p=req.params.top_p)
            req.generated.append(int(tok[0]))
            if req.t_first_token is None:
                req.t_first_token = now
            produced += 1
            self.kv.advance(slot)
            # RoPE prefetch hook: promote blocks around the decode
            # position (async when the transfer worker is on)
            if req.block_ids:
                self._submit_prefetch(req.block_ids,
                                      self.kv.slots[slot].length)
        self._finish_rows(decode_reqs)
        return produced

    def _finish_rows(self, decode_reqs) -> None:
        """Finish the decoded rows that are done (lengths already
        advanced): release their blocks to the manager and their slots."""
        for req in decode_reqs:
            if req.finished() or req.total_len >= self.ecfg.max_len - 1:
                # retain_blocks (session continuation) balances the dedup
                # refcount but keeps the blocks registered for the next
                # turn's prefix match
                self.manager.release_sequence(req.block_ids,
                                              retain=req.retain_blocks)
                self.scheduler.finish(req)
                self.kv.release(req.slot)

    # ------------------------------------------------------------------
    def step(self) -> int:
        """One engine iteration (poll transfers -> admit -> budget-select
        -> prefill chunks -> batched decode -> sample/finish); returns
        #tokens generated."""
        with self._on_device():
            return self._step()

    def _acquire_slot(self, req: Request) -> int:
        slot = self.kv.acquire(req.request_id, req.prompt_len)
        if req.t_slot is None:
            req.t_slot = time.monotonic()
        return slot

    def _step(self) -> int:
        sch = self.scheduler
        with spans.span(spans.ITERATION):
            with spans.span(spans.POLL):
                # completion events (scheduler polls; engine interprets)
                self._poll_transfers()
                # straggler handling
                for req in sch.check_stragglers():
                    self.preempt(req)
            with spans.span(spans.ADMISSION):
                for req in sch.admissible(len(self.kv.free_slots())):
                    ent = self._preempted_payloads.get(req.request_id)
                    if ent is not None and ent[0] is None:
                        self._begin_async_restore(req)
                        continue
                    self._admit(req, self._acquire_slot(req))
            if not sch.running:
                return 0
            # budget-select the mixed batch: the decode set is snapshotted
            # *before* prefill runs, so this step's token count is bounded
            # by max_step_tokens even when a chunk finishes a prompt
            decode_reqs, grants = sch.plan_step()
            prefill_tokens = 0
            with spans.span(spans.PREFILL):
                for req, n in grants:
                    req.prefill_steps += 1
                    prefill_tokens += self._run_prefill_chunks(req, n)
            self.last_step_prefill_tokens = prefill_tokens
            self.max_step_prefill_tokens = max(self.max_step_prefill_tokens,
                                               prefill_tokens)
            self.prefill_tokens_total += prefill_tokens
            produced = 0
            if decode_reqs:
                rows = len(decode_reqs)
                ctx = rows + sum(self.kv.slots[r.slot].length
                                 for r in decode_reqs)
                self.decode_rows += rows
                self.decode_ctx_tokens += ctx
                with spans.span(spans.DECODE, rows=rows, ctx_tokens=ctx):
                    produced = (self._decode_fused(decode_reqs)
                                if self.fused
                                else self._decode_unfused(decode_reqs))
            with spans.span(spans.CACHE_UPKEEP):
                if self.paged:
                    # unpin pages of blocks the manager demoted or dropped
                    self.kv.gc_blocks(self.manager)
                self.manager.tick()
                self.manager.age_all()
            self.steps += 1
            return produced

    def preempt(self, req: Request) -> None:
        """Demote a running request's KV into the tier hierarchy —
        asynchronously when the transfer worker is on (the step loop
        never waits on the write)."""
        if req.phase is Phase.PREFILL and req.prefill_pos <= 0:
            # nothing prefilled yet: no KV worth demoting — release the
            # slot and requeue for a fresh prefill
            req.prefill_tokens, req.prefill_pos = None, 0
            req.seg_spans = []
            self.kv.release(req.slot)
            self.scheduler.preempt(req)
            return
        if req.seg_spans and req.prefill_left > 0:
            # the demoted payload covers [0, frontier) only — resumed
            # islands beyond it die with the slot; the restore re-enters
            # chunked prefill without them
            bt = self.manager.block_tokens
            req.seg_spans = [sp for sp in req.seg_spans
                             if (sp[0] + sp[1]) * bt <= req.prefill_pos]
        payload, length = self.kv.evict_slot_to_payload(req.slot)
        self._preempted_payloads[req.request_id] = [payload, length]
        bid = f"preempt-{req.request_id}"
        # drop any previous-epoch tier copy so size accounting matches
        # the new payload (the in-flight old write, if any, is superseded
        # FIFO by the one submitted below)
        self._drop_tier_copy(req.request_id)
        if self.worker is not None:
            ticket = self.worker.submit(TransferRequest(
                bid, 0, 1, kind="demote", payload=payload,
                nbytes=float(payload.nbytes), tag=str(req.request_id)))
            self._demote_tickets[req.request_id] = ticket
        else:
            self.manager.hierarchy[1].write(bid, payload,
                                            nbytes=float(payload.nbytes))
        self.kv.release(req.slot)
        self.scheduler.preempt(req)

    # ------------------------------------------------------------------
    def run(self, max_steps: int = 10_000) -> dict:
        while self.scheduler.has_work() and self.steps < max_steps:
            produced = self.step()
            if (produced == 0 and not self.scheduler.running
                    and self.scheduler.blocked):
                self.idle_transfer_waits += 1
                time.sleep(1e-3)       # idle: only fetches in flight
        return self.stats()

    def recompiles(self) -> dict:
        """Compiled-variant count per jitted step-loop closure (the jit
        cache size).  Steady-state serving must hold every count
        constant — growth means a shape or dtype is leaking into a
        trace (the exact compile storm the fixed-width scatter and the
        reused step buffers exist to prevent); a test gates on this."""
        out = {}
        closures = [("decode", self._decode),
                    ("fused_decode", self._fused_decode),
                    ("prefill", self._prefill),
                    ("prefill_chunk", self._prefill_chunk)]
        if self._prefill_chunk_seg is not None:
            closures.append(("prefill_chunk_seg", self._prefill_chunk_seg))
        for name, fn in closures:
            out[name] = int(fn._cache_size())
        return out

    def stats(self) -> dict:
        out = {"scheduler": self.scheduler.stats(),
               "cache": self.manager.metrics(),
               "kernel_backend": self.kernel_backend,
               "steps": self.steps,
               "idle_transfer_waits": self.idle_transfer_waits,
               "paged": self.paged,
               "chunked": self.chunked,
               "fused": self.fused,
               "recompiles": self.recompiles(),
               "prefill_chunks": self.prefill_chunks,
               "prefill_tokens": self.prefill_tokens_total,
               "max_step_prefill_tokens": self.max_step_prefill_tokens,
               "cow_share_hits": self.cow_share_hits,
               "inject_hits": self.inject_hits,
               "shared_fetch_hits": self.shared_fetch_hits,
               "segment_reuse": self.seg_enabled,
               "segment_share_hits": self.segment_share_hits,
               "segment_inject_hits": self.segment_inject_hits,
               "segment_chunks": self.segment_chunks,
               "decode_rows": self.decode_rows,
               "decode_sampled_steps": self.decode_sampled_steps,
               "decode_ctx_tokens": self.decode_ctx_tokens,
               "d2h_bytes": self.kv.d2h_bytes,
               "d2h_wait_s": self.kv.d2h_wait_s,
               "d2h_copy_s": self.kv.d2h_copy_s,
               "h2d_bytes": self.kv.h2d_bytes}
        if self.paged:
            out["allocator"] = self.kv.allocator.stats_dict()
            if self.kv.expert_tokens is not None:
                # [MoE layer][expert] tokens routed since the engine was
                # built, counted on the device; read here and only here
                out["moe_expert_tokens"] = np.asarray(
                    self.kv.expert_tokens).tolist()
            out["decode_state_reuses"] = self.kv.state_reuses
            out["decode_state_rebuilds"] = self.kv.state_rebuilds
        if self.worker is not None:
            out["async_transfers"] = self.worker.stats()
        out["faults"] = self.manager.hierarchy.fault_stats()
        return out

    def cancel_request(self, req: Request) -> bool:
        """Drop one live request from every scheduler queue and release
        its resources (slot pages via ``kv.release``, dedup refs via
        ``release_sequence``, staged preempt payloads) — the frontend's
        drain-deadline shed path.  Returns False when the request is not
        live on this engine (already finished, or another replica's)."""
        sch = self.scheduler
        rid = req.request_id
        found = False
        if rid in sch.running:
            sch.running.pop(rid)
            if req.slot is not None and req.slot >= 0:
                self.kv.release(req.slot)
            found = True
        elif rid in sch.blocked:
            sch.blocked.pop(rid)
            found = True
        elif req in sch.waiting:
            sch.waiting.remove(req)
            found = True
        elif req in sch.preempted:
            sch.preempted.remove(req)
            found = True
        if not found:
            return False
        self.manager.release_sequence(req.block_ids,
                                      retain=req.retain_blocks)
        self._preempted_payloads.pop(rid, None)
        self._demote_tickets.pop(rid, None)
        self._drop_tier_copy(rid)
        req.phase = Phase.DONE
        if self.paged:
            self.kv.gc_blocks(self.manager)
        return True

    def shutdown(self) -> None:
        if self.worker is not None:
            # escalate at the deadline: injected stalls become failed
            # TransferEvents instead of a hung shutdown
            self.worker.drain(timeout=5.0, escalate=True)
            self.worker.close()
            self.worker = None

    def release_resources(self) -> None:
        """Failover teardown: close the transfer worker and release the
        cache manager's block/tier registrations (payload copies, tier
        residency, radix index, dedup store) so a failed replica frees
        its memory instead of leaking it.  ``ManagerStats`` survive for
        fleet-level aggregation."""
        self.shutdown()
        self._preempted_payloads.clear()
        self._demote_tickets.clear()
        self._inflight_prefetch.clear()
        self.manager.release_all()
