"""KV caches for the live engine: paged block-table pool (default) and
the dense per-slot layout (``paged=False`` fallback).

``PagedKVCache`` is the paper's Tier-0 block layout made live: KV state
lives in a global pool of fixed-size pages ([L, n_pages, page, ...]),
each decode slot owns a block table of page indices, and the Pallas
paged-attention kernels (kernels/paged_attention.py,
kernels/mla_paged_decode.py) read through that indirection during
batched decode.  Pages are refcounted (serving/block_allocator.py):
radix-prefix hits map the prefix's physical pages straight into the new
request's block table (copy-on-write sharing — zero bytes moved), and
the PredictiveCacheManager pins the pages of every tier-0-resident
prompt block so they survive request completion for cross-request reuse.

``SlotKVCache`` keeps the original contiguous per-slot DecodeState for
A/B comparison and for families without a paged decode path (hybrid,
RWKV, enc-dec, VLM).

Both caches speak the same engine-facing API (see ``_KVCacheBase``):
    acquire / release / free_slots / set_length
    write_prefill / write_range / inject_block / prefix_kv
    extract_block / evict_slot_to_payload / restore_slot
Block payloads (numpy, [2, L, n, Hkv, hd] or MLA [1, L, n, dl+dr]) are
the currency of the multi-tier hierarchy — identical in both layouts,
so tier demotion/promotion is layout-agnostic.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import MLA, ModelConfig
from repro.core.tiers import CapacityError
from repro.models.model import Model
from repro.serving.block_allocator import BlockAllocator


@dataclass
class SlotInfo:
    request_id: int = -1
    length: int = 0
    active: bool = False


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_pool(arr, pids, offs, data):
    """Scatter ``data`` [L, W, ...] into pool ``arr`` [L, N, page, ...]
    at (pids[w], offs[w]).  Jitted with the pool donated: the update
    runs in place instead of copying the whole pool per write (the
    eager ``.at[].set`` both copied and re-compiled for every distinct
    token count — the compile storm that dominated replay wall-clock
    once the kernels themselves were compiled).  The compile cache is
    module-level, so every engine/replica with the same pool and
    chunk-buffer shapes shares one compilation."""
    return arr.at[:, pids, offs].set(data.astype(arr.dtype))


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_pool_page(arr, dst, src):
    """Copy page ``src`` of pool ``arr`` [L, N, page, ...] onto page
    ``dst``, in place (donated: an eager ``.at[].set`` would hold a
    second copy of the whole pool while it runs)."""
    return arr.at[:, dst].set(arr[:, src])


class _KVCacheBase:
    """Slot bookkeeping + payload conversion shared by both layouts.

    Subclasses provide ``write_range`` / ``extract_block`` /
    ``set_length``; everything here is layout-agnostic."""

    cfg: ModelConfig
    slots: List[SlotInfo]
    # block copies between device and host, for ``ServingEngine.stats()``
    d2h_bytes = 0         # bytes copied to host (``extract_block``)
    d2h_wait_s = 0.0      # ... waiting for the device work queued ahead
    d2h_copy_s = 0.0      # ... copying
    h2d_bytes = 0         # payload bytes injected (its time is on the
    #                       device trace; timing it here would add a sync)

    # -- slots --------------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if not s.active]

    def acquire(self, request_id: int, length: int) -> int:
        for i, s in enumerate(self.slots):
            if not s.active:
                self.slots[i] = SlotInfo(request_id, length, True)
                return i
        raise RuntimeError("no free slot")

    # -- payloads -----------------------------------------------------------
    def _payload_state(self, payload: np.ndarray) -> Dict:
        if self.cfg.attention_variant == MLA:
            return {"latent": jnp.asarray(payload[0])[:, None]}
        return {"k": jnp.asarray(payload[0])[:, None],
                "v": jnp.asarray(payload[1])[:, None]}

    def write_prefill(self, slot: int, state1: Dict, length: int) -> None:
        """Copy a batch-1 prefill state into slot `slot`."""
        self.write_range(slot, state1, 0, length)
        self.set_length(slot, length)

    def inject_block(self, slot: int, payload: np.ndarray,
                     start: int) -> int:
        """Write one reused block payload at token offset `start`."""
        n = payload.shape[2]
        self.h2d_bytes += payload.nbytes
        self.write_range(slot, self._payload_state(payload), start, n)
        return n

    def write_chunk(self, slot: int, state1: Dict, offset: int,
                    n_tokens: int) -> None:
        """Incremental chunked-prefill write: scatter the first
        ``n_tokens`` of a prefill chunk's KV at token ``offset`` and
        advance the slot's valid length (pad positions past the valid
        suffix are never written)."""
        self.write_range(slot, state1, offset, n_tokens)
        self.set_length(slot, offset + n_tokens)

    def _to_host(self, *arrays) -> List[np.ndarray]:
        """Device slices -> numpy.  The wait for the device work queued
        ahead of them is timed apart from the copy itself."""
        t0 = time.perf_counter()
        jax.block_until_ready(arrays)
        t1 = time.perf_counter()
        out = [np.asarray(a) for a in arrays]
        self.d2h_copy_s += time.perf_counter() - t1
        self.d2h_wait_s += t1 - t0
        self.d2h_bytes += sum(a.nbytes for a in out)
        return out

    # -- preemption ---------------------------------------------------------
    def evict_slot_to_payload(self, slot: int) -> Tuple[np.ndarray, int]:
        """Preemption: extract the whole slot state for tier demotion."""
        length = self.slots[slot].length
        payload = self.extract_block(slot, 0, length)
        return payload, length

    def restore_slot(self, slot: int, payload: np.ndarray,
                     length: int) -> None:
        self.inject_block(slot, payload, 0)
        self.set_length(slot, length)

    # -- decode bookkeeping -------------------------------------------------
    def advance(self, slot: int) -> None:
        """Post-decode length advance.  The decode step already advanced
        the device-side length, so this only moves the host mirror — it
        must NOT invalidate a cached device state (``PagedKVCache``
        overrides the invalidating ``set_length`` path for exactly this
        reason)."""
        self.slots[slot].length += 1


class SlotKVCache(_KVCacheBase):
    """Fixed decode slots over the model's contiguous DecodeState."""

    def __init__(self, model: Model, n_slots: int, max_len: int, *,
                 device: Optional[jax.Device] = None):
        self.model = model
        self.cfg = model.cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.state = model.init_decode_state(n_slots, max_len)
        if device is not None:
            self.state = jax.device_put(self.state, device)
        self.slots = [SlotInfo() for _ in range(n_slots)]

    # ------------------------------------------------------------------
    def release(self, slot: int) -> None:
        self.slots[slot] = SlotInfo()
        self.state["lengths"] = self.state["lengths"].at[slot].set(0)

    def set_length(self, slot: int, length: int) -> None:
        self.slots[slot].length = length
        self.state["lengths"] = self.state["lengths"].at[slot].set(length)

    # ------------------------------------------------------------------
    # moving KV between the slot cache and block payloads (numpy)
    # ------------------------------------------------------------------
    def write_range(self, slot: int, state1: Dict, start: int,
                    n_tokens: int) -> None:
        """Copy a batch-1 KV state into positions [start, start+n)."""
        if self.cfg.attention_variant == MLA:
            self.state["latent"] = self.state["latent"].at[
                :, slot, start:start + n_tokens].set(
                state1["latent"][:, 0, :n_tokens])
        else:
            self.state["k"] = self.state["k"].at[
                :, slot, start:start + n_tokens].set(
                state1["k"][:, 0, :n_tokens])
            self.state["v"] = self.state["v"].at[
                :, slot, start:start + n_tokens].set(
                state1["v"][:, 0, :n_tokens])

    def extract_block(self, slot: int, start: int, n_tokens: int) -> np.ndarray:
        """Slot KV -> block payload [2, L, n_tokens, H, hd] (or MLA
        [1, L, n_tokens, dl+dr])."""
        if self.cfg.attention_variant == MLA:
            lat, = self._to_host(
                self.state["latent"][:, slot, start:start + n_tokens])
            return lat[None]
        k, v = self._to_host(self.state["k"][:, slot, start:start + n_tokens],
                             self.state["v"][:, slot, start:start + n_tokens])
        return np.stack([k, v])

    def prefix_kv(self, slot: int, length: int):
        """Cached prefix (k, v) for suffix-prefill, batch dim restored."""
        if self.cfg.attention_variant == MLA:
            return (self.state["latent"][:, slot:slot + 1, :length],)
        return (self.state["k"][:, slot:slot + 1, :length],
                self.state["v"][:, slot:slot + 1, :length])


# ===========================================================================
# Paged block-table cache (the default serving path)
# ===========================================================================
class PagedKVCache(_KVCacheBase):
    """Global page pool + per-slot block tables + CoW prefix sharing.

    Pool layout (page 0 is a reserved scratch page that absorbs the
    decode-step writes of inactive slots):

        GQA/MHA/MQA:  k_pages, v_pages  [L, n_pages, page, Hkv, hd]
        MLA:          latent_pages      [L, n_pages, page, dl+dr]

    Block tables are host numpy ([n_slots, pages_per_slot] int32, 0 =
    unmapped); `decode_state()` snapshots them (plus per-slot lengths)
    into device arrays for `Model.decode_step_paged`, which scatters the
    new token's KV into the pool and attends through the Pallas paged
    kernels.  ``device`` commits the pools to one accelerator (``None``:
    JAX's default device).
    """

    def __init__(self, model: Model, n_slots: int, max_len: int, *,
                 page_tokens: int = 64, reserve_pages: Optional[int] = None,
                 dtype=jnp.bfloat16, device: Optional[jax.Device] = None):
        cfg = model.cfg
        self.model = model
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.page = page_tokens
        self.pages_per_slot = -(-max_len // page_tokens)
        if reserve_pages is None:
            # headroom for manager-pinned prefix blocks that outlive slots
            reserve_pages = max(8, 2 * self.pages_per_slot)
        self.n_pages = 1 + n_slots * self.pages_per_slot + reserve_pages
        self.allocator = BlockAllocator(self.n_pages, reserved=(0,))
        self.mla = cfg.attention_variant == MLA
        L = cfg.n_layers
        if self.mla:
            d = cfg.d_latent + cfg.d_rope
            self.pools = {"latent_pages": jnp.zeros(
                (L, self.n_pages, self.page, d), dtype, device=device)}
        else:
            hkv = max(cfg.n_kv_heads, 1)
            shape = (L, self.n_pages, self.page, hkv, cfg.hd)
            self.pools = {"k_pages": jnp.zeros(shape, dtype, device=device),
                          "v_pages": jnp.zeros(shape, dtype, device=device)}
        # tokens routed to each expert of each MoE layer [Lm, E]: the
        # decode and prefill-chunk programs add to it on the device, and
        # the host reads it only for ``ServingEngine.stats()``
        self.expert_tokens = (
            jnp.zeros((cfg.n_moe_layers, cfg.layout_n_experts), jnp.int32,
                      device=device) if cfg.n_experts else None)
        self.tables = np.zeros((n_slots, self.pages_per_slot), np.int32)
        self._mapped = [0] * n_slots           # contiguous mapped page count
        self.slots = [SlotInfo() for _ in range(n_slots)]
        self.block_pages: Dict[str, List[int]] = {}
        # device-state cache for the fused step loop: in steady-state
        # decode (same slot set, no table/length/pool mutation since the
        # last step) the state returned by the previous fused decode is
        # handed straight back — no table copy, no masking pass, no
        # host->device upload.  ``state_version`` is bumped by every
        # host-side mutation that would make the cached snapshot stale.
        self.state_version = 0
        self._cached_state: Optional[Dict] = None
        self._cached_slots: Optional[frozenset] = None
        self._cached_version = -1
        self.state_reuses = 0      # decode_state served from the cache
        self.state_rebuilds = 0    # full snapshot builds

    # ------------------------------------------------------------------
    # slot lifecycle
    # ------------------------------------------------------------------
    def release(self, slot: int) -> None:
        for pi in range(self._mapped[slot]):
            self.allocator.deref(int(self.tables[slot, pi]))
        self.tables[slot, :] = 0
        self._mapped[slot] = 0
        self.slots[slot] = SlotInfo()
        self.state_version += 1

    def set_length(self, slot: int, length: int) -> None:
        self.slots[slot].length = length
        self.state_version += 1

    # ------------------------------------------------------------------
    # page mapping
    # ------------------------------------------------------------------
    def _alloc(self, n: int) -> List[int]:
        """Allocate with backpressure: a full pool first reclaims pages
        pinned for manager blocks (oldest registrations first) — the
        blocks' host payloads survive in the manager, so prefix hits
        degrade from CoW page-sharing to payload injection instead of
        the engine crashing."""
        try:
            return self.allocator.alloc(n)
        except CapacityError:
            for bid in list(self.block_pages):
                self.drop_block_pages(bid)
                if self.allocator.n_free >= n:
                    break
            return self.allocator.alloc(n)

    def _ensure_pages(self, slot: int, n_tokens: int) -> None:
        need = -(-n_tokens // self.page)
        cur = self._mapped[slot]
        if need <= cur:
            return
        for i, pid in enumerate(self._alloc(need - cur)):
            self.tables[slot, cur + i] = pid
        self._mapped[slot] = need
        self.state_version += 1

    def ensure_private(self, slot: int, page_index: int) -> None:
        """Copy-on-write: give the slot a private copy of a shared page
        before any write lands on it."""
        pid = int(self.tables[slot, page_index])
        if pid == 0 or self.allocator.refcount(pid) <= 1:
            return
        new = self._alloc(1)[0]
        for key, arr in self.pools.items():
            self.pools[key] = _copy_pool_page(arr, new, pid)
        self.tables[slot, page_index] = new
        self.allocator.deref(pid)
        self.allocator.note_cow_copy()
        self.state_version += 1

    # ------------------------------------------------------------------
    # CoW prefix sharing with the cache manager
    # ------------------------------------------------------------------
    def can_share(self, block_id: str) -> bool:
        return block_id in self.block_pages

    def share_block(self, slot: int, block_id: str, start: int) -> int:
        """Map a pool-resident block's pages into the slot's table
        (refcount bump — no bytes move).  Returns tokens mapped."""
        pids = self.block_pages[block_id]
        assert start % self.page == 0, "shared blocks must be page-aligned"
        pi0 = start // self.page
        for j, pid in enumerate(pids):
            self.allocator.ref(pid, share=True)
            self.tables[slot, pi0 + j] = pid
        self._mapped[slot] = max(self._mapped[slot], pi0 + len(pids))
        self.state_version += 1
        return len(pids) * self.page

    def register_block_pages(self, block_id: str, slot: int, start: int,
                             n_tokens: int) -> None:
        """Pin the pages backing a newly-registered prompt block so they
        survive the slot for cross-request reuse."""
        if block_id in self.block_pages:
            return
        assert start % self.page == 0 and n_tokens % self.page == 0
        pids = [int(self.tables[slot, pi])
                for pi in range(start // self.page,
                                (start + n_tokens) // self.page)]
        for pid in pids:
            self.allocator.ref(pid)
        self.block_pages[block_id] = pids

    def drop_block_pages(self, block_id: str) -> None:
        for pid in self.block_pages.pop(block_id, ()):
            self.allocator.deref(pid)

    def gc_blocks(self, manager) -> int:
        """Unpin pages of blocks that left tier 0 (demoted / evicted /
        released by the PredictiveCacheManager)."""
        dropped = 0
        for bid in list(self.block_pages):
            if bid not in manager.metas or manager.hierarchy.locate(bid) != 0:
                self.drop_block_pages(bid)
                dropped += 1
        return dropped

    # ------------------------------------------------------------------
    # writes
    # ------------------------------------------------------------------
    def _take_counts(self, state1: Dict) -> None:
        if "expert_tokens" in state1:
            self.expert_tokens = state1["expert_tokens"]

    def write_range(self, slot: int, state1: Dict, start: int,
                    n_tokens: int) -> None:
        """Scatter a batch-1 KV state into positions [start, start+n),
        allocating (and CoW-privatizing) pages as needed.  One donated
        jitted scatter per pool tensor (``_scatter_pool``): the index
        arrays span the data buffer's FULL width (chunk buffer / block
        payload), with entries past ``n_tokens`` directed at the
        reserved scratch page 0 — so the scatter shape depends only on
        the buffer shape, compiles once per buffer (not once per token
        count), and the hot chunked-prefill path hits one cached
        executable for every chunk."""
        self._ensure_pages(slot, start + n_tokens)
        for pi in range(start // self.page,
                        (start + n_tokens - 1) // self.page + 1):
            self.ensure_private(slot, pi)
        if self.mla:
            items = [("latent_pages", state1["latent"][:, 0])]
        else:
            items = [("k_pages", state1["k"][:, 0]),
                     ("v_pages", state1["v"][:, 0])]
        width = items[0][1].shape[1]
        pos = np.arange(start, start + n_tokens)
        pids = np.zeros(width, np.int32)
        offs = np.zeros(width, np.int32)
        pids[:n_tokens] = self.tables[slot, pos // self.page]
        offs[:n_tokens] = pos % self.page
        pid_arr, off_arr = jnp.asarray(pids), jnp.asarray(offs)
        for key, data in items:
            self.pools[key] = _scatter_pool(self.pools[key], pid_arr,
                                            off_arr, data)
        self._take_counts(state1)
        self.state_version += 1    # pool arrays replaced

    def ensure_pages_at(self, slot: int, page_indices: Sequence[int]) -> None:
        """Allocate pages for unmapped (hole) table entries among
        ``page_indices``.  Segment assembly maps resumed segments beyond
        the contiguous frontier (``share_block``), which advances
        ``_mapped`` past gap pages that are still table-entry 0 — the
        contiguous ``_ensure_pages`` sweep would skip those holes and
        gap writes would land on the scratch page."""
        missing = [pi for pi in page_indices
                   if int(self.tables[slot, pi]) == 0]
        if not missing:
            return
        for pi, pid in zip(missing, self._alloc(len(missing))):
            self.tables[slot, pi] = pid
        self._mapped[slot] = max(self._mapped[slot],
                                 max(page_indices) + 1)
        self.state_version += 1

    def write_chunk_positions(self, slot: int, state1: Dict,
                              positions: Sequence[int]) -> None:
        """Scatter the first ``len(positions)`` tokens of a segment-
        prefill chunk at the given absolute token positions (ascending,
        possibly non-contiguous: a chunk may span several prompt gaps
        around resumed segments).  Buffer entries past the valid count
        are directed at the reserved scratch page, same as
        ``write_range``.  Does NOT advance the slot length — the caller
        moves the contiguous frontier (``set_length``) once adjoining
        resumed segments merge with it."""
        n = len(positions)
        if n == 0:
            return
        pos = np.asarray(positions, np.int64)
        touched = sorted({int(p) for p in pos // self.page})
        self.ensure_pages_at(slot, touched)
        for pi in touched:
            self.ensure_private(slot, pi)
        if self.mla:
            items = [("latent_pages", state1["latent"][:, 0])]
        else:
            items = [("k_pages", state1["k"][:, 0]),
                     ("v_pages", state1["v"][:, 0])]
        width = items[0][1].shape[1]
        pids = np.zeros(width, np.int32)
        offs = np.zeros(width, np.int32)
        pids[:n] = self.tables[slot, pos // self.page]
        offs[:n] = pos % self.page
        pid_arr, off_arr = jnp.asarray(pids), jnp.asarray(offs)
        for key, data in items:
            self.pools[key] = _scatter_pool(self.pools[key], pid_arr,
                                            off_arr, data)
        self._take_counts(state1)
        self.state_version += 1    # pool arrays replaced

    # ------------------------------------------------------------------
    # reads
    # ------------------------------------------------------------------
    def _gather(self, key: str, slot: int, start: int, n_tokens: int):
        """Pool pages -> contiguous [L, n_tokens, ...] (device array)."""
        p0 = start // self.page
        p1 = (start + n_tokens - 1) // self.page
        pids = np.asarray(self.tables[slot, p0:p1 + 1])
        arr = self.pools[key][:, pids]              # [L, np, page, ...]
        L = arr.shape[0]
        flat = arr.reshape((L, -1) + arr.shape[3:])
        rel = start - p0 * self.page
        return flat[:, rel:rel + n_tokens]

    def extract_block(self, slot: int, start: int,
                      n_tokens: int) -> np.ndarray:
        if self.mla:
            lat, = self._to_host(
                self._gather("latent_pages", slot, start, n_tokens))
            return lat[None]
        k, v = self._to_host(self._gather("k_pages", slot, start, n_tokens),
                             self._gather("v_pages", slot, start, n_tokens))
        return np.stack([k, v])

    def prefix_kv(self, slot: int, length: int):
        if self.mla:
            return (self._gather("latent_pages", slot, 0, length)[:, None],)
        return (self._gather("k_pages", slot, 0, length)[:, None],
                self._gather("v_pages", slot, 0, length)[:, None])

    # ------------------------------------------------------------------
    # decode-step interface
    # ------------------------------------------------------------------
    def _prepare_decode_pages(self, include: Optional[set]) -> None:
        """Guarantee every decoding slot a private page for the incoming
        token.  Page needs are gathered host-side first and satisfied in
        ONE allocator call for the whole step (the per-slot
        ``_ensure_pages`` loop paid one allocator lock round-trip per
        request per step)."""
        needs = []
        for i, s in enumerate(self.slots):
            if not s.active or (include is not None and i not in include):
                continue
            need = -(-(s.length + 1) // self.page) - self._mapped[i]
            if need > 0:
                needs.append((i, need))
        if needs:
            pids = self._alloc(sum(n for _, n in needs))
            j = 0
            for i, need in needs:
                cur = self._mapped[i]
                for t in range(need):
                    self.tables[i, cur + t] = pids[j]
                    j += 1
                self._mapped[i] = cur + need
            self.state_version += 1
        for i, s in enumerate(self.slots):
            if not s.active or (include is not None and i not in include):
                continue
            self.ensure_private(i, s.length // self.page)

    def decode_state(self, decode_slots: Optional[Sequence[int]] = None,
                     reuse: bool = False) -> Dict:
        """Snapshot for Model.decode_step_paged.  Guarantees every
        decoding slot has a private page mapped for the incoming token.

        ``decode_slots`` restricts the batch to those slots (the mixed
        token-budget step: slots mid-chunked-prefill stay out): excluded
        rows get a zeroed block table and length 0, so the kernel's
        per-row KV write lands on the reserved scratch page instead of
        the slot's real (possibly CoW-shared) prefix pages.

        ``reuse=True`` (fused step loop): if the previous fused step's
        returned state is cached, covers the same slot set, and no
        host-side mutation happened since (``state_version``), hand it
        straight back — the caller donates it into the step closure and
        ``absorb`` re-caches the result.  Steady-state decode then runs
        with zero per-step table copies or host->device uploads."""
        include = (None if decode_slots is None else set(decode_slots))
        self._prepare_decode_pages(include)
        if reuse and include is not None:
            key = frozenset(include)
            if (self._cached_state is not None
                    and self._cached_slots == key
                    and self._cached_version == self.state_version):
                state = self._cached_state
                self._cached_state = None  # donated into the closure
                self.state_reuses += 1
                if self.expert_tokens is not None:
                    state["expert_tokens"] = self.expert_tokens
                return state
        self.state_rebuilds += 1
        tables = self.tables
        lengths = np.asarray(
            [s.length if s.active and (include is None or i in include)
             else 0 for i, s in enumerate(self.slots)], np.int32)
        if include is not None:
            tables = tables.copy()
            for i in range(self.n_slots):
                if i not in include:
                    tables[i, :] = 0
        state = dict(self.pools)
        state["block_tables"] = jnp.asarray(tables)
        state["lengths"] = jnp.asarray(lengths)
        if self.expert_tokens is not None:
            state["expert_tokens"] = self.expert_tokens
        return state

    def chunk_state(self, slot: int, n_valid: Optional[int] = None) -> Dict:
        """Snapshot for Model.prefill_chunk: the page pools plus this
        slot's block-table row (batch dim 1); with experts, their
        counter and ``n_valid``, the chunk's count of prompt tokens (the
        rest is padding the experts skip; by default none is, so a
        caller that leaves it out computes every row's experts and
        counts the padding too)."""
        state = dict(self.pools)
        state["block_table"] = jnp.asarray(self.tables[slot:slot + 1])
        if self.expert_tokens is not None:
            state["expert_tokens"] = self.expert_tokens
            state["n_valid"] = jnp.asarray(
                np.iinfo(np.int32).max if n_valid is None else n_valid,
                jnp.int32)
        return state

    def absorb(self, new_state: Dict,
               decode_slots: Optional[Sequence[int]] = None) -> None:
        """Take back the (donated) pool arrays after a decode step.

        With ``decode_slots`` (fused path) the whole returned state —
        pools, tables, per-row lengths already advanced on device — is
        cached for ``decode_state(reuse=True)`` next step."""
        for key in self.pools:
            self.pools[key] = new_state[key]
        self._take_counts(new_state)
        self.state_version += 1    # pool arrays replaced
        if decode_slots is not None:
            self._cached_state = new_state
            self._cached_slots = frozenset(decode_slots)
            self._cached_version = self.state_version
        else:
            self._cached_state = None
