"""Continuous-batching serving engine: a fixed pool of decode slots fed by
an admission queue, so requests join and leave a *running* batch instead of
waiting for the slowest sequence in a static batch.

Design
------
* **Paged KV pool** — full-attention K/V lives in a shared page pool
  (``dec.init_paged_cache``): ``n_pages`` pages of ``page_size`` tokens,
  addressed through per-slot page tables. Device memory scales with live
  tokens instead of ``n_slots * max_len``; host bookkeeping (free list,
  refcounts, prefix hashes) lives in ``serve.paging.PagedAllocator``.
  Admission *reserves* a request's worst-case page demand up front
  (``ceil((prompt + max_new - 1) / page_size)``), then allocates decode
  pages lazily as the sequence crosses page boundaries — so admitted
  requests can never deadlock on pages, and unused tail reservations are
  returned at eviction. Page 0 is the garbage page: inactive slots' tables
  point at it so the fused tick's dummy writes never touch live data.
* **Prefix reuse** — for pure-attention stacks (``dec.prefix_sharing_ok``)
  a finished prompt registers each full page's cumulative content hash;
  later requests whose prompt matches page-for-page *share the physical
  pages* (refcount > 1) and skip recomputing them. Shared pages are never
  written — the engine only writes pages it allocated itself, and a
  defensive copy-on-write ``fork`` guards the (unreachable by
  construction) case of a write landing on a shared page.
* **Chunked prefill** — prompts of chunk-exact families
  (``dec.chunk_tokens_for``: pure-attn, attn+SSD) are consumed one
  page-aligned chunk per engine tick, interleaved with fused decode, so a
  long prompt never head-of-line-blocks tokens for running requests.
  Families where chunked math would diverge from a solo run (rgLRU,
  SWA/local windows, MoE capacity routing, enc-dec, modality frontends)
  prefill whole — still into the paged pool, in a single tick.
* **Fused multi-slot decode** — every tick runs ONE ``decode_step`` over
  all N slots with per-slot index and page-table vectors (see
  repro.serve.decode); slots at different sequence offsets decode in the
  same kernel launch. Inactive and still-prefilling slots flow through
  with index 0 and all-garbage page tables: they compute garbage that is
  never read and write only the garbage page.
* **Eviction** — a slot frees on EOS or when the request's ``max_new``
  budget is spent: its pages are released (shared pages just drop one
  reference), outstanding reservations are returned, and the next queued
  request is admitted on the same tick.
* **KAN deploy-once** — KAN-FFN architectures are served against frozen
  ``core.kan.DeployedKAN`` artifacts built at engine construction
  (``tfm.deploy_kan``): int8 coefficient codes, per-output-channel scales
  and the SH-LUT are quantized/built exactly once, never inside a tick.

Exactness
---------
Per-request outputs are independent of co-resident slots for every
batch-independent layer family (attn/swa/local, ssd, rglru, cross-attn,
mlp/kan FFN) — tests/test_engine.py pins this batching invariance against
solo runs, through the paged pool and chunked prefill. The one exception
is MoE capacity routing: GShard token dropping couples tokens across the
batch, so MoE archs match solo runs only when capacity is not binding
(raise ``capacity_factor`` for serving). docs/serving.md walks the
exactness argument per family.

Decoding is greedy (argmax), matching ``serve.decode.generate``.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kan
from repro.dist import sharding as shlib
from repro.models import transformer as tfm
from repro.models.transformer import ModelConfig
from repro.obs.recorder import NullRecorder
from repro.serve import decode as dec
from repro.serve.paging import GARBAGE_PAGE, PagedAllocator, page_hashes
from repro.serve.scheduler import (AdmissionQueue, Completion, EngineStats,
                                   Request)


# The jitted kernels are module-level pure functions (parameterized via
# functools.partial on hashable config, never on the Engine instance): a
# bound-method closure would keep the defining engine — and its whole slot
# pool — alive inside any callable shared through ``adopt_compiled``.

def _decode_fn(params, cache, tokens, index, pages, *, cfg):
    """Fused tick: [N] last tokens + [N] indices + [N, P] page tables ->
    next tokens. Full-attention layers read/write through ``pages``; all
    other layer families keep their per-slot rows."""
    logits, cache = dec.decode_step(params, cache, tokens[:, None], index,
                                    cfg, pages=pages)
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32), cache


def _prefill_fn(params, batch, *, cfg, max_len):
    logits, cache = dec.prefill(params, cfg, batch, max_len=max_len,
                                last_only=True)
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32), cache


def _chunk_fn(params, cache, tokens, start, slot, pages_row, *, cfg,
              first, last):
    """One chunked-prefill step (see ``dec.prefill_chunk``); compiled once
    per (chunk length, first, last) and shared by every slot/offset."""
    return dec.prefill_chunk(params, cfg, cache, tokens, start, slot,
                             pages_row, first=first, last=last)


def _scatter_attn_leaf(pool_leaf, solo_leaf, pages_row, page_size):
    """Write a solo-prefill monolithic K or V row [1, max_len, Kv, hd] into
    the page pool through one slot's page table. The row is padded to whole
    pages; table entries still pointing at the garbage page (positions the
    prompt never reached) harmlessly overwrite garbage-page contents."""
    n_cp = pages_row.shape[0]
    t = solo_leaf.shape[1]
    row = jnp.pad(solo_leaf[0], ((0, n_cp * page_size - t), (0, 0), (0, 0)))
    row = row.reshape(n_cp, page_size, *row.shape[1:])
    return pool_leaf.at[pages_row].set(row.astype(pool_leaf.dtype))


def _scatter_fn(pool, solo, slot, pages_row, *, stages, page_size):
    """Write a whole-prompt (path A) solo prefill cache into the pool:
    full-attention K/V through the slot's page table, every per-slot leaf
    (ssd/rglru state, rolling windows, cross-attn K/V) into row ``slot``.
    Pool donated — XLA updates it in place."""
    out = []
    for pool_blk, solo_blk, stage in zip(pool, solo, stages):
        ax = 1 if stage.repeats > 1 else 0
        nb = {}
        for i, sp in enumerate(stage.block):
            pc, sc = pool_blk[f"l{i}"], solo_blk[f"l{i}"]
            nc = {}
            for key in pc:
                pl, sl = pc[key], sc[key]
                if sp.mixer == "attn" and key in ("k", "v"):
                    if stage.repeats > 1:
                        nc[key] = jax.vmap(
                            lambda a, b: _scatter_attn_leaf(
                                a, b, pages_row, page_size))(pl, sl)
                    else:
                        nc[key] = _scatter_attn_leaf(pl, sl, pages_row,
                                                     page_size)
                else:
                    nc[key] = jax.lax.dynamic_update_slice_in_dim(
                        pl, sl.astype(pl.dtype), slot, axis=ax)
            nb[f"l{i}"] = nc
        out.append(nb)
    return out


def _copy_page_fn(cache, src, dst, *, stages):
    """Copy page ``src`` -> ``dst`` in every full-attention pool (the
    device half of copy-on-write ``fork``)."""
    out = []
    for blk, stage in zip(cache, stages):
        nb = {}
        for i, sp in enumerate(stage.block):
            c = blk[f"l{i}"]
            nc = dict(c)
            if sp.mixer == "attn":
                for key in ("k", "v"):
                    leaf = c[key]
                    if stage.repeats > 1:
                        nc[key] = jax.vmap(
                            lambda x: x.at[dst].set(
                                jnp.take(x, src, axis=0)))(leaf)
                    else:
                        nc[key] = leaf.at[dst].set(jnp.take(leaf, src,
                                                            axis=0))
            nb[f"l{i}"] = nc
        out.append(nb)
    return out


def _chunk_jit_name(key: Tuple[int, bool, bool]) -> str:
    """Profiler name for a chunked-prefill jit. A first-and-last chunk IS a
    whole prompt, so it keeps the historical ``prefill_len{n}`` name (one
    compile per distinct prompt length — pinned by tests/test_obs.py);
    interior/terminal chunks are named by chunk length and position."""
    length, first, last = key
    if first and last:
        return f"prefill_len{length}"
    name = f"prefill_chunk{length}"
    if first:
        name += "_first"
    if last:
        name += "_last"
    return name


class Engine:
    """Continuous-batching engine over a paged KV pool.

    Parameters
    ----------
    params, cfg : model weights + ModelConfig (any supported family).
    n_slots     : decode-slot pool size (the fused tick's batch dimension).
    max_len     : per-slot sequence capacity; a request needs
                  ``len(prompt) + max_new - 1 <= max_len`` (the final
                  generated token never enters the cache).
    page_size   : tokens per KV page. Default ``min(64, max_len)`` — one
                  page per slot, which makes the paged engine byte-for-byte
                  the old monolithic layout (the degenerate config).
    n_pages     : page-pool capacity (page 0 is the garbage page). Default
                  ``n_slots * ceil(max_len / page_size) + 1`` — enough for
                  every slot's worst case, so the page gate never binds;
                  set it lower to actually oversubscribe memory and let
                  admission block on pages.
    queue       : optional AdmissionQueue (bounded => backpressure).
    eos_id      : engine-wide EOS (per-request ``Request.eos_id`` overrides).
    enc_len     : enc-dec only — encoder length shared by all requests.
    device      : optional ``jax.Device`` to pin this engine's params and
                  cache to (``jax.device_put``). Used by the multi-replica
                  router/bench to place data-parallel replicas on distinct
                  devices of the host mesh; mutually exclusive with an
                  active sharding mesh. Default None = jax's default
                  placement (unchanged single-engine behavior). Under an
                  active mesh the cache is sharded by ``paged_cache_spec``
                  and the params by ``param_spec`` (deployed KAN artifacts
                  replicate).
    recorder    : optional ``repro.obs.EngineRecorder``. Default is the
                  no-op ``NullRecorder`` — the tick path then contains no
                  timing calls and no profiled jits. With a recorder, the
                  engine records per-request TTFT/TPOT + queue-wait,
                  per-tick phase timings (admit/prefill/decode/host),
                  page-pool occupancy, prefix-cache hit counters, compile
                  events, and the request lifecycle as Chrome trace spans.
    """

    def __init__(self, params, cfg: ModelConfig, *, n_slots: int,
                 max_len: int, page_size: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 queue: Optional[AdmissionQueue] = None,
                 eos_id: Optional[int] = None, enc_len: int = 0,
                 device=None, recorder=None):
        # KAN-FFN archs serve frozen integer artifacts: deploy() runs
        # EXACTLY ONCE here, so the prefill/decode hot paths contain no
        # coefficient quantization or LUT construction (pinned by
        # core.kan.trace_requantizes in tests and benchmarks/bench_serve).
        self.params = tfm.deploy_kan(params, cfg)
        self.kan_deployed = kan.contains_deployed(self.params)
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.enc_len = enc_len
        self.queue = queue if queue is not None else AdmissionQueue()
        self.eos_id = eos_id
        self.stages = tfm.stages_for(cfg)
        self.mesh = shlib.current_mesh()

        if page_size is None:
            page_size = min(64, max_len)
        if not 1 <= page_size <= max_len:
            raise ValueError(f"page_size must be in [1, max_len], got "
                             f"{page_size} (max_len={max_len})")
        self.page_size = page_size
        self.n_slot_pages = -(-max_len // page_size)      # table width P
        if n_pages is None:
            n_pages = n_slots * self.n_slot_pages + 1
        self.n_pages = n_pages
        self.alloc = PagedAllocator(n_pages, page_size)
        #: chunked-prefill unit (tokens/tick), or None => whole-prompt path
        self.chunk_tokens = dec.chunk_tokens_for(cfg, page_size)
        #: hash-matched prompt prefixes may share physical pages
        self.share_ok = dec.prefix_sharing_ok(cfg)

        self.cache = dec.init_paged_cache(cfg, n_slots, max_len,
                                          page_size=page_size,
                                          n_pages=n_pages, enc_len=enc_len)
        if self.mesh is not None:
            if device is not None:
                raise ValueError("Engine: device placement and an active "
                                 "sharding mesh are mutually exclusive — "
                                 "a replica is either pinned whole to one "
                                 "device or sharded across the mesh")
            shardings = shlib.tree_shardings(self.mesh, self.cache,
                                             dec.paged_cache_spec(cfg))
            self.cache = jax.device_put(self.cache, shardings)
            if self.kan_deployed:
                # deployed KAN artifacts no longer match param_spec; KAN-FFN
                # models are small, so they replicate
                pshard = jax.sharding.NamedSharding(
                    self.mesh, jax.sharding.PartitionSpec())
            else:
                pshard = shlib.tree_shardings(self.mesh, self.params,
                                              tfm.param_spec(cfg))
            self.params = jax.device_put(self.params, pshard)
        elif device is not None:
            self.params = jax.device_put(self.params, device)
            self.cache = jax.device_put(self.cache, device)
        self.device = device

        # host-side per-slot state
        self.active = np.zeros(n_slots, dtype=bool)       # decoding
        self.prefilling = np.zeros(n_slots, dtype=bool)   # consuming prompt
        self.index = np.zeros(n_slots, dtype=np.int64)    # tokens in cache
        self.last_tok = np.zeros(n_slots, dtype=np.int64)
        self.remaining = np.zeros(n_slots, dtype=np.int64)
        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self.slot_tokens: List[List[int]] = [[] for _ in range(n_slots)]
        self.slot_admitted = np.zeros(n_slots, dtype=np.int64)
        # paging state: page table rows, unspent reservations, prefill
        # cursor, held prompt + its page digests (prefix registration)
        self.slot_pages = np.full((n_slots, self.n_slot_pages),
                                  GARBAGE_PAGE, dtype=np.int32)
        self.slot_reserved = np.zeros(n_slots, dtype=np.int64)
        self.slot_pos = np.zeros(n_slots, dtype=np.int64)
        self.slot_prompt: List[Optional[np.ndarray]] = [None] * n_slots
        self.slot_hashes: List[List[bytes]] = [[] for _ in range(n_slots)]

        self.tick_no = 0
        self.stats = EngineStats(n_slots=n_slots, page_size=page_size,
                                 n_pages=n_pages)
        self.obs = recorder if recorder is not None else NullRecorder()
        self._prefill_jit: Dict[Tuple[int, int], object] = {}
        self._chunk_jit: Dict[Tuple[int, bool, bool], object] = {}
        self._decode_jit = jax.jit(
            functools.partial(_decode_fn, cfg=cfg), donate_argnums=1)
        self._scatter_jit = jax.jit(
            functools.partial(_scatter_fn, stages=tuple(self.stages),
                              page_size=page_size), donate_argnums=0)
        self._copy_jit = jax.jit(
            functools.partial(_copy_page_fn, stages=tuple(self.stages)),
            donate_argnums=0)
        if self.obs.enabled:
            from repro.obs import profile as obs_profile
            self._decode_jit = obs_profile.JitProfiler(
                self._decode_jit, "decode_tick", self.obs)
            self._scatter_jit = obs_profile.JitProfiler(
                self._scatter_jit, "cache_write", self.obs)

    def _prefill_for(self, prompt_len: int, enc_len: int):
        key = (prompt_len, enc_len)
        if key not in self._prefill_jit:
            fn = jax.jit(functools.partial(
                _prefill_fn, cfg=self.cfg, max_len=self.max_len))
            if self.obs.enabled:
                from repro.obs import profile as obs_profile
                name = f"prefill_len{prompt_len}"
                if enc_len:
                    name += f"_enc{enc_len}"
                fn = obs_profile.JitProfiler(fn, name, self.obs)
            self._prefill_jit[key] = fn
        return self._prefill_jit[key]

    def _chunk_for(self, length: int, first: bool, last: bool):
        key = (length, first, last)
        if key not in self._chunk_jit:
            fn = jax.jit(functools.partial(
                _chunk_fn, cfg=self.cfg, first=first, last=last),
                donate_argnums=1)
            if self.obs.enabled:
                from repro.obs import profile as obs_profile
                fn = obs_profile.JitProfiler(fn, _chunk_jit_name(key),
                                             self.obs)
            self._chunk_jit[key] = fn
        return self._chunk_jit[key]

    # -- admission / eviction ----------------------------------------------

    def _worst_case_pages(self, prompt_len: int, max_new: int) -> int:
        """Pages needed if the request runs to its full budget (the cache
        holds ``prompt + max_new - 1`` tokens at most)."""
        return -(-(prompt_len + max_new - 1) // self.page_size)

    def validate_request(self, req: Request) -> None:
        """Raise ValueError for a request that can never be served by this
        engine's geometry: non-positive budget, over-length vs the slot
        cache, worst-case page demand beyond the pool, or an enc-dec
        frames mismatch. Shared by ``submit`` and the multi-replica router
        (replicas are geometry-homogeneous, so one replica's verdict holds
        for all)."""
        s = int(np.asarray(req.tokens).shape[-1])
        if req.max_new < 1:
            raise ValueError(f"request {req.rid!r}: max_new must be >= 1")
        if s + req.max_new - 1 > self.max_len:
            raise ValueError(
                f"request {req.rid!r}: prompt {s} + max_new {req.max_new} - 1 "
                f"exceeds slot capacity max_len={self.max_len}")
        if self._worst_case_pages(s, req.max_new) > self.n_pages - 1:
            raise ValueError(
                f"request {req.rid!r}: worst case needs "
                f"{self._worst_case_pages(s, req.max_new)} pages but the "
                f"pool only has {self.n_pages - 1} allocatable pages")
        if req.frames is not None:
            f = int(np.asarray(req.frames).shape[-2])
            if f != self.enc_len:
                # a shorter update would silently write only f of enc_len
                # pool rows, and cross-attn reads the full width — zero (or
                # a previous occupant's) encoder K/V would leak into softmax
                raise ValueError(
                    f"request {req.rid!r}: frames length {f} != engine "
                    f"enc_len {self.enc_len}")
        elif self.enc_len:
            raise ValueError(f"request {req.rid!r}: engine was built with "
                             f"enc_len={self.enc_len} but request has no "
                             "frames")

    def submit(self, req: Request) -> bool:
        """Queue a request. False = backpressure (bounded queue full).
        Raises ValueError for requests that can never fit the slot cache or
        the page pool."""
        self.validate_request(req)
        ok = self.queue.submit(req)
        if ok:
            self.obs.on_submit(req, self.tick_no)
        else:
            self.stats.rejected += 1
            self.obs.on_reject(req)
        return ok

    def _eos_for(self, req: Request) -> Optional[int]:
        return req.eos_id if req.eos_id is not None else self.eos_id

    def _try_admit_pages(self, req: Request):
        """Transactional page admission for one request: claim shared
        prefix pages, then reserve the rest of the worst-case demand.
        Returns (matched page ids, remaining reservation, page digests) or
        None — with all claims rolled back — when the pool can't cover it
        (the request then waits at the head of the queue)."""
        prompt = np.asarray(req.tokens).ravel()
        s = int(prompt.shape[-1])
        worst = self._worst_case_pages(s, req.max_new)
        digests: List[bytes] = []
        matched: List[int] = []
        if self.share_ok:
            digests = page_hashes(prompt, self.page_size)
            # the page holding the last prompt token is never matched: its
            # logits must be computed to produce the first output token
            matched = self.alloc.match_prefix(
                digests[:(s - 1) // self.page_size])
        need = worst - len(matched)
        if not self.alloc.reserve(need):
            for pid in matched:
                self.alloc.release(pid)
            return None
        return matched, need, digests

    def _admit(self, slot: int, req: Request, matched: List[int],
               reserved: int, digests: List[bytes]) -> None:
        """Bind a request to a slot: install matched prefix pages, allocate
        the pages its prompt will write, and mark the slot prefilling. No
        device work happens here — the prefill phase consumes the prompt."""
        self.obs.on_admit(req, slot, self.tick_no)
        prompt = np.asarray(np.asarray(req.tokens).ravel(), dtype=np.int64)
        s = int(prompt.shape[-1])
        n_prompt_pages = -(-s // self.page_size)
        self.slot_pages[slot, :len(matched)] = matched
        for i in range(len(matched), n_prompt_pages):
            self.slot_pages[slot, i] = self.alloc.alloc(reserved=True)
            reserved -= 1
        self.slot_reserved[slot] = reserved
        self.slot_pos[slot] = len(matched) * self.page_size
        self.slot_prompt[slot] = prompt
        self.slot_hashes[slot] = digests
        self.prefilling[slot] = True
        self.active[slot] = False
        self.slot_req[slot] = req
        self.slot_tokens[slot] = []
        self.slot_admitted[slot] = self.tick_no
        self.stats.slot_served[slot] += 1
        if self.share_ok:
            eligible = (s - 1) // self.page_size
            self.stats.prefix_hit_pages += len(matched)
            self.stats.prefix_eligible_pages += eligible
            self.obs.on_prefix(len(matched), eligible)

    def _prefill_tick(self, slot: int) -> List[Completion]:
        """Advance one prefilling slot: the whole prompt for single-piece
        families (path A: solo prefill + scatter through the page table),
        one ``chunk_tokens`` chunk otherwise (path B). Returns completions
        when the prompt's first token already satisfies a stop rule."""
        req = self.slot_req[slot]
        prompt = self.slot_prompt[slot]
        s = int(prompt.shape[-1])
        pages_row = jnp.asarray(self.slot_pages[slot])
        if self.chunk_tokens is None:
            toks = jnp.asarray(prompt.astype(np.int32))[None, :]
            batch = {"tokens": toks}
            enc_len = 0
            if req.frames is not None:
                frames = jnp.asarray(np.asarray(req.frames))[None]
                batch["frames"] = frames
                enc_len = frames.shape[1]
            tok0, solo = self._prefill_for(s, enc_len)(self.params, batch)
            self.cache = self._scatter_jit(self.cache, solo,
                                           jnp.asarray(slot, jnp.int32),
                                           pages_row)
            return self._finish_prefill(slot, int(np.asarray(tok0)[0]))
        pos = int(self.slot_pos[slot])
        length = min(self.chunk_tokens, s - pos)
        first = pos == 0
        last = pos + length == s
        chunk = jnp.asarray(prompt[pos:pos + length].astype(np.int32))[None]
        tok, self.cache = self._chunk_for(length, first, last)(
            self.params, self.cache, chunk, jnp.asarray(pos, jnp.int32),
            jnp.asarray(slot, jnp.int32), pages_row)
        self.slot_pos[slot] = pos + length
        self.stats.prefill_chunks += 1
        if last:
            return self._finish_prefill(slot, int(np.asarray(tok)[0]))
        return []

    def _finish_prefill(self, slot: int, tok0: int) -> List[Completion]:
        """Prompt fully consumed: publish page hashes for prefix reuse,
        record TTFT, and flip the slot to decoding (it joins this very
        tick's fused decode)."""
        req = self.slot_req[slot]
        s = int(self.slot_prompt[slot].shape[-1])
        if self.share_ok:
            # every FULL prompt page is now written and immutable until
            # eviction: publish for prefix matching (no-op for pages that
            # were themselves matched — first writer wins)
            for i, d in enumerate(self.slot_hashes[slot]):
                self.alloc.register_hash(int(self.slot_pages[slot, i]), d)
        ttft = self.obs.on_first_token(req, self.tick_no)
        if ttft is not None:
            self.stats.ttft_s.append(ttft)
        self.prefilling[slot] = False
        self.active[slot] = True
        self.index[slot] = s
        self.last_tok[slot] = tok0
        self.remaining[slot] = req.max_new - 1
        self.slot_tokens[slot] = [tok0]
        self.stats.prefills += 1
        # the prefill token may already satisfy a stop condition
        eos = self._eos_for(req)
        if eos is not None and tok0 == eos:
            return [self._evict(slot, "eos")]
        if self.remaining[slot] <= 0:
            return [self._evict(slot, "length")]
        return []

    def try_admit(self, req: Request) -> bool:
        """Transactional slot+page admission that bypasses the local
        queue: True binds ``req`` to a free slot (prefill starts next
        ``step``), False changes nothing — no free slot, or the page pool
        can't cover the worst case right now. This is the replica-facing
        seam the multi-replica router dispatches through: the router owns
        the *global* queue and its FIFO discipline, so the engine must
        not interpose its own."""
        free = np.flatnonzero(~self.active & ~self.prefilling)
        if not len(free):
            return False
        adm = self._try_admit_pages(req)
        if adm is None:
            return False
        self._admit(int(free[0]), req, *adm)
        return True

    def _release_slot(self, slot: int) -> None:
        """Free a slot's pages (shared pages drop one reference), return
        unspent reservations, and clear all per-slot host state. Common
        tail of ``_evict`` (normal completion) and ``preempt`` (drain)."""
        for pg in range(self.n_slot_pages):
            pid = int(self.slot_pages[slot, pg])
            if pid != GARBAGE_PAGE:
                self.alloc.release(pid)
        self.slot_pages[slot, :] = GARBAGE_PAGE
        self.alloc.unreserve(int(self.slot_reserved[slot]))
        self.slot_reserved[slot] = 0
        self.active[slot] = False
        self.prefilling[slot] = False
        self.slot_req[slot] = None
        self.slot_tokens[slot] = []
        self.slot_prompt[slot] = None
        self.slot_hashes[slot] = []

    def _evict(self, slot: int, reason: str) -> Completion:
        req = self.slot_req[slot]
        comp = Completion(
            rid=req.rid, tokens=np.asarray(self.slot_tokens[slot]),
            reason=reason, slot=slot,
            admitted_tick=int(self.slot_admitted[slot]),
            finished_tick=self.tick_no)
        self._release_slot(slot)
        self.stats.completed += 1
        if reason == "eos":
            self.stats.evicted_eos += 1
        else:
            self.stats.evicted_length += 1
        self.obs.on_evict(comp)
        return comp

    def preempt(self, slot: int) -> Request:
        """Forcibly evict the request bound to ``slot`` and hand it back
        for requeueing elsewhere. All progress is discarded — pages,
        reservations, and any generated tokens (greedy decoding is
        deterministic, so a clean re-run elsewhere emits the identical
        token sequence; resuming mid-stream would need page migration
        across replica pools). Drain-time tool of the router."""
        req = self.slot_req[slot]
        if req is None:
            raise ValueError(f"preempt: slot {slot} is idle")
        self._release_slot(slot)
        self.stats.preempted += 1
        self.obs.on_preempt(req, slot)
        return req

    def drain_queued(self) -> List[Request]:
        """Remove and return every request still waiting in the local
        admission queue (pop order). With the router, the local queue is
        unused and this returns [] — it exists so drain handles engines
        that were also fed directly."""
        return self.queue.drain()

    # -- the tick -----------------------------------------------------------

    def _ensure_decode_pages(self) -> None:
        """Give every active slot a writable page for this tick's token:
        allocate lazily (consuming the slot's reservation) when the table
        still points at the garbage page, and copy-on-write fork when the
        target is shared. The fork path is unreachable by construction —
        decode only ever writes pages past the registered prompt pages —
        but it keeps the invariant 'never write refcount>1' local and
        checkable rather than global and assumed."""
        for slot in np.flatnonzero(self.active):
            slot = int(slot)
            pg = int(self.index[slot]) // self.page_size
            pid = int(self.slot_pages[slot, pg])
            if pid == GARBAGE_PAGE:
                self.slot_pages[slot, pg] = self.alloc.alloc(reserved=True)
                self.slot_reserved[slot] -= 1
            elif self.alloc.refcount[pid] > 1:
                new = self.alloc.fork(pid)
                self.cache = self._copy_jit(self.cache,
                                            jnp.asarray(pid, jnp.int32),
                                            jnp.asarray(new, jnp.int32))
                self.slot_pages[slot, pg] = new

    def step(self) -> List[Completion]:
        """One engine tick: admit whatever fits (slots AND pages), advance
        every prefilling slot by one chunk, then one fused decode over all
        slots. Returns the requests completed during this tick."""
        done: List[Completion] = []
        obs = self.obs
        with obs.phase("admit"):
            while True:
                free = np.flatnonzero(~self.active & ~self.prefilling)
                if not len(free):
                    break
                req = self.queue.peek(self.tick_no)
                if req is None:
                    break
                adm = self._try_admit_pages(req)
                if adm is None:
                    break               # page pool full: head of queue waits
                self.queue.pop(self.tick_no)
                self._admit(int(free[0]), req, *adm)

        if self.prefilling.any():
            with obs.phase("prefill"):
                for slot in np.flatnonzero(self.prefilling):
                    done += self._prefill_tick(int(slot))

        if self.active.any():
            self._ensure_decode_pages()
            # inactive/prefilling slots still flow through the fused step
            # (static batch shape): index 0 keeps their garbage writes
            # in-bounds and an all-garbage page table keeps them off every
            # live page.
            tokens = jnp.asarray(np.where(self.active, self.last_tok, 0)
                                 .astype(np.int32))
            index = jnp.asarray(np.where(self.active, self.index, 0)
                                .astype(np.int32))
            pages = jnp.asarray(np.where(self.active[:, None],
                                         self.slot_pages, GARBAGE_PAGE)
                                .astype(np.int32))
            with obs.phase("decode") as ph:
                nxt, self.cache = self._decode_jit(self.params, self.cache,
                                                   tokens, index, pages)
                nxt = np.asarray(nxt)       # blocks: real decode latency
            n_active = int(self.active.sum())
            if obs.enabled:
                # the fused tick produced one token per active slot: each of
                # those tokens experienced the tick's wall time as its TPOT
                obs.on_decode_tick(n_active, ph.dur_s)
                self.stats.tpot_s.extend([ph.dur_s] * n_active)
            self.stats.occupancy_ticks += n_active
            self.stats.decode_tokens += n_active
            with obs.phase("host"):
                for slot in np.flatnonzero(self.active):
                    slot = int(slot)
                    tok = int(nxt[slot])
                    self.slot_tokens[slot].append(tok)
                    self.index[slot] += 1
                    self.last_tok[slot] = tok
                    self.remaining[slot] -= 1
                    eos = self._eos_for(self.slot_req[slot])
                    if eos is not None and tok == eos:
                        done.append(self._evict(slot, "eos"))
                    elif self.remaining[slot] <= 0:
                        done.append(self._evict(slot, "length"))
        elif not self.prefilling.any():
            self.stats.idle_ticks += 1
        self.stats.pages_in_use_peak = self.alloc.in_use_peak
        obs.on_page_pool(self.alloc.in_use, self.n_pages)
        self.tick_no += 1
        self.stats.ticks += 1
        return done

    def adopt_compiled(self, other: "Engine") -> "Engine":
        """Reuse another engine's compiled prefill/tick/write callables —
        warm starts for probe/benchmark engines with identical cfg, slot
        count, max_len, and page geometry (the jit caches key on those
        shapes)."""
        mine = (self.cfg, self.n_slots, self.max_len, self.page_size,
                self.n_pages)
        theirs = (other.cfg, other.n_slots, other.max_len, other.page_size,
                  other.n_pages)
        if mine != theirs:
            raise ValueError("adopt_compiled: engines differ in "
                             "cfg/n_slots/max_len/page_size/n_pages")
        self._prefill_jit = other._prefill_jit
        self._chunk_jit = other._chunk_jit
        self._decode_jit = other._decode_jit
        self._scatter_jit = other._scatter_jit
        self._copy_jit = other._copy_jit
        if self.obs.enabled:
            # re-bind adopted profilers to THIS engine's recorder (sharing
            # their warm compiled caches); raw unprofiled jits are left
            # untouched — re-wrapping them would force an AOT recompile
            from repro.obs import profile as obs_profile

            def rebind(fn, name):
                if isinstance(fn, obs_profile.JitProfiler):
                    return obs_profile.JitProfiler(fn, name, self.obs)
                return fn

            self._decode_jit = rebind(self._decode_jit, "decode_tick")
            self._scatter_jit = rebind(self._scatter_jit, "cache_write")
            self._prefill_jit = {
                k: rebind(fn, f"prefill_len{k[0]}"
                          + (f"_enc{k[1]}" if k[1] else ""))
                for k, fn in other._prefill_jit.items()}
            self._chunk_jit = {
                k: rebind(fn, _chunk_jit_name(k))
                for k, fn in other._chunk_jit.items()}
        return self

    def run(self, requests: Sequence[Request] = (),
            max_ticks: int = 1_000_000) -> List[Completion]:
        """Submit ``requests`` then tick until the queue drains and every
        slot is free. Idle stretches are *fast-forwarded*: when every slot
        is free and the queue holds only future arrivals, ``tick_no`` jumps
        straight to the next arrival instead of burning one host-loop
        iteration per idle tick — the skipped ticks are counted in
        ``idle_ticks`` (and ``ff_ticks``), so occupancy math is unchanged.
        When the admission queue is bounded, ``run`` itself absorbs the
        backpressure: requests the queue refuses are held back and
        resubmitted as it drains, so nothing is silently dropped."""
        pending = list(requests)
        t0 = time.perf_counter()
        out: List[Completion] = []
        while (pending or self.active.any() or self.prefilling.any()
               or len(self.queue)):
            while pending and (self.queue.max_pending is None
                               or len(self.queue) < self.queue.max_pending):
                self.submit(pending.pop(0))
            if (not self.active.any() and not self.prefilling.any()
                    and len(self.queue)):
                nxt = self.queue.next_arrival()
                if nxt is not None and nxt > self.tick_no:
                    skip = nxt - self.tick_no
                    self.tick_no = nxt
                    self.stats.ticks += skip
                    self.stats.idle_ticks += skip
                    self.stats.ff_ticks += skip
            if self.stats.ticks >= max_ticks:
                raise RuntimeError(f"engine exceeded max_ticks={max_ticks}")
            out.extend(self.step())
        self.stats.wall_s += time.perf_counter() - t0
        return out


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def synth_trace(vocab: int, n_requests: int, *, max_prompt: int = 12,
                min_prompt: int = 4, max_new: int = 8, min_new: int = 3,
                stagger: int = 2, n_priorities: int = 2,
                common_prefix: int = 0, seed: int = 0) -> List[Request]:
    """Staggered-arrival synthetic trace: request i arrives at tick
    ``i * stagger`` with a random prompt length/budget and a cycling
    priority class — the canonical input for the driver, the benchmark, and
    the batching-invariance tests. ``common_prefix`` prepends that many
    shared tokens to every prompt (drawn once), which exercises the paged
    engine's prefix-sharing path on archs where it is enabled; 0 (the
    default) reproduces the historical traces bit-for-bit."""
    rng = np.random.RandomState(seed)
    prefix = (rng.randint(0, vocab, size=(common_prefix,)).astype(np.int32)
              if common_prefix else np.zeros((0,), np.int32))
    reqs = []
    for i in range(n_requests):
        s = int(rng.randint(min_prompt, max_prompt + 1))
        toks = np.concatenate(
            [prefix, rng.randint(0, vocab, size=(s,)).astype(np.int32)])
        reqs.append(Request(
            rid=i,
            tokens=toks,
            max_new=int(rng.randint(min_new, max_new + 1)),
            priority=i % n_priorities,
            arrival=i * stagger))
    return reqs


def make_replicas(params, cfg: ModelConfig, n_replicas: int, *,
                  adopt_from: Optional[Engine] = None, recorder_for=None,
                  **engine_kw) -> List[Engine]:
    """Data-parallel replicas for ``serve.router.Router``: replica i is
    pinned whole to ``jax.devices()[i % n_devices]``. Replica 0 deploys any
    KAN artifacts once; the others copy its deployed params to their own
    device and adopt its jitted callables (jit still compiles once per
    device). ``adopt_from`` warm-starts replica 0 from an engine of the
    same geometry; ``recorder_for(i)`` gives replica i's recorder;
    ``engine_kw`` is the shared geometry (``n_slots``, ``max_len``, ...)."""
    devices = jax.devices()
    rec = recorder_for or (lambda i: None)
    first = Engine(params, cfg, device=devices[0], recorder=rec(0),
                   **engine_kw)
    if adopt_from is not None:
        first.adopt_compiled(adopt_from)
    replicas = [first]
    for i in range(1, n_replicas):
        replicas.append(Engine(
            first.params, cfg, device=devices[i % len(devices)],
            recorder=rec(i), **engine_kw).adopt_compiled(first))
    return replicas


def generate_dynamic(params, cfg: ModelConfig, prompts: Sequence,
                     n_new: int, max_len: Optional[int] = None,
                     n_slots: Optional[int] = None) -> jax.Array:
    """Ragged-batch greedy generation via the engine: ``prompts`` is a list
    of 1-D token arrays with heterogeneous lengths. Returns [B, n_new]
    (every request generates exactly ``n_new`` tokens; no EOS)."""
    lens = [int(np.asarray(p).shape[-1]) for p in prompts]
    max_len = max_len or (max(lens) + n_new)
    n_slots = n_slots or min(len(prompts), 4)
    eng = Engine(params, cfg, n_slots=n_slots, max_len=max_len)
    reqs = [Request(rid=i, tokens=p, max_new=n_new)
            for i, p in enumerate(prompts)]
    comps = eng.run(reqs)
    out = np.zeros((len(prompts), n_new), dtype=np.int64)
    for c in comps:
        out[c.rid] = c.tokens
    return jnp.asarray(out)
