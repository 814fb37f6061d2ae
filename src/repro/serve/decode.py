"""Serving: prefill (build caches) and single-token decode steps.

Cache layouts per layer type (stacked [repeats, ...] inside scanned stages):
  attn  — K/V caches [B, T, Kv, hd]; T = max_len for full attention, the
          window size for SWA/local layers (rolling ring buffer — softmax is
          permutation-invariant over KV so ring order is fine).
  ssd   — recurrent state [B, H, P, N] + depthwise-conv ring buffer.
  rglru — hidden state [B, dr] + conv buffer.
  cross — encoder K/V computed once at prefill, read-only at decode.

``decode_step`` and ``prefill_chunk`` run a scanned stage as one layer scan
that carries the stacked cache (``_run_layers``): each layer reads its own
rows at the layer index and writes back only the rows it changes, so a
donated cache is updated in place.

``decode_step`` is the artifact lowered for the ``decode_32k``/``long_500k``
dry-run cells: one new token against a cache of the given sequence length.
SSM/hybrid archs carry O(1) state — that is their long_500k story.

Paged serving (the continuous-batching engine's layout): full-attention
K/V lives in a shared page pool instead of per-slot rows —
``init_paged_cache`` builds [n_pages, page_size, Kv, hd] pools for every
``attn`` layer (one logical page-id space indexes all of them), while
SWA/local rings, SSD/rgLRU state, conv buffers and cross-attn K/V stay
per-slot. ``decode_step(..., pages=[B, P])`` routes reads/writes through
the page tables, and ``prefill_chunk`` consumes a prompt page-aligned
chunk at a time so prefill interleaves into decode ticks (docs/serving.md
covers the exactness argument per layer family; ``chunk_tokens_for``
returns the largest chunk unit that keeps the math identical to a solo
run, or None for families that must prefill in one piece).

Every op of a decode or prefill-chunk layer sits under one
``jax.named_scope`` a device trace can group by: ``mixer`` (norm, QKV,
rope, attention or the SSD/rgLRU block),
``cache_update`` (K/V pool writes, recurrent-state write masks), ``ffn``
(MLP, MoE or KAN-FFN) and, after the layers, ``unembed`` (final norm,
logits, argmax). Finer scopes nest inside them: ``paged_attention``,
``ssd_scan``, ``kan_ffn``. Scopes are op metadata only.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import kan
from repro.dist.sharding import shard
from repro.models import attention as attn_lib
from repro.models import layers, moe as moe_lib, rglru as rglru_lib
from repro.models import ssd as ssd_lib
from repro.models import transformer as tfm
from repro.models.transformer import LayerSpec, ModelConfig, Stage

Array = jax.Array


def _kv_len(spec: LayerSpec, cfg: ModelConfig, max_len: int) -> Tuple[int, bool]:
    if spec.mixer == "swa" and cfg.window:
        return min(cfg.window, max_len), True
    if spec.mixer == "local" and cfg.local_window:
        return min(cfg.local_window, max_len), True
    return max_len, False


def _init_layer_cache(spec: LayerSpec, cfg: ModelConfig, batch: int,
                      max_len: int, enc_len: int = 0) -> Dict[str, Array]:
    c: Dict[str, Array] = {}
    hd = cfg.resolved_head_dim
    if spec.mixer in ("attn", "swa", "local"):
        t, _ = _kv_len(spec, cfg, max_len)
        shape = (batch, t, cfg.padded_kv_heads, hd)
        c["k"] = jnp.zeros(shape, cfg.dtype)
        c["v"] = jnp.zeros(shape, cfg.dtype)
    elif spec.mixer == "ssd":
        c.update(ssd_lib.init_ssd_cache(batch, cfg.ssd_cfg, cfg.dtype))
    elif spec.mixer == "rglru":
        c.update(rglru_lib.init_rglru_cache(batch, cfg.rglru_cfg, cfg.dtype))
    if spec.cross_attn:
        c["ck"] = jnp.zeros((batch, enc_len, cfg.padded_kv_heads, hd),
                            cfg.dtype)
        c["cv"] = jnp.zeros((batch, enc_len, cfg.padded_kv_heads, hd),
                            cfg.dtype)
    return c


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               enc_len: int = 0) -> list:
    """Cache pytree parallel to params["stages"]."""
    out = []
    for stage in tfm.stages_for(cfg):
        blk = {f"l{i}": _init_layer_cache(sp, cfg, batch, max_len, enc_len)
               for i, sp in enumerate(stage.block)}
        if stage.repeats > 1:
            blk = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None],
                                           (stage.repeats,) + x.shape), blk)
        out.append(blk)
    return out


def init_paged_cache(cfg: ModelConfig, n_slots: int, max_len: int, *,
                     page_size: int, n_pages: int, enc_len: int = 0) -> list:
    """Cache pytree for the paged serving engine.

    Identical to ``init_cache`` except that every full-attention layer's
    K/V becomes a shared page pool [n_pages, page_size, Kv, hd]: slots
    address it through page tables (``pages`` in ``decode_step``) instead
    of owning a row, so device memory scales with live tokens rather than
    ``n_slots * max_len``. One logical page-id space indexes every layer's
    pool. SWA/local rings, SSD/rgLRU state and cross-attn K/V keep their
    per-slot [n_slots, ...] layout (their footprint is already O(1) or
    window-bounded per slot)."""
    hd = cfg.resolved_head_dim
    pool_shape = (n_pages, page_size, cfg.padded_kv_heads, hd)
    out = []
    for stage in tfm.stages_for(cfg):
        blk = {}
        for i, sp in enumerate(stage.block):
            c = _init_layer_cache(sp, cfg, n_slots, max_len, enc_len)
            if sp.mixer == "attn":
                c["k"] = jnp.zeros(pool_shape, cfg.dtype)
                c["v"] = jnp.zeros(pool_shape, cfg.dtype)
            blk[f"l{i}"] = c
        if stage.repeats > 1:
            blk = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None],
                                           (stage.repeats,) + x.shape), blk)
        out.append(blk)
    return out


def _cache_spec(cfg: ModelConfig, paged: bool) -> list:
    kv_tail = "head_dim" if cfg.kv_shard_mode == "head_dim" else "none"

    def layer_spec(spec: LayerSpec):
        s = {}
        if spec.mixer == "attn" and paged:
            # page pool: page axis replicated, heads sharded as usual
            s["k"] = ("none", "none", "kv_heads", kv_tail)
            s["v"] = ("none", "none", "kv_heads", kv_tail)
        elif spec.mixer in ("attn", "swa", "local"):
            s["k"] = ("batch", "seq", "kv_heads", kv_tail)
            s["v"] = ("batch", "seq", "kv_heads", kv_tail)
        elif spec.mixer == "ssd":
            s["state"] = ("batch", "heads", "none", "none")
            s["conv_buf"] = ("batch", "none", "state")
        elif spec.mixer == "rglru":
            s["h"] = ("batch", "state")
            s["conv_buf"] = ("batch", "none", "state")
        if spec.cross_attn:
            s["ck"] = ("batch", "seq", "kv_heads", kv_tail)
            s["cv"] = ("batch", "seq", "kv_heads", kv_tail)
        return s
    out = []
    for stage in tfm.stages_for(cfg):
        blk = {f"l{i}": layer_spec(sp) for i, sp in enumerate(stage.block)}
        if stage.repeats > 1:
            blk = jax.tree.map(lambda n: ("layers",) + n, blk,
                               is_leaf=lambda x: isinstance(x, tuple))
        out.append(blk)
    return out


def cache_spec(cfg: ModelConfig) -> list:
    """Logical sharding names for the ``init_cache`` pytree (kv_heads falls
    back to head_dim sharding when the head count does not divide the model
    axis)."""
    return _cache_spec(cfg, paged=False)


def paged_cache_spec(cfg: ModelConfig) -> list:
    """Logical sharding names for the ``init_paged_cache`` pytree: page
    pools replicate their page axis and shard kv_heads/head_dim exactly
    like monolithic rows; per-slot leaves keep the ``cache_spec`` names."""
    return _cache_spec(cfg, paged=True)


def chunk_tokens_for(cfg: ModelConfig, page_size: int) -> Optional[int]:
    """Chunked-prefill unit (tokens per engine tick) for this arch, or None
    when the arch must prefill each prompt in a single piece.

    Chunking is enabled only where the chunked math is *exact* against a
    solo full-prompt run: pure-attention stacks (masked page slots
    contribute exact zeros to the online softmax) and attention+SSD stacks
    (``ssd_chunked`` carries ``init_state`` across chunks, provided chunk
    boundaries are multiples of the SSD scan chunk — hence the lcm).
    rgLRU (associative-scan tree grouping changes with segment length),
    SWA/local windows, MoE FFNs (capacity routing couples tokens across
    the chunk), enc-dec and modality-frontend archs prefill whole —
    still through the paged pool, still interleaved into the tick loop,
    just not split."""
    if cfg.family == "encdec" or cfg.frontend != "none":
        return None
    specs = [sp for st in tfm.stages_for(cfg) for sp in st.block]
    mixers = {sp.mixer for sp in specs}
    if any(sp.ffn == "moe" for sp in specs) or not mixers <= {"attn", "ssd"}:
        return None
    step = page_size
    if "ssd" in mixers:
        c = cfg.ssd_cfg.chunk
        step = step * c // math.gcd(step, c)
    return step


def prefix_sharing_ok(cfg: ModelConfig) -> bool:
    """Whether hash-matched prompt prefixes may share physical pages.

    True only for pure-attention decoder-only stacks: all of a request's
    sequence state then lives in the (position-aligned, content-identical)
    pages themselves. Any recurrent mixer carries per-slot state that the
    pool does not capture, and enc-dec K/V depends on the encoder input,
    so those families always recompute."""
    if chunk_tokens_for(cfg, 1) is None:
        return False
    return {sp.mixer for st in tfm.stages_for(cfg)
            for sp in st.block} == {"attn"}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _qkv(p, xn, cfg: ModelConfig, which: str = "attn"):
    q = jnp.einsum("bsd,dhk->bshk", xn, p[which]["wq"].astype(cfg.dtype))
    k = jnp.einsum("bsd,dhk->bshk", xn, p[which]["wk"].astype(cfg.dtype))
    v = jnp.einsum("bsd,dhk->bshk", xn, p[which]["wv"].astype(cfg.dtype))
    if "bq" in p[which]:
        q = q + p[which]["bq"].astype(cfg.dtype)
        k = k + p[which]["bk"].astype(cfg.dtype)
        v = v + p[which]["bv"].astype(cfg.dtype)
    return q, k, v


def _mask_state_writes(new, cache, pages: Optional[Array]):
    """Keep recurrent per-slot state (ssd/rglru rows) frozen for slots that
    are not actively decoding. Full-attention garbage writes are harmless —
    inactive slots' page tables point at the garbage page — but recurrent
    rows have no such indirection, and a slot mid chunked-prefill holds
    REAL carried state in its row that a fused tick between chunks would
    clobber. The page table doubles as the activity mask: the engine zeroes
    inactive slots' rows to GARBAGE_PAGE, so row 0 is a real page iff the
    slot is decoding."""
    if pages is None:                      # solo / static batching: no-op
        return new
    act = pages[:, 0] != 0                 # GARBAGE_PAGE
    return {k: jnp.where(act.reshape((-1,) + (1,) * (v.ndim - 1)),
                         v, cache[k].astype(v.dtype))
            for k, v in new.items()}


def _rows(leaf: Array, layer: Optional[Array], slot: Optional[Array] = None
          ) -> Array:
    """One layer's rows of a cache leaf, or with ``slot`` that slot's row
    alone (kept as a batch of one). A scanned stage's leaves are stacked
    [repeats, B, ...] and read at ``layer``; an unstacked layer's own leaf
    (``layer`` None) is already its rows."""
    if layer is not None:
        leaf = jax.lax.dynamic_index_in_dim(leaf, layer, keepdims=False)
    if slot is not None:
        leaf = jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=0)
    return leaf


def _set_rows(leaf: Array, rows: Array, layer: Optional[Array],
              slot: Optional[Array] = None) -> Array:
    """Write ``rows`` back where ``_rows(leaf, layer, slot)`` read them and
    leave the rest of the leaf as it is, so a stacked leaf carried through
    the layer scan is updated in place."""
    rows = rows.astype(leaf.dtype)
    if layer is None and slot is None:
        return rows
    if layer is not None:
        rows = rows[None]
    starts = [i for i in (layer, slot) if i is not None]
    return jax.lax.dynamic_update_slice(
        leaf, rows, starts + [0] * (leaf.ndim - len(starts)))


def _run_layers(params, cache, x, cfg: ModelConfig, layer_fn):
    """Run every stage's layers over ``x``, threading the cache through.

    ``layer_fn(p, c, x, spec, layer) -> (x, c)`` runs one layer on its
    cache dict ``c``. An unstacked stage runs its layers in turn on their
    own leaves (``layer`` None). A stacked stage (``repeats > 1``) is one
    ``lax.scan`` over its params and the layer index that carries ``x`` and
    the stacked cache: each layer reads its rows at the index and writes
    back only the leaves and rows it changes, so a donated cache is updated
    in place — no whole-cache copy, slice or write-back per call."""
    new_caches = []
    for st_params, st_cache, stage in zip(params["stages"], cache,
                                          tfm.stages_for(cfg)):
        if stage.repeats == 1:
            nc = {}
            for i, sp in enumerate(stage.block):
                x, nc[f"l{i}"] = layer_fn(st_params[f"l{i}"],
                                          st_cache[f"l{i}"], x, sp, None)
        else:
            def body(carry, inp, stage=stage):
                xx, c = carry
                lp, layer = inp
                c = dict(c)
                for i, sp in enumerate(stage.block):
                    xx, c[f"l{i}"] = layer_fn(lp[f"l{i}"], c[f"l{i}"], xx,
                                              sp, layer)
                return (xx, c), None
            (x, nc), _ = jax.lax.scan(
                body, (x, st_cache),
                (st_params, jnp.arange(stage.repeats, dtype=jnp.int32)))
        new_caches.append(nc)
    return x, new_caches


def _ffn(p, x, spec: LayerSpec, cfg: ModelConfig, **moe_kw):
    """The layer's FFN residual update (MLP, MoE or KAN-FFN) under the
    ``ffn`` scope. DeployedKAN subtrees (``tfm.deploy_kan``) run the frozen
    integer artifact; raw param trees run the float training path."""
    if spec.ffn not in ("mlp", "moe", "kan"):
        return x
    with jax.named_scope("ffn"):
        if spec.ffn == "mlp":
            return x + tfm._mlp_ffn(p, x, cfg)
        xn = layers.NORM_APPLY[cfg.norm](p["ffn_norm"], x)
        if spec.ffn == "moe":
            y, _ = moe_lib.apply_moe(p["moe"], xn, cfg.moe_cfg, **moe_kw)
            return x + y
        with jax.named_scope("kan_ffn"):
            return x + kan.apply_any(p["kan"], xn,
                                     cfg.kan_spec).astype(x.dtype)


def _unembed(params, x, cfg: ModelConfig) -> Array:
    """Final norm and logits (soft-capped where the config says) under the
    ``unembed`` scope."""
    with jax.named_scope("unembed"):
        x = layers.NORM_APPLY[cfg.norm](params["final_norm"], x)
        table = params.get("unembed", params["embed"])
        logits = layers.unembed(x, table.astype(cfg.dtype))
        if cfg.logits_softcap:
            logits = cfg.logits_softcap * jnp.tanh(
                logits / cfg.logits_softcap)
        return logits


def _decode_layer(p, cache, x, spec: LayerSpec, cfg: ModelConfig,
                  index: Array, pages: Optional[Array] = None,
                  layer: Optional[Array] = None):
    """x: [B, 1, D]; index: count of tokens so far (0-based position of the
    token being decoded) — scalar, or [B] for per-slot continuous batching.
    ``pages`` ([B, P] page tables) switches full-attention layers onto the
    paged pool layout; all other layer kinds ignore it. ``layer`` indexes
    stacked cache leaves inside the layer scan (``_run_layers``)."""
    new_cache = dict(cache)
    if spec.mixer == "attn" and pages is not None:
        with jax.named_scope("mixer"):
            xn = layers.NORM_APPLY[cfg.norm](p["mixer_norm"], x)
            q, k, v = _qkv(p, xn, cfg)
            if cfg.rope_theta:
                pos = (index[:, None] if index.ndim
                       else jnp.full((1, 1), index))
                q = layers.apply_rope(q, pos, cfg.rope_theta)
                k = layers.apply_rope(k, pos, cfg.rope_theta)
            bidx = jnp.broadcast_to(jnp.asarray(index), (x.shape[0],))
        with jax.named_scope("cache_update"):
            kp, vp = attn_lib.paged_cache_update(cache["k"], cache["v"], k,
                                                 v, pages, bidx, layer)
        new_cache["k"], new_cache["v"] = kp, vp
        with jax.named_scope("mixer"):
            with jax.named_scope("paged_attention"):
                ck = attn_lib.paged_gather(kp, pages, layer)
                cv = attn_lib.paged_gather(vp, pages, layer)
                o = attn_lib.decode_attention(q, ck, cv, bidx + 1)
            x = x + jnp.einsum("bshk,hkd->bsd", o,
                               p["attn"]["wo"].astype(cfg.dtype))
    elif spec.mixer in ("attn", "swa", "local"):
        with jax.named_scope("mixer"):
            xn = layers.NORM_APPLY[cfg.norm](p["mixer_norm"], x)
            q, k, v = _qkv(p, xn, cfg)
            if cfg.rope_theta:
                pos = index[:, None] if index.ndim else jnp.full((1,), index)
                q = layers.apply_rope(q, pos, cfg.rope_theta)
                k = layers.apply_rope(k, pos, cfg.rope_theta)
        rolling = spec.mixer in ("swa", "local")
        with jax.named_scope("cache_update"):
            ck, cv = attn_lib.cache_update(_rows(cache["k"], layer),
                                           _rows(cache["v"], layer), k, v,
                                           index, rolling=rolling)
            new_cache["k"] = _set_rows(cache["k"], ck, layer)
            new_cache["v"] = _set_rows(cache["v"], cv, layer)
        with jax.named_scope("mixer"):
            o = attn_lib.decode_attention(q, ck, cv, index + 1,
                                          rolling=rolling)
            x = x + jnp.einsum("bshk,hkd->bsd", o,
                               p["attn"]["wo"].astype(cfg.dtype))
    elif spec.mixer in ("ssd", "rglru"):
        keys = ("state", "conv_buf") if spec.mixer == "ssd" else (
            "h", "conv_buf")
        with jax.named_scope("mixer"):
            old = {k: _rows(cache[k], layer) for k in keys}
            xn = layers.NORM_APPLY[cfg.norm](p["mixer_norm"], x)
            if spec.mixer == "ssd":
                y, sc = ssd_lib.apply_ssd_block_decode(p["ssd"], xn, old,
                                                       cfg.ssd_cfg)
            else:
                y, sc = rglru_lib.apply_rglru_block_decode(
                    p["rglru"], xn, old, cfg.rglru_cfg)
        with jax.named_scope("cache_update"):
            for k, rows in _mask_state_writes(sc, old, pages).items():
                new_cache[k] = _set_rows(cache[k], rows, layer)
        with jax.named_scope("mixer"):
            x = x + y.astype(x.dtype)
    if spec.cross_attn:                    # read-only: never written back
        with jax.named_scope("mixer"):
            xn = layers.NORM_APPLY[cfg.norm](p["cross_norm"], x)
            q = jnp.einsum("bsd,dhk->bshk", xn,
                           p["cross"]["wq"].astype(cfg.dtype))
            ck, cv = _rows(cache["ck"], layer), _rows(cache["cv"], layer)
            o = attn_lib.decode_attention(q, ck, cv, ck.shape[1])
            x = x + jnp.einsum("bshk,hkd->bsd", o,
                               p["cross"]["wo"].astype(cfg.dtype))
    x = _ffn(p, x, spec, cfg, weights_stationary=cfg.moe_serve_stationary)
    return x, new_cache


def decode_step(params, cache, tokens: Array, index: Array,
                cfg: ModelConfig, *,
                pages: Optional[Array] = None) -> Tuple[Array, list]:
    """One decode step. tokens: [B, 1] -> (logits [B, 1, V], new cache).

    ``index`` is the 0-based position of the incoming token: a scalar when
    the whole batch decodes in lockstep (classic static batching), or a [B]
    vector when every row sits at its own offset (the continuous-batching
    engine's fused multi-slot tick — see repro.serve.engine).

    ``pages`` ([B, P] int32 page tables, paged engine only) makes every
    full-attention layer read/write the shared page pool instead of
    per-slot rows; the cache pytree must then come from
    ``init_paged_cache``. Inactive slots point every table entry at the
    garbage page so their fused-tick writes are harmless."""
    index = jnp.asarray(index)
    x = layers.embed_lookup(params["embed"], tokens).astype(cfg.dtype)
    if cfg.family == "encdec":
        if index.ndim:
            pe = jnp.take(params["dec_pos"], index, axis=0)[:, None]
        else:
            pe = jax.lax.dynamic_slice_in_dim(
                params["dec_pos"], index, 1, axis=0)[None]
        x = x + pe.astype(cfg.dtype)
    x, new_caches = _run_layers(
        params, cache, x, cfg,
        lambda p, c, xx, sp, layer: _decode_layer(p, c, xx, sp, cfg, index,
                                                  pages, layer))
    return _unembed(params, x, cfg), new_caches


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def _prefill_layer(p, cache, x, spec: LayerSpec, cfg: ModelConfig,
                   positions, enc_out=None):
    new_cache = dict(cache)
    if spec.mixer in ("attn", "swa", "local"):
        xn = layers.NORM_APPLY[cfg.norm](p["mixer_norm"], x)
        q, k, v = _qkv(p, xn, cfg)
        if cfg.rope_theta:
            q = layers.apply_rope(q, positions, cfg.rope_theta)
            k = layers.apply_rope(k, positions, cfg.rope_theta)
        t_cache = cache["k"].shape[1]
        if spec.mixer in ("swa", "local"):
            win = cfg.window if spec.mixer == "swa" else cfg.local_window
            o = attn_lib.windowed_attention(q, k, v, window=win)
            s = k.shape[1]
            if s <= t_cache:        # prompt fits: slots i == position i
                pad = t_cache - s
                new_cache["k"] = jnp.pad(
                    k, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(cfg.dtype)
                new_cache["v"] = jnp.pad(
                    v, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(cfg.dtype)
            else:                   # ring-order the last t_cache tokens
                tail_k, tail_v = k[:, -t_cache:], v[:, -t_cache:]
                slots = (jnp.arange(s - t_cache, s)) % t_cache
                order = jnp.argsort(slots)
                new_cache["k"] = tail_k[:, order].astype(cfg.dtype)
                new_cache["v"] = tail_v[:, order].astype(cfg.dtype)
        else:
            o = attn_lib.chunked_attention(q, k, v, causal=True,
                                           kv_chunk=cfg.attn_kv_chunk)
            pad = t_cache - k.shape[1]
            new_cache["k"] = jnp.pad(
                k, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(cfg.dtype)
            new_cache["v"] = jnp.pad(
                v, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(cfg.dtype)
        x = x + jnp.einsum("bshk,hkd->bsd", o,
                           p["attn"]["wo"].astype(cfg.dtype))
    elif spec.mixer == "ssd":
        xn = layers.NORM_APPLY[cfg.norm](p["mixer_norm"], x)
        y, sc = _ssd_prefill(p["ssd"], xn, cfg)
        new_cache.update(sc)
        x = x + y.astype(x.dtype)
    elif spec.mixer == "rglru":
        xn = layers.NORM_APPLY[cfg.norm](p["mixer_norm"], x)
        y, rc = _rglru_prefill(p["rglru"], xn, cfg)
        new_cache.update(rc)
        x = x + y.astype(x.dtype)
    if spec.cross_attn and enc_out is not None:
        xn = layers.NORM_APPLY[cfg.norm](p["cross_norm"], x)
        q, ck, cv = _qkv(p, xn, cfg, "cross")
        ck = jnp.einsum("bsd,dhk->bshk", enc_out,
                        p["cross"]["wk"].astype(cfg.dtype))
        cv = jnp.einsum("bsd,dhk->bshk", enc_out,
                        p["cross"]["wv"].astype(cfg.dtype))
        o = attn_lib.chunked_attention(q, ck, cv, causal=False,
                                       kv_chunk=cfg.attn_kv_chunk)
        new_cache["ck"], new_cache["cv"] = ck, cv
        x = x + jnp.einsum("bshk,hkd->bsd", o,
                           p["cross"]["wo"].astype(cfg.dtype))
    return _ffn(p, x, spec, cfg), new_cache


def _ssd_prefill(p, x, cfg: ModelConfig):
    """Like apply_ssd_block but also returns the final recurrent state."""
    scfg = cfg.ssd_cfg
    b, t, _ = x.shape
    di, n, h = scfg.d_inner, scfg.d_state, scfg.n_heads
    zxbcdt = x @ p["in_proj"]
    z, xin, bmat, cmat, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    conv_in = jnp.concatenate([xin, bmat, cmat], axis=-1)
    conv_buf = conv_in[:, -(scfg.conv_width - 1):].astype(cfg.dtype)
    conv_out = jax.nn.silu(ssd_lib._causal_conv(conv_in, p["conv"]))
    xin, bmat, cmat = jnp.split(conv_out, [di, di + n], axis=-1)
    dtp = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    y, state = ssd_lib.ssd_chunked(
        xin.reshape(b, t, h, scfg.head_dim), dtp, a, bmat, cmat,
        p["d_skip"], chunk=scfg.chunk)
    y = y.reshape(b, t, di).astype(x.dtype)
    y = layers.rmsnorm(p["norm"], y * jax.nn.silu(z))
    return y @ p["out_proj"], {"state": state, "conv_buf": conv_buf}


def _rglru_prefill(p, x, cfg: ModelConfig):
    rcfg = cfg.rglru_cfg
    gate = jax.nn.gelu(x @ p["w_gate"])
    main = x @ p["w_main"]
    conv_buf = main[:, -(rcfg.conv_width - 1):].astype(cfg.dtype)
    main = ssd_lib._causal_conv(main, p["conv"])
    h = rglru_lib.rglru_scan(p, main)
    y = (h.astype(x.dtype) * gate) @ p["w_out"]
    return y, {"h": h[:, -1], "conv_buf": conv_buf}


def _ssd_prefill_chunk(p, x, cfg: ModelConfig, row: Dict[str, Array],
                       first: bool):
    """One chunk of SSD prefill for a single slot (batch 1).

    ``row`` holds the slot's carried state: ``state`` [1,H,P,N] (recurrent
    state at the chunk boundary) and ``conv_buf`` [1,cw-1,dc] (the last
    conv_width-1 pre-conv activations of the previous chunk). ``first``
    (static) selects implicit-zero history — that path is op-for-op the
    solo ``_ssd_prefill`` math, and the carried path is exact because chunk
    boundaries are multiples of the SSD scan chunk (``chunk_tokens_for``)
    so ``ssd_chunked`` executes the identical inter-chunk recurrence."""
    scfg = cfg.ssd_cfg
    b, t, _ = x.shape
    di, n, h = scfg.d_inner, scfg.d_state, scfg.n_heads
    cw = scfg.conv_width
    zxbcdt = x @ p["in_proj"]
    z, xin, bmat, cmat, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    conv_in = jnp.concatenate([xin, bmat, cmat], axis=-1)
    if first:
        conv_out = jax.nn.silu(ssd_lib._causal_conv(conv_in, p["conv"]))
        full = jnp.concatenate(
            [jnp.zeros((b, cw - 1, conv_in.shape[-1]), conv_in.dtype),
             conv_in], axis=1)
        init_state = None
    else:
        full = jnp.concatenate(
            [row["conv_buf"].astype(conv_in.dtype), conv_in], axis=1)
        conv_out = jax.nn.silu(
            ssd_lib._causal_conv(full, p["conv"])[:, cw - 1:])
        init_state = row["state"].astype(jnp.float32)
    new_buf = full[:, full.shape[1] - (cw - 1):].astype(cfg.dtype)
    xin, bmat, cmat = jnp.split(conv_out, [di, di + n], axis=-1)
    dtp = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    with jax.named_scope("ssd_scan"):
        y, state = ssd_lib.ssd_chunked(
            xin.reshape(b, t, h, scfg.head_dim), dtp, a, bmat, cmat,
            p["d_skip"], chunk=scfg.chunk, init_state=init_state)
    y = y.reshape(b, t, di).astype(x.dtype)
    y = layers.rmsnorm(p["norm"], y * jax.nn.silu(z))
    return y @ p["out_proj"], {"state": state.astype(row["state"].dtype),
                               "conv_buf": new_buf}


def _chunk_layer(p, cache, x, spec: LayerSpec, cfg: ModelConfig,
                 positions, start, slot, pages_row, first: bool,
                 layer: Optional[Array] = None):
    """One layer of chunked prefill for one slot. x: [1, L, D].

    Full-attention K/V goes through the page pool (``paged_prefill_update``
    writes the chunk, ``paged_gather`` reads every earlier page back for
    the non-first chunks). SSD layers carve the slot's row out of the
    per-slot state arrays, run ``_ssd_prefill_chunk`` and write that row
    back alone — ``slot`` stays a traced scalar so one compiled chunk
    serves all slots. ``layer`` indexes stacked cache leaves inside the
    layer scan (``_run_layers``). Only families ``chunk_tokens_for`` admits
    ever reach here."""
    new_cache = dict(cache)
    if spec.ffn not in ("mlp", "kan", "none"):
        raise NotImplementedError(
            f"chunked prefill does not support ffn={spec.ffn!r}")
    if spec.mixer == "attn":
        with jax.named_scope("mixer"):
            xn = layers.NORM_APPLY[cfg.norm](p["mixer_norm"], x)
            q, k, v = _qkv(p, xn, cfg)
            if cfg.rope_theta:
                q = layers.apply_rope(q, positions, cfg.rope_theta)
                k = layers.apply_rope(k, positions, cfg.rope_theta)
        with jax.named_scope("cache_update"):
            kp, vp = attn_lib.paged_prefill_update(cache["k"], cache["v"], k,
                                                   v, pages_row, start, layer)
        new_cache["k"], new_cache["v"] = kp, vp
        with jax.named_scope("mixer"):
            with jax.named_scope("paged_attention"):
                if first:
                    # start == 0: the chunk is self-contained — same math
                    # as solo.
                    o = attn_lib.chunked_attention(
                        q, k, v, causal=True, kv_chunk=cfg.attn_kv_chunk)
                else:
                    ck = attn_lib.paged_gather(kp, pages_row[None], layer)
                    cv = attn_lib.paged_gather(vp, pages_row[None], layer)
                    o = attn_lib.chunked_attention(
                        q, ck, cv, causal=True, q_offset=start,
                        kv_valid_len=start + x.shape[1],
                        kv_chunk=cfg.attn_kv_chunk)
            x = x + jnp.einsum("bshk,hkd->bsd", o,
                               p["attn"]["wo"].astype(cfg.dtype))
    elif spec.mixer == "ssd":
        with jax.named_scope("mixer"):
            xn = layers.NORM_APPLY[cfg.norm](p["mixer_norm"], x)
            row = {k: _rows(cache[k], layer, slot)
                   for k in ("state", "conv_buf")}
            y, rc = _ssd_prefill_chunk(p["ssd"], xn, cfg, row, first)
        with jax.named_scope("cache_update"):
            for k in ("state", "conv_buf"):
                new_cache[k] = _set_rows(cache[k], rc[k], layer, slot)
        with jax.named_scope("mixer"):
            x = x + y.astype(x.dtype)
    else:
        raise NotImplementedError(
            f"chunked prefill does not support mixer={spec.mixer!r} "
            f"(chunk_tokens_for should have returned None)")
    return _ffn(p, x, spec, cfg), new_cache


def prefill_chunk(params, cfg: ModelConfig, cache, tokens: Array,
                  start: Array, slot: Array, pages_row: Array, *,
                  first: bool, last: bool) -> Tuple[Array, list]:
    """Consume one page-aligned prompt chunk for one slot of the paged
    engine. tokens: [1, L] at logical positions [start, start+L); cache is
    the engine's full ``init_paged_cache`` pytree (pools are shared, SSD
    rows are per-slot — ``slot``/``start`` are traced, so the compiled
    artifact is keyed only on (L, first, last)).

    Returns (token [1] int32, new cache): the greedy next token after the
    prompt when ``last``, else a zero placeholder (non-final chunks never
    unembed — the [L, V] logits tensor is skipped entirely)."""
    start = jnp.asarray(start)
    slot = jnp.asarray(slot)
    x = layers.embed_lookup(params["embed"], tokens).astype(cfg.dtype)
    positions = start + jnp.arange(tokens.shape[1])
    x, new_caches = _run_layers(
        params, cache, x, cfg,
        lambda p, c, xx, sp, layer: _chunk_layer(
            p, c, xx, sp, cfg, positions, start, slot, pages_row, first,
            layer))
    if not last:
        return jnp.zeros((1,), jnp.int32), new_caches
    logits = _unembed(params, x[:, -1:], cfg)
    with jax.named_scope("unembed"):
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    return tok, new_caches


def prefill(params, cfg: ModelConfig, batch: Dict[str, Array],
            max_len: int, last_only: bool = False) -> Tuple[Array, list]:
    """Run the prompt, return (logits, cache at position S). With
    ``last_only`` (production serving) only the final position is unembedded
    — the full [B,S,V] logits tensor never materializes."""
    enc_out = None
    if cfg.family == "encdec":
        enc_out = tfm.encode(params, cfg, batch)
        # decoder side: token embedding + learned positions (no frontend)
        x = layers.embed_lookup(params["embed"], batch["tokens"]
                                ).astype(cfg.dtype)
        x = x + params["dec_pos"][:x.shape[1]].astype(cfg.dtype)[None]
    else:
        x = tfm.embed_inputs(params, cfg, batch)
    positions = jnp.arange(x.shape[1])
    stages = tfm.stages_for(cfg)
    b = x.shape[0]
    enc_len = enc_out.shape[1] if enc_out is not None else 0
    cache = init_cache(cfg, b, max_len, enc_len)
    new_caches = []
    for st_params, st_cache, stage in zip(params["stages"], cache, stages):
        if stage.repeats == 1:
            nc = {}
            for i, sp in enumerate(stage.block):
                x, nc[f"l{i}"] = _prefill_layer(
                    st_params[f"l{i}"], st_cache[f"l{i}"], x, sp, cfg,
                    positions, enc_out)
            new_caches.append(nc)
        else:
            def body(carry, inp, stage=stage):
                xx = carry
                lp, lc = inp
                nc = {}
                for i, sp in enumerate(stage.block):
                    xx, nc[f"l{i}"] = _prefill_layer(
                        lp[f"l{i}"], lc[f"l{i}"], xx, sp, cfg, positions,
                        enc_out)
                return xx, nc
            fn = jax.checkpoint(body) if cfg.remat else body
            x, nc = jax.lax.scan(fn, x, (st_params, st_cache))
            new_caches.append(nc)
    if last_only:
        x = x[:, -1:]
    return _unembed(params, x, cfg), new_caches


def generate(params, cfg: ModelConfig, prompt: Array, n_new: int,
             max_len: Optional[int] = None) -> Array:
    """Greedy generation (functional loop, used by examples/tests).

    Contract (pinned): returns exactly ``n_new`` tokens per request. Token 0
    is the argmax over the prefill logits at the last prompt position, so
    ``n_new=1`` runs zero decode steps and the scan below never executes.

    ``prompt`` is either a rectangular [B, S] array (static batch, lockstep
    decode) or a list/tuple of 1-D token arrays with heterogeneous lengths —
    the dynamic-batch case, which routes through the continuous-batching
    engine (repro.serve.engine) and still returns [len(prompt), n_new].
    """
    if n_new < 1:
        raise ValueError(f"n_new must be >= 1, got {n_new}")
    if isinstance(prompt, (list, tuple)):
        from repro.serve import engine as engine_lib
        return engine_lib.generate_dynamic(params, cfg, prompt, n_new,
                                           max_len=max_len)
    b, s = prompt.shape
    max_len = max_len or (s + n_new)
    logits, cache = prefill(params, cfg, {"tokens": prompt}, max_len)
    tok = jnp.argmax(logits[:, -1:], axis=-1)
    if n_new == 1:
        return tok

    def step(carry, i):
        tok, cache = carry
        logits, cache = decode_step(params, cache, tok, s + i, cfg)
        nxt = jnp.argmax(logits[:, -1:, :], axis=-1)
        return (nxt, cache), nxt

    (_, _), toks = jax.lax.scan(step, (tok, cache), jnp.arange(n_new - 1))
    rest = jnp.swapaxes(toks[..., 0], 0, 1)
    return jnp.concatenate([tok, rest], axis=1)
