"""Attention: GQA/MQA/MHA with memory-efficient chunked softmax.

Three execution paths, all pure JAX (compilable on any backend — required by
the multi-pod dry-run, which lowers on host devices):

* ``chunked_attention`` — full (causal or bidirectional) attention with an
  online-softmax scan over KV chunks: peak memory O(S * ckv) instead of
  O(S^2), the standard XLA-level flash-attention substitute.
* ``windowed_attention`` — sliding-window (Mistral/Mixtral SWA, Griffin local
  attention) via the banded two-chunk trick: with the window W as chunk size,
  a query in chunk i only needs key chunks i-1 and i. O(S*W) compute and
  memory, fully parallel over chunks (no scan).
* ``decode_attention`` — single-token query against a (possibly rolling) KV
  cache.

GQA is expressed by grouping query heads [B, S, Kv, G, hd]; KV heads shard
over 'model' when divisible, otherwise head_dim shards (see dist.sharding).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.dist.sharding import shard

Array = jax.Array
NEG_INF = -1e30


def _split_heads(q: Array, n_kv: int) -> Array:
    """[B, S, Hq, hd] -> [B, S, Kv, G, hd]."""
    b, s, hq, hd = q.shape
    return q.reshape(b, s, n_kv, hq // n_kv, hd)


def chunked_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                      q_offset=0, kv_valid_len: Optional[Array] = None,
                      kv_chunk: int = 512) -> Array:
    """Online-softmax attention, scanning KV chunks.

    q: [B, S, Hq, hd]; k, v: [B, T, Kv, hd]; q position i = q_offset + i.
    kv_valid_len: optional scalar — keys at positions >= valid_len are masked.
    Returns [B, S, Hq, hd].
    """
    b, s, hq, hd = q.shape
    t = k.shape[1]
    n_kv = k.shape[2]
    kv_chunk = min(kv_chunk, t)
    pad = (-t) % kv_chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nkc = (t + pad) // kv_chunk

    qg = _split_heads(q, n_kv).astype(jnp.float32) * (hd ** -0.5)
    q_pos = q_offset + jnp.arange(s)
    kc = jnp.moveaxis(k.reshape(b, nkc, kv_chunk, n_kv, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nkc, kv_chunk, n_kv, hd), 1, 0)

    def step(carry, inp):
        m, l, acc = carry
        ci, kb, vb = inp
        k_pos = ci * kv_chunk + jnp.arange(kv_chunk)
        scores = jnp.einsum("bskgd,btkd->bskgt", qg, kb.astype(jnp.float32))
        mask = jnp.ones((s, kv_chunk), dtype=bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        mask &= (k_pos < t)[None, :]
        if kv_valid_len is not None:
            mask &= (k_pos < kv_valid_len)[None, :]
        scores = jnp.where(mask[None, :, None, None, :], scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = (acc * corr[..., None]
                   + jnp.einsum("bskgt,btkd->bskgd", p,
                                vb.astype(jnp.float32)))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, s, n_kv, hq // n_kv), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros_like(m0)
    a0 = jnp.zeros((b, s, n_kv, hq // n_kv, hd), dtype=jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (jnp.arange(nkc), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, s, hq, hd).astype(q.dtype)


def windowed_attention(q: Array, k: Array, v: Array, *, window: int,
                       q_offset=0) -> Array:
    """Banded causal attention: position i attends to (i-window, i].

    Pads S to a multiple of ``window``; each query chunk attends to its own
    and the previous key chunk — O(S*W), parallel over chunks.
    """
    b, s, hq, hd = q.shape
    n_kv = k.shape[2]
    w = window
    pad = (-s) % w
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s + pad
    nc = sp // w

    qg = _split_heads(q, n_kv).astype(jnp.float32) * (hd ** -0.5)
    qg = qg.reshape(b, nc, w, n_kv, hq // n_kv, hd)

    def chunks(x):                                    # [B, Sp, Kv, hd]
        xc = x.reshape(b, nc, w, n_kv, hd)
        prev = jnp.pad(xc, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
        return jnp.concatenate([prev, xc], axis=2)    # [B, nc, 2w, Kv, hd]

    kc, vc = chunks(k.astype(jnp.float32)), chunks(v.astype(jnp.float32))
    scores = jnp.einsum("bcqkgd,bctkd->bcqkgt", qg, kc)

    q_idx = jnp.arange(w)[:, None]                    # position within chunk
    t_idx = jnp.arange(2 * w)[None, :] - w            # relative to chunk start
    rel = q_idx - t_idx                               # q_pos - k_pos
    mask = (rel >= 0) & (rel < w)                     # causal, banded
    c_idx = jnp.arange(nc)
    valid_abs = (c_idx[:, None, None] * w + t_idx[None]) >= 0
    full_mask = mask[None] & valid_abs                # [nc, w, 2w]
    scores = jnp.where(full_mask[None, :, :, None, None, :], scores, NEG_INF)

    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bcqkgt,bctkd->bcqkgd", p, vc)
    out = out.reshape(b, sp, hq, hd)[:, :s]
    return out.astype(q.dtype)


def decode_attention(q: Array, k_cache: Array, v_cache: Array,
                     cache_index: Array, *, rolling: bool = False) -> Array:
    """One-token decode. q: [B, 1, Hq, hd]; caches: [B, T, Kv, hd].

    ``cache_index`` = number of valid tokens already in the cache INCLUDING
    the current one — a scalar (whole batch at one position) or a [B] vector
    (continuous batching: every slot carries its own token count). For
    rolling (windowed) caches, every slot < min(index, T) is valid — softmax
    is permutation-invariant over KV so slot order does not matter.
    """
    b, _, hq, hd = q.shape
    t, n_kv = k_cache.shape[1], k_cache.shape[2]
    qg = _split_heads(q, n_kv).astype(jnp.float32) * (hd ** -0.5)
    scores = jnp.einsum("bskgd,btkd->bskgt", qg,
                        k_cache.astype(jnp.float32))
    pos = jnp.arange(t)
    limit = jnp.asarray(cache_index)
    if rolling:
        limit = jnp.minimum(limit, t)
    limit = jnp.broadcast_to(limit, (b,))
    mask = pos[None, :] < limit[:, None]               # [B, T]
    scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bskgt,btkd->bskgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, hq, hd).astype(q.dtype)


def _row_major(x: Array) -> Array:
    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def paged_gather(pool: Array, pages: Array,
                 layer: Optional[Array] = None) -> Array:
    """Materialize per-slot K or V views from a page pool.

    pool: [n_pages, ps, Kv, hd] (one layer's pages, shared by all slots);
    pages: [B, P] page-table rows — entry j is the physical page holding
    logical tokens [j*ps, (j+1)*ps). Returns [B, P*ps, Kv, hd] where the
    gathered token axis IS logical position order, so the result drops into
    ``decode_attention``/``chunked_attention`` exactly like a monolithic
    cache row (garbage-page entries land past the valid length and are
    masked by ``cache_index``/``kv_valid_len``).

    With ``layer`` (a traced scalar) ``pool`` is a scanned stage's stacked
    pools [repeats, n_pages, ps, Kv, hd] and the pages are gathered at
    ``[layer, page]`` directly: no layer's whole pool is sliced out."""
    b, p = pages.shape
    ps, n_kv, hd = pool.shape[-3:]
    got = pool[pages] if layer is None else pool[layer, pages]
    return got.reshape(b, p * ps, n_kv, hd)


def paged_cache_update(k_pool: Array, v_pool: Array, k_new: Array,
                       v_new: Array, pages: Array, index: Array,
                       layer: Optional[Array] = None
                       ) -> Tuple[Array, Array]:
    """Scatter one decode token's K/V through the page tables.

    k_new/v_new: [B, 1, Kv, hd]; pages: [B, P]; index: [B] (0-based logical
    position of the incoming token). Slot b writes page
    ``pages[b, index[b] // ps]`` at offset ``index[b] % ps``. Live slots
    always target distinct pages (the engine gives every slot private write
    pages — copy-on-write forks any shared page first); inactive slots all
    target the garbage page, where colliding writes are never read.

    With ``layer`` the pools are a scanned stage's stacked pools [repeats,
    n_pages, ps, Kv, hd] and slot b writes ``[layer, page, offset]``: only
    the new tokens are written, so a carried pool updates in place."""
    ps = k_pool.shape[-3]
    index = jnp.asarray(index)
    phys = jnp.take_along_axis(pages, (index // ps)[:, None], axis=1)[:, 0]
    at = (phys, index % ps) if layer is None else (layer, phys, index % ps)
    k_pool = k_pool.at[at].set(k_new[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[at].set(v_new[:, 0].astype(v_pool.dtype))
    return k_pool, v_pool


def paged_prefill_update(k_pool: Array, v_pool: Array, k_new: Array,
                         v_new: Array, pages_row: Array, start: Array,
                         layer: Optional[Array] = None
                         ) -> Tuple[Array, Array]:
    """Scatter one prefill chunk's K/V into a single slot's pages.

    k_new/v_new: [1, L, Kv, hd] with the chunk starting at logical position
    ``start`` (a page-aligned traced scalar); pages_row: [P] — this slot's
    page table. The chunk is zero-padded up to whole pages (the tail of a
    partial final page is masked garbage) and written page-at-a-time into
    ``pages_row[start//ps : start//ps + ceil(L/ps)]`` — all pages the slot
    itself allocated, never a shared prefix page.

    With ``layer`` the pools are a scanned stage's stacked pools [repeats,
    n_pages, ps, Kv, hd] and the pages are written at ``[layer, page]``."""
    ps = k_pool.shape[-3]
    _, l, n_kv, hd = k_new.shape
    n_cp = -(-l // ps)
    pad = n_cp * ps - l
    if pad:
        k_new = jnp.pad(k_new, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_new = jnp.pad(v_new, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kw = k_new[0].reshape(n_cp, ps, n_kv, hd).astype(k_pool.dtype)
    vw = v_new[0].reshape(n_cp, ps, n_kv, hd).astype(v_pool.dtype)
    pslice = jax.lax.dynamic_slice(pages_row, (start // ps,), (n_cp,))
    if layer is None:
        return k_pool.at[pslice].set(kw), v_pool.at[pslice].set(vw)
    # The chunk's K/V leaves its projection in a layout of its own; pinned
    # to the pools' layout as program arguments, the chunk is transposed,
    # not the whole stacked pool on every layer (as XLA's TPU layout
    # assignment otherwise does where a later chunk gathers from it).
    return (_row_major(k_pool.at[layer, pslice].set(kw)),
            _row_major(v_pool.at[layer, pslice].set(vw)))


def cache_update(k_cache: Array, v_cache: Array, k_new: Array, v_new: Array,
                 index: Array, *, rolling: bool = False
                 ) -> Tuple[Array, Array]:
    """Insert one token's K/V at ``index`` (mod T for rolling caches).

    ``index`` is a scalar (whole batch writes one position) or a [B] vector
    (per-row positions — the continuous-batching engine's decode tick, where
    each slot sits at its own sequence offset)."""
    t = k_cache.shape[1]
    index = jnp.asarray(index)
    slot = jnp.mod(index, t) if rolling else index
    k_new = k_new.astype(k_cache.dtype)
    v_new = v_new.astype(v_cache.dtype)
    if slot.ndim:                       # per-row scatter, vmapped over batch
        upd = jax.vmap(lambda c, n, s: jax.lax.dynamic_update_slice_in_dim(
            c, n, s, axis=0))
        return upd(k_cache, k_new, slot), upd(v_cache, v_new, slot)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new, slot,
                                                  axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new, slot,
                                                  axis=1)
    return k_cache, v_cache
