"""Paged KV cache: allocator invariants, prefix-hash soundness, chunked
prefill exactness, prefix sharing end to end, and no head-of-line blocking.

Three layers of evidence, mirroring the design:

* Host bookkeeping (no jax): a randomized request trace against
  :class:`repro.serve.paging.PagedAllocator` cross-checked by an
  independent model — no page leaks, no non-prefix aliasing (two slots
  share a physical page only when their token prefixes agree through that
  page), and copy-on-write forks never touch the surviving shared page.
* Engine integration: chunked prefill with small pages is argmax-exact
  against the solo scalar-index reference for both the attn and ssd
  families; N requests with a common prompt prefix pin ONE set of prefix
  pages (refcount == N) and the stats record the hit rate.
* Scheduling: a long prompt admitted first must not stall short requests
  — chunked prefill interleaves with the fused decode tick, asserted from
  the recorded obs trace (ticks that run both a prefill AND a decode span).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import transformer as tfm
from repro.serve import decode as dec
from repro.serve.engine import Engine
from repro.serve.paging import GARBAGE_PAGE, PagedAllocator, page_hashes
from repro.serve.scheduler import Request


def _model(arch_id, seed=0):
    m = get_arch(arch_id, smoke=True).model
    params = tfm.init_model(jax.random.PRNGKey(seed), m)
    return m, params


def _solo_greedy(params, m, prompt, n_new, max_len):
    """Reference: the request alone through the scalar-index decode path."""
    logits, cache = dec.prefill(params, m,
                                {"tokens": jnp.asarray(prompt)[None]},
                                max_len=max_len, last_only=True)
    tok = int(jnp.argmax(logits[0, -1]))
    out = [tok]
    i = len(prompt)
    for _ in range(n_new - 1):
        l, cache = dec.decode_step(params, cache, jnp.asarray([[tok]]), i, m)
        tok = int(jnp.argmax(l[0, -1]))
        out.append(tok)
        i += 1
    return out


# ---------------------------------------------------------------------------
# page_hashes: the chaining property prefix sharing relies on
# ---------------------------------------------------------------------------

def test_page_hashes_chain_property():
    ps = 4
    a = np.array([1, 2, 3, 4, 5, 6, 7, 8, 9, 10])
    b = np.array([1, 2, 3, 4, 5, 6, 99, 8, 9, 10, 11, 12])
    ha, hb = page_hashes(a, ps), page_hashes(b, ps)
    # only FULL pages are hashed
    assert len(ha) == len(a) // ps and len(hb) == len(b) // ps
    # identical prefix through page 0 -> equal digest; divergence inside
    # page 1 -> different digest there AND for every later page (the chain
    # commits to the whole prefix, not just the page body)
    assert ha[0] == hb[0]
    assert ha[1] != hb[1]
    c = np.array([0, 2, 3, 4, 5, 6, 7, 8])   # differs in page 0
    hc = page_hashes(c, ps)
    assert hc[0] != ha[0] and hc[1] != ha[1]
    # equal tokens under a different salt must not collide
    assert page_hashes(a, ps, salt=b"x") != ha


def test_page_hashes_same_prefix_same_digests():
    rng = np.random.default_rng(0)
    ps = 3
    prefix = rng.integers(0, 50, size=9)
    t1 = np.concatenate([prefix, rng.integers(0, 50, size=7)])
    t2 = np.concatenate([prefix, rng.integers(0, 50, size=4)])
    h1, h2 = page_hashes(t1, ps), page_hashes(t2, ps)
    assert h1[:3] == h2[:3]


# ---------------------------------------------------------------------------
# PagedAllocator: randomized trace vs an independent model
# ---------------------------------------------------------------------------

def test_allocator_randomized_trace_no_leak_no_aliasing():
    """Random admit/evict/fork trace. The model tracks, per physical page,
    the canonical token prefix it holds; every shared mapping must agree
    with it (no non-prefix aliasing), every fork must leave the shared
    page's refcount and content claim intact, and full eviction must
    return the pool to empty (no leak)."""
    rng = np.random.default_rng(42)
    ps, n_pages = 2, 24
    alloc = PagedAllocator(n_pages, ps)
    # model state
    live = {}          # rid -> {"pages": [pid], "toks": np.ndarray}
    page_prefix = {}   # pid -> token prefix (np.ndarray) it was written with
    next_rid = 0

    def admit():
        nonlocal next_rid
        # small alphabet + shared stems => frequent prefix collisions
        n_tok = int(rng.integers(2, 13))
        toks = rng.integers(0, 3, size=n_tok)
        digests = page_hashes(toks, ps)
        matchable = digests[:max(0, (n_tok - 1) // ps)]
        matched = alloc.match_prefix(matchable)
        n_prompt_pages = -(-n_tok // ps)
        need = n_prompt_pages - len(matched)
        if not alloc.reserve(need):
            for pid in matched:           # rollback, like the engine
                alloc.release(pid)
            return
        pages = list(matched)
        for _ in range(need):
            pages.append(alloc.alloc(reserved=True))
        # "write" the private pages, then register their hashes
        for i, pid in enumerate(pages):
            pfx = toks[:(i + 1) * ps]
            if i < len(matched):
                # sharing is only sound if the physical page already holds
                # exactly this prefix
                assert np.array_equal(page_prefix[pid], pfx), \
                    f"non-prefix aliasing on page {pid}"
            else:
                page_prefix[pid] = pfx
                if (i + 1) * ps <= n_tok:
                    alloc.register_hash(pid, digests[i])
        live[next_rid] = {"pages": pages, "toks": toks}
        next_rid += 1

    def evict():
        rid = int(rng.choice(list(live)))
        for pid in live[rid]["pages"]:
            alloc.release(pid)
        del live[rid]

    def fork():
        shared = [pid for pid in set(p for r in live.values()
                                     for p in r["pages"])
                  if alloc.refcount[pid] > 1]
        if not shared or alloc.available() <= 0:
            return
        pid = int(rng.choice(shared))
        owners = [rid for rid, r in live.items() if pid in r["pages"]]
        rid = owners[0]
        before = alloc.refcount[pid]
        new = alloc.fork(pid)
        # CoW: the writer got a fresh private page; the shared page keeps
        # its content claim and the other owners' references
        assert new != pid and alloc.refcount[new] == 1
        assert alloc.refcount[pid] == before - 1
        i = live[rid]["pages"].index(pid)
        live[rid]["pages"][i] = new
        page_prefix[new] = np.array(page_prefix[pid], copy=True)

    for _ in range(400):
        op = rng.random()
        if op < 0.5 or not live:
            admit()
        elif op < 0.85:
            evict()
        else:
            fork()
        alloc.check()
        # every live reference is counted exactly once
        counts = {}
        for r in live.values():
            for pid in r["pages"]:
                counts[pid] = counts.get(pid, 0) + 1
        for pid, n in counts.items():
            assert alloc.refcount[pid] == n, (pid, n, alloc.refcount[pid])
        assert alloc.in_use == len(counts)

    while live:
        evict()
    alloc.check()
    assert alloc.in_use == 0, "pages leaked after full eviction"


def test_allocator_reservation_gate_and_garbage_page():
    alloc = PagedAllocator(5, 4)           # 4 allocatable pages
    assert alloc.available() == 4
    assert alloc.reserve(3)
    assert not alloc.reserve(2)            # only 1 unreserved left
    a = alloc.alloc(reserved=True)
    assert a != GARBAGE_PAGE
    b = alloc.alloc()                      # the single unreserved page
    with pytest.raises(RuntimeError):
        alloc.alloc()                      # rest is spoken for
    alloc.release(a), alloc.release(b)
    alloc.unreserve(2)
    alloc.check()
    with pytest.raises(ValueError):
        alloc.release(GARBAGE_PAGE)


def test_allocator_cached_free_revival():
    """A released page keeps its hash until reallocated, so an identical
    prompt arriving later revives it instead of recomputing."""
    alloc = PagedAllocator(6, 2)
    toks = np.array([7, 8, 9, 10])
    d = page_hashes(toks, 2)
    p0, p1 = alloc.alloc(), alloc.alloc()
    alloc.register_hash(p0, d[0])
    alloc.register_hash(p1, d[1])
    alloc.release(p0), alloc.release(p1)
    assert alloc.in_use == 0
    revived = alloc.match_prefix(d)
    assert revived == [p0, p1]             # same physical pages, revived
    assert alloc.refcount[p0] == 1 and alloc.refcount[p1] == 1
    alloc.check()


# ---------------------------------------------------------------------------
# engine: chunked prefill is argmax-exact vs the solo reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch_id,page_size",
                         [("mistral_nemo_12b", 4), ("mistral_nemo_12b", 8),
                          ("mamba2_1p3b", 4)])
def test_multi_chunk_prefill_invariance(arch_id, page_size):
    """Prompts spanning several pages, small page size, slot contention:
    the paged + chunked engine must reproduce the solo scalar-index run
    token for token (the same batching-invariance contract as
    tests/test_engine.py, now crossing page boundaries mid-prompt)."""
    m, params = _model(arch_id)
    max_len = 24
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, tokens=rng.integers(1, m.vocab, size=s),
                    max_new=4)
            for i, s in enumerate([13, 9, 17, 6])]
    eng = Engine(params, m, n_slots=2, max_len=max_len, page_size=page_size)
    assert eng.chunk_tokens is not None    # both archs take the chunked path
    comps = eng.run(reqs)
    assert len(comps) == len(reqs)
    for c in comps:
        r = reqs[c.rid]
        ref = _solo_greedy(params, m, np.asarray(r.tokens), r.max_new,
                           max_len)
        assert list(c.tokens) == ref, (c.rid, list(c.tokens), ref)
    assert eng.stats.prefill_chunks > len(reqs)   # genuinely multi-chunk
    assert eng.alloc.in_use == 0                  # all pages returned
    eng.alloc.check()


# ---------------------------------------------------------------------------
# engine: prefix sharing pins one set of pages across N slots
# ---------------------------------------------------------------------------

def test_prefix_sharing_refcount_equals_n():
    """N staggered requests with an identical prompt: once all are resident
    the shared prefix pages must be the SAME physical pages in every slot
    with refcount == N, and the stats/report must show the hits."""
    m, params = _model("mistral_nemo_12b")
    ps, n = 4, 3
    max_len = 32
    prompt = (np.arange(1, 14) * 3) % m.vocab   # 13 tokens -> 3 full pages
    shareable = (len(prompt) - 1) // ps         # matchable page count
    # stagger wide enough that request 0 finishes prefill (registering its
    # page hashes) before request 1 is admitted
    reqs = [Request(rid=i, tokens=prompt.copy(), max_new=12)
            for i in range(n)]
    eng = Engine(params, m, n_slots=n, max_len=max_len, page_size=ps)
    assert eng.share_ok
    for i, r in enumerate(reqs):
        eng.submit(r)
        for _ in range(4):                      # 4 ticks between arrivals
            eng.step()
    # all three are now resident and decoding: inspect the page tables
    assert eng.active.sum() == n
    tables = eng.slot_pages[:, :shareable]
    for s in range(1, n):
        assert np.array_equal(tables[s], tables[0]), \
            "later slots did not reuse the first slot's prefix pages"
    for pid in tables[0]:
        assert eng.alloc.refcount[pid] == n, \
            f"shared page {pid} refcount {eng.alloc.refcount[pid]} != {n}"
    assert eng.stats.prefix_hit_pages == (n - 1) * shareable
    assert eng.stats.report()["prefix_hit_rate"] > 0
    # drain; identical prompts must produce identical (solo-exact) tokens
    comps = eng.run([])
    ref = _solo_greedy(params, m, prompt, 12, max_len)
    assert all(list(c.tokens) == ref for c in comps)
    assert eng.alloc.in_use == 0
    eng.alloc.check()


def test_ssd_arch_never_claims_prefix_sharing():
    m, params = _model("mamba2_1p3b")
    eng = Engine(params, m, n_slots=2, max_len=16, page_size=4)
    assert not eng.share_ok   # recurrent row state is not page-addressable


# ---------------------------------------------------------------------------
# engine: chunked prefill does not head-of-line block decode
# ---------------------------------------------------------------------------

def test_long_prefill_does_not_stall_short_requests(tmp_path):
    """A long prompt is admitted first; short requests arriving behind it
    must finish BEFORE the long request emits its first token, and the
    recorded trace must show ticks that ran both a prefill chunk and a
    decode step (interleaving, not head-of-line blocking)."""
    from repro.obs import EngineRecorder

    m, params = _model("mistral_nemo_12b")
    ps = 4
    rng = np.random.default_rng(7)
    long_req = Request(rid="long", tokens=rng.integers(1, m.vocab, size=28),
                       max_new=2)
    shorts = [Request(rid=f"s{i}", tokens=rng.integers(1, m.vocab, size=4),
                      max_new=3) for i in range(2)]
    rec = EngineRecorder()
    eng = Engine(params, m, n_slots=3, max_len=36, page_size=ps,
                 recorder=rec)
    eng.submit(long_req)
    eng.step()                      # long starts chunked prefill (7 chunks)
    for r in shorts:
        eng.submit(r)
    comps = {c.rid: c for c in eng.run([])}

    long_first_token_tick = (comps["long"].finished_tick
                             - (long_req.max_new - 1))
    for i in range(2):
        assert comps[f"s{i}"].finished_tick < long_first_token_tick, \
            "short request stalled behind the long prompt's prefill"

    # trace-level proof: reconstruct ticks from the X spans (each tick
    # opens with an 'admit' span) and find prefill+decode in the SAME tick
    path = rec.export_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    xs.sort(key=lambda e: e["ts"])
    ticks, cur = [], set()
    for e in xs:
        if e["name"] == "admit":
            ticks.append(cur)
            cur = set()
        cur.add(e["name"])
    ticks.append(cur)
    both = [t for t in ticks if "prefill" in t and "decode" in t]
    assert both, "no tick interleaved a prefill chunk with a decode step"
    n_prefill = sum(1 for t in ticks if "prefill" in t)
    assert n_prefill >= 7, "long prompt was not chunked across ticks"


# ---------------------------------------------------------------------------
# rglru: segment scan with carried state matches the full scan
# ---------------------------------------------------------------------------

def test_rglru_scan_carried_state_matches_full_scan():
    """rglru_scan(h0=...) is the primitive a future rglru chunked-prefill
    path needs: scanning a sequence in two segments, carrying the hidden
    state, must match the one-shot scan."""
    from repro.models import rglru as rg

    cfg = rg.RGLRUConfig(d_model=8, d_rnn=6, dtype=jnp.float32)
    params = rg.init_rglru_block(jax.random.PRNGKey(0), cfg)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 10, cfg.d_rnn))
    full = rg.rglru_scan(params, u)
    h1 = rg.rglru_scan(params, u[:, :6])
    h2 = rg.rglru_scan(params, u[:, 6:], h0=h1[:, -1])
    np.testing.assert_allclose(np.asarray(jnp.concatenate([h1, h2], axis=1)),
                               np.asarray(full), rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# the layer scan updates the stacked cache in place, with the same numbers
# ---------------------------------------------------------------------------

def _unrolled_layers(params, cache, x, cfg, layer_fn):
    """``dec._run_layers`` without a scan: the stacked layers run one by one
    in Python, each on its own slice of the stacked params and cache
    (``layer`` None), and their caches are stacked again."""
    out = []
    for st_p, st_c, stage in zip(params["stages"], cache,
                                 tfm.stages_for(cfg)):
        reps = range(stage.repeats) if stage.repeats > 1 else [None]
        per = []
        for r in reps:
            p_r, c_r = (st_p, st_c) if r is None else jax.tree.map(
                lambda a, r=r: a[r], (st_p, st_c))
            nc = {}
            for i, sp in enumerate(stage.block):
                x, nc[f"l{i}"] = layer_fn(p_r[f"l{i}"], c_r[f"l{i}"], x, sp,
                                          None)
            per.append(nc)
        out.append(per[0] if stage.repeats == 1
                   else jax.tree.map(lambda *a: jnp.stack(a), *per))
    return x, out


def _random_like(tree, seed):
    leaves, tdef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return tdef.unflatten([jax.random.normal(k, a.shape).astype(a.dtype)
                           for k, a in zip(keys, leaves)])


# slot 1 is idle: its page table is all garbage page, its index 0
_PAGES = np.array([[1, 2, 3, 4, 9, 10], [0] * 6, [5, 6, 7, 8, 11, 12]],
                  np.int32)
_INDEX = np.array([13, 0, 20], np.int32)


@pytest.mark.parametrize("case,arch_id", [
    ("decode_ssd_masked_slot", "mamba2_1p3b"),
    ("decode_paged_garbage_slot", "mistral_nemo_12b"),
    ("decode_unpaged", "mistral_nemo_12b"),
    ("decode_rolling", "mixtral_8x7b"),
    ("decode_cross_read_only", "whisper_base"),
    ("chunk_non_first_ssd", "mamba2_1p3b"),
    ("chunk_non_first_paged", "mistral_nemo_12b")])
def test_layer_scan_matches_unrolled_layers(case, arch_id, monkeypatch):
    """The stacked-layer scan that carries the cache and writes each layer's
    rows in place gives, bit for bit, the logits (or token) and every cache
    leaf of the same layers run one by one on their own slices."""
    m, params = _model(arch_id)
    enc_len = 8 if m.family == "encdec" else 0
    n_slots, max_len, ps = 3, 48, 8
    if case == "decode_unpaged":
        cache = dec.init_cache(m, n_slots, max_len)
    else:
        cache = dec.init_paged_cache(m, n_slots, max_len, page_size=ps,
                                     n_pages=13, enc_len=enc_len)
    cache = _random_like(cache, 1)
    assert any(st.repeats > 1 for st in tfm.stages_for(m))
    if case.startswith("decode"):
        pages = None if case == "decode_unpaged" else jnp.asarray(_PAGES)
        toks = jnp.asarray([[5], [7], [11]], jnp.int32)
        if case == "decode_rolling":      # past the window: the ring wraps
            assert m.window < 20
            idx = jnp.asarray([m.window + 3, 0, m.window - 2], jnp.int32)
        else:
            idx = jnp.asarray(_INDEX)

        def run():
            return jax.jit(lambda p, c: dec.decode_step(
                p, c, toks, idx, m, pages=pages))(params, cache)
    else:
        step = dec.chunk_tokens_for(m, ps)
        toks = jnp.asarray(np.arange(1, step + 1)[None], jnp.int32)

        def run():
            return jax.jit(lambda p, c: dec.prefill_chunk(
                p, m, c, toks, jnp.int32(step), jnp.int32(2),
                jnp.asarray(_PAGES[2]), first=False, last=True))(params, cache)
    got = run()
    monkeypatch.setattr(dec, "_run_layers", _unrolled_layers)
    want = run()
    flat_got, tdef = jax.tree.flatten(got)
    flat_want, tdef_want = jax.tree.flatten(want)
    assert tdef == tdef_want
    for g, w in zip(flat_got, flat_want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_paged_helpers_layer_index_matches_one_layer():
    """``paged_cache_update``, ``paged_prefill_update`` and ``paged_gather``
    with a ``layer`` index on stacked pools act as the unindexed forms on
    that layer's pool, and leave every other layer as it was."""
    from repro.models import attention as attn_lib

    reps, n_pages, ps, kv, hd = 3, 9, 4, 2, 8
    k0, v0 = _random_like(
        (jnp.zeros((reps, n_pages, ps, kv, hd)),) * 2, 2)
    pages = jnp.asarray([[1, 2, 3], [0, 0, 0], [4, 5, 6]], jnp.int32)
    index = jnp.asarray([5, 0, 9], jnp.int32)
    new = _random_like((jnp.zeros((3, 1, kv, hd)),) * 2, 3)
    chunk = _random_like((jnp.zeros((1, 6, kv, hd)),) * 2, 4)
    layer = jnp.int32(1)

    def one_layer(stacked, per_layer):
        return stacked.at[1].set(per_layer)

    kp, vp = attn_lib.paged_cache_update(k0, v0, *new, pages, index, layer)
    k1, v1 = attn_lib.paged_cache_update(k0[1], v0[1], *new, pages, index)
    np.testing.assert_array_equal(kp, one_layer(k0, k1))
    np.testing.assert_array_equal(vp, one_layer(v0, v1))

    kp, vp = attn_lib.paged_prefill_update(k0, v0, *chunk, pages[2],
                                           jnp.int32(4), layer)
    k1, v1 = attn_lib.paged_prefill_update(k0[1], v0[1], *chunk, pages[2],
                                           jnp.int32(4))
    np.testing.assert_array_equal(kp, one_layer(k0, k1))
    np.testing.assert_array_equal(vp, one_layer(v0, v1))

    np.testing.assert_array_equal(attn_lib.paged_gather(k0, pages, layer),
                                  attn_lib.paged_gather(k0[1], pages))
