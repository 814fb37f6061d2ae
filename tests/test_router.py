"""Property-test harness for the multi-replica serving router.

Routing and multi-queue scheduling are exactly the logic unit tests
under-cover, so the router's invariants are pinned the way
tests/test_paged_cache.py pinned the allocator: seeded randomized traces
(hundreds of scheduling operations each) driven through a host-only
``FakeEngine`` that duck-types the Engine seam over a **real**
``PagedAllocator`` — page accounting, prefix matching and reservation
rollback are the production code paths, only the device math is replaced
by a deterministic token function. The pinned properties:

(a) **completion equivalence** — the multiset of Completions from an
    N-replica fleet equals a single-engine run token-for-token: no request
    lost, duplicated, or re-tokenized, regardless of placement;
(b) **global FIFO-within-priority** — every dispatch in
    ``RouterStats.dispatch_log`` is the eligible head of an independent
    reference queue model (higher priority first, submission order within
    a class, arrival gating respected);
(c) **drain requeues everything** — mid-trace drains/removes preempt every
    in-flight request, requeue all of them, never dispatch to a drained
    replica again, and the trace still completes with correct tokens;
(d) **affinity is placement-only** — prefix-affinity routing concentrates
    shared-prefix requests but never changes a single emitted token.

The file also carries this PR's satellite regression tests: AdmissionQueue
boundary paths (empty / all-future / pop-at-exact-arrival), EngineStats
empty-report hardening, per-replica recorder labels + balanced trace
spans across preempt/requeue, and two real-Engine (jax) smoke versions of
(a) and (c).
"""
import hashlib
import json

import numpy as np
import pytest

from repro.dist.fault import PreemptionHandler
from repro.obs.recorder import EngineRecorder, NullRecorder
from repro.serve.paging import GARBAGE_PAGE, PagedAllocator, page_hashes
from repro.serve.router import Router, RouterStats
from repro.serve.scheduler import (EMPTY_PERCENTILES, AdmissionQueue,
                                   EngineStats, Request)

VOCAB = 97
CHUNK = 4          # FakeEngine prefill tokens consumed per tick
FAKE_CFG = "fake-cfg-v1"   # shared geometry sentinel across a fleet


def expected_token(prompt, k: int) -> int:
    """The k-th token the fake model emits for ``prompt`` — a pure function
    of (prompt, k), so any placement/requeue schedule must reproduce it."""
    h = hashlib.blake2b(np.asarray(prompt, np.int64).tobytes()
                        + int(k).to_bytes(4, "little"), digest_size=4)
    return int.from_bytes(h.digest(), "little") % VOCAB


class FakeEngine:
    """Host-only replica implementing the Engine seam the Router dispatches
    through (``validate_request`` / ``try_admit`` / ``step`` / ``preempt``
    / ``drain_queued`` + the host state arrays). Paging is the REAL
    ``PagedAllocator`` — admission reserves the worst case, prefix pages
    are matched/registered/released exactly like the production engine —
    while "prefill" consumes CHUNK prompt tokens per tick and "decode"
    emits ``expected_token`` instead of running a model."""

    def __init__(self, *, n_slots, max_len, page_size, n_pages=None,
                 recorder=None):
        self.cfg = FAKE_CFG
        self.n_slots = n_slots
        self.max_len = max_len
        self.page_size = page_size
        self.n_slot_pages = -(-max_len // page_size)
        self.n_pages = (n_pages if n_pages is not None
                        else n_slots * self.n_slot_pages + 1)
        self.alloc = PagedAllocator(self.n_pages, page_size)
        self.share_ok = True
        self.enc_len = 0
        self.queue = AdmissionQueue()
        self.obs = recorder if recorder is not None else NullRecorder()
        self.tick_no = 0
        self.stats = EngineStats(n_slots=n_slots, page_size=page_size,
                                 n_pages=self.n_pages)
        self.active = np.zeros(n_slots, dtype=bool)
        self.prefilling = np.zeros(n_slots, dtype=bool)
        self.index = np.zeros(n_slots, dtype=np.int64)
        self.remaining = np.zeros(n_slots, dtype=np.int64)
        self.slot_req = [None] * n_slots
        self.slot_tokens = [[] for _ in range(n_slots)]
        self.slot_admitted = np.zeros(n_slots, dtype=np.int64)
        self.slot_pages = np.full((n_slots, self.n_slot_pages),
                                  GARBAGE_PAGE, dtype=np.int32)
        self.slot_reserved = np.zeros(n_slots, dtype=np.int64)
        self.slot_pos = np.zeros(n_slots, dtype=np.int64)
        self.slot_prompt = [None] * n_slots
        self.slot_hashes = [[] for _ in range(n_slots)]

    # -- Engine-seam admission (same transactional logic) --------------------

    def _worst_case_pages(self, s, max_new):
        return -(-(s + max_new - 1) // self.page_size)

    def validate_request(self, req):
        s = int(np.asarray(req.tokens).shape[-1])
        if req.max_new < 1:
            raise ValueError(f"request {req.rid!r}: max_new must be >= 1")
        if s + req.max_new - 1 > self.max_len:
            raise ValueError(f"request {req.rid!r}: over slot capacity")
        if self._worst_case_pages(s, req.max_new) > self.n_pages - 1:
            raise ValueError(f"request {req.rid!r}: over pool capacity")

    def try_admit(self, req):
        free = np.flatnonzero(~self.active & ~self.prefilling)
        if not len(free):
            return False
        prompt = np.asarray(req.tokens).ravel()
        s = int(prompt.shape[-1])
        digests = page_hashes(prompt, self.page_size)
        matched = self.alloc.match_prefix(digests[:(s - 1) // self.page_size])
        need = self._worst_case_pages(s, req.max_new) - len(matched)
        if not self.alloc.reserve(need):
            for pid in matched:
                self.alloc.release(pid)
            return False
        slot = int(free[0])
        prompt = prompt.astype(np.int64)
        n_prompt_pages = -(-s // self.page_size)
        self.slot_pages[slot, :len(matched)] = matched
        reserved = need
        for i in range(len(matched), n_prompt_pages):
            self.slot_pages[slot, i] = self.alloc.alloc(reserved=True)
            reserved -= 1
        self.slot_reserved[slot] = reserved
        self.slot_pos[slot] = len(matched) * self.page_size
        self.slot_prompt[slot] = prompt
        self.slot_hashes[slot] = digests
        self.prefilling[slot] = True
        self.slot_req[slot] = req
        self.slot_tokens[slot] = []
        self.slot_admitted[slot] = self.tick_no
        self.stats.slot_served[slot] += 1
        self.stats.prefix_hit_pages += len(matched)
        self.stats.prefix_eligible_pages += (s - 1) // self.page_size
        self.obs.on_admit(req, slot, self.tick_no)
        return True

    # -- Engine-seam tick ----------------------------------------------------

    def _finish_prefill(self, slot):
        req = self.slot_req[slot]
        for i, d in enumerate(self.slot_hashes[slot]):
            self.alloc.register_hash(int(self.slot_pages[slot, i]), d)
        self.obs.on_first_token(req, self.tick_no)
        self.prefilling[slot] = False
        self.active[slot] = True
        self.index[slot] = int(self.slot_prompt[slot].shape[-1])
        self.remaining[slot] = req.max_new - 1
        self.slot_tokens[slot] = [expected_token(req.tokens, 0)]
        self.stats.prefills += 1
        if self.remaining[slot] <= 0:
            return [self._evict(slot)]
        return []

    def _release_slot(self, slot):
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

    def _evict(self, slot):
        from repro.serve.scheduler import Completion
        req = self.slot_req[slot]
        comp = Completion(rid=req.rid,
                          tokens=np.asarray(self.slot_tokens[slot]),
                          reason="length", slot=slot,
                          admitted_tick=int(self.slot_admitted[slot]),
                          finished_tick=self.tick_no)
        self._release_slot(slot)
        self.stats.completed += 1
        self.stats.evicted_length += 1
        self.obs.on_evict(comp)
        return comp

    def preempt(self, slot):
        req = self.slot_req[slot]
        if req is None:
            raise ValueError(f"preempt: slot {slot} is idle")
        self._release_slot(slot)
        self.stats.preempted += 1
        self.obs.on_preempt(req, slot)
        return req

    def drain_queued(self):
        return self.queue.drain()

    def step(self):
        done = []
        for slot in np.flatnonzero(self.prefilling):
            slot = int(slot)
            s = int(self.slot_prompt[slot].shape[-1])
            pos = int(self.slot_pos[slot])
            self.slot_pos[slot] = min(pos + CHUNK, s)
            self.stats.prefill_chunks += 1
            if self.slot_pos[slot] == s:
                done += self._finish_prefill(slot)
        act = [int(s) for s in np.flatnonzero(self.active)]
        if act:
            for slot in act:
                pg = int(self.index[slot]) // self.page_size
                if int(self.slot_pages[slot, pg]) == GARBAGE_PAGE:
                    self.slot_pages[slot, pg] = self.alloc.alloc(
                        reserved=True)
                    self.slot_reserved[slot] -= 1
            self.stats.occupancy_ticks += len(act)
            self.stats.decode_tokens += len(act)
            for slot in act:
                req = self.slot_req[slot]
                tok = expected_token(req.tokens, len(self.slot_tokens[slot]))
                self.slot_tokens[slot].append(tok)
                self.index[slot] += 1
                self.remaining[slot] -= 1
                if self.remaining[slot] <= 0:
                    done.append(self._evict(slot))
        elif not self.prefilling.any():
            self.stats.idle_ticks += 1
        self.stats.pages_in_use_peak = self.alloc.in_use_peak
        self.tick_no += 1
        self.stats.ticks += 1
        return done


# ---------------------------------------------------------------------------
# trace generation + reference checks
# ---------------------------------------------------------------------------

def _fleet(n, *, n_slots=2, max_len=24, page_size=4, recorder=None):
    return [FakeEngine(n_slots=n_slots, max_len=max_len, page_size=page_size,
                       recorder=(recorder.for_replica(i) if recorder else
                                 None))
            for i in range(n)]


def _random_trace(rng, n_reqs, *, max_len=24, share_prob=0.4):
    """Random prompts/budgets/priorities/arrivals; with ``share_prob`` a
    request reuses a previous prompt's prefix (exercises affinity + the
    prefix cache). ~n_reqs * (prompt/CHUNK + max_new) scheduling ops."""
    reqs, prompts = [], []
    for i in range(n_reqs):
        if prompts and rng.rand() < share_prob:
            base = prompts[rng.randint(len(prompts))]
            keep = rng.randint(1, len(base) + 1)
            extra = rng.randint(0, VOCAB, size=rng.randint(0, 5))
            toks = np.concatenate([base[:keep], extra])[:max_len - 8]
        else:
            toks = rng.randint(0, VOCAB, size=rng.randint(1, 13))
        toks = toks.astype(np.int64)
        prompts.append(toks)
        reqs.append(Request(rid=i, tokens=toks,
                            max_new=int(rng.randint(1, 8)),
                            priority=int(rng.randint(0, 3)),
                            arrival=int(rng.randint(0, 60))))
    return reqs


def _completion_map(comps):
    out = {}
    for c in comps:
        assert c.rid not in out, f"request {c.rid} completed twice"
        out[c.rid] = list(c.tokens)
    return out


def _assert_tokens_expected(reqs, comps):
    got = _completion_map(comps)
    assert sorted(got) == sorted(r.rid for r in reqs), "lost/extra requests"
    for r in reqs:
        want = [expected_token(r.tokens, k) for k in range(r.max_new)]
        assert got[r.rid] == want, (r.rid, got[r.rid], want)


def _assert_fleet_clean(router):
    """Post-run allocator invariants on every live replica: internal
    consistency and zero leaked pages."""
    for i, eng in enumerate(router.replicas):
        eng.alloc.check()
        if not router.removed[i]:
            assert not eng.active.any() and not eng.prefilling.any()


def _check_global_fifo(reqs, dispatch_log):
    """Reference model for property (b): replay the dispatch log against a
    plain list — each dispatched rid must be the eligible head by
    (priority desc, submission order) among requests whose arrival has
    passed. Only valid for drain-free traces (requeues re-enter at the
    back of their class with a new submission position)."""
    pending = {r.rid: (r.priority, seq, r.arrival)
               for seq, r in enumerate(reqs)}
    for tick, rid, _replica in dispatch_log:
        prio, seq, arrival = pending[rid]
        assert arrival <= tick, f"rid {rid} dispatched before arrival"
        for orid, (oprio, oseq, oarr) in pending.items():
            if orid == rid or oarr > tick:
                continue
            assert (-oprio, oseq) >= (-prio, seq), (
                f"rid {rid} (prio {prio}, seq {seq}) dispatched at tick "
                f"{tick} ahead of eligible rid {orid} "
                f"(prio {oprio}, seq {oseq})")
        del pending[rid]


# ---------------------------------------------------------------------------
# (a) completion equivalence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n_replicas", [1, 2, 3, 4])
def test_completion_multiset_equals_single_engine(seed, n_replicas):
    """No request lost, duplicated, or re-tokenized: an N-replica fleet
    completes the exact multiset a 1-replica run does, token-for-token."""
    rng = np.random.RandomState(seed)
    reqs = _random_trace(rng, 50)
    single = Router(_fleet(1)).run(reqs)
    multi = Router(_fleet(n_replicas)).run(reqs)
    assert _completion_map(multi) == _completion_map(single)
    _assert_tokens_expected(reqs, multi)


# ---------------------------------------------------------------------------
# (b) global FIFO-within-priority
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [3, 4, 5, 6])
@pytest.mark.parametrize("n_replicas", [2, 4])
def test_fifo_within_priority_across_replicas(seed, n_replicas):
    """Every dispatch is the eligible global head: priority classes never
    invert, submission order never inverts within a class, and arrival
    gating holds — across all replica queues at once."""
    rng = np.random.RandomState(seed)
    reqs = _random_trace(rng, 60)
    router = Router(_fleet(n_replicas))
    router.run(reqs)
    log = router.stats.dispatch_log
    assert len(log) == len(reqs)
    _check_global_fifo(reqs, log)
    _assert_fleet_clean(router)


# ---------------------------------------------------------------------------
# (c) drain / remove with in-flight requeue
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [7, 8, 9])
def test_drain_requeues_in_flight_and_completes_all(seed):
    """Mid-trace drains (one per non-zero replica, one of them a remove)
    preempt the replica's in-flight work, requeue all of it, stop all
    dispatch to that replica, and the trace still completes with the exact
    expected tokens."""
    rng = np.random.RandomState(seed)
    reqs = _random_trace(rng, 50)
    n_replicas = 3
    router = Router(_fleet(n_replicas))
    drain_ticks = {}
    for i in range(1, n_replicas):
        t = int(rng.randint(5, 40))
        drain_ticks[i] = t
        router.schedule_drain(i, t, remove=(i == n_replicas - 1))
    comps = router.run(reqs)
    _assert_tokens_expected(reqs, comps)
    assert router.stats.drains == len(drain_ticks)
    # drains landed mid-flight at least once across seeds is not guaranteed
    # per replica, but every preempted request must be recycled 1:1
    assert router.stats.requeued == sum(e.stats.preempted
                                        for e in router.replicas)
    for tick, _rid, idx in router.stats.dispatch_log:
        if idx in drain_ticks:
            assert tick < drain_ticks[idx], (
                f"dispatch to replica {idx} at tick {tick} after its "
                f"drain at {drain_ticks[idx]}")
    assert router.removed[n_replicas - 1]
    _assert_fleet_clean(router)


def test_drain_actually_preempts_in_flight_work():
    """Deterministic drain-hits-work case: long decode budgets guarantee
    replica 1 holds in-flight requests at the drain tick."""
    reqs = [Request(rid=i, tokens=np.arange(1, 9, dtype=np.int64),
                    max_new=12, arrival=0) for i in range(4)]
    router = Router(_fleet(2, max_len=24))
    router.schedule_drain(1, 6)
    comps = router.run(reqs)
    _assert_tokens_expected(reqs, comps)
    assert router.replicas[1].stats.preempted > 0
    assert router.stats.requeued == router.replicas[1].stats.preempted
    _assert_fleet_clean(router)


def test_preemption_handler_drains_on_trigger():
    """dist.fault wiring: a triggered PreemptionHandler drains its replica
    on the next step — the SIGTERM-eviction path, minus the signal."""
    reqs = [Request(rid=i, tokens=np.arange(1, 7, dtype=np.int64),
                    max_new=10, arrival=0) for i in range(4)]
    router = Router(_fleet(2))
    handler = PreemptionHandler(install=False)
    router.watch_preemption(1, handler)
    for r in reqs:
        assert router.submit(r)
    out = []
    for _ in range(4):
        out += router.step()
    assert router.replicas[1].stats.prefills > 0   # replica 1 took work
    handler.trigger()
    while router._busy() or len(router.queue):
        out += router.step()
    assert router.stats.drains == 1
    assert router.draining[1] and not router.removed[1]
    _assert_tokens_expected(reqs, out)
    # resume reopens dispatch
    router.resume(1)
    assert not router.draining[1]


# ---------------------------------------------------------------------------
# (d) affinity is placement-only
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [10, 11, 12])
def test_affinity_never_changes_tokens(seed):
    """Prefix-affinity routing concentrates shared-prefix requests (it
    fires on these traces) but the emitted tokens are identical to the
    affinity-off run, request by request."""
    rng = np.random.RandomState(seed)
    reqs = _random_trace(rng, 50, share_prob=0.7)
    r_on = Router(_fleet(3), affinity=True)
    on = r_on.run(reqs)
    r_off = Router(_fleet(3), affinity=False)
    off = r_off.run(reqs)
    assert _completion_map(on) == _completion_map(off)
    _assert_tokens_expected(reqs, on)
    assert r_on.stats.affinity_hits > 0
    assert r_off.stats.affinity_hits == 0


# ---------------------------------------------------------------------------
# router construction / backpressure / aggregate report
# ---------------------------------------------------------------------------

def test_router_rejects_heterogeneous_replicas():
    a = FakeEngine(n_slots=2, max_len=24, page_size=4)
    b = FakeEngine(n_slots=2, max_len=32, page_size=4)
    with pytest.raises(ValueError, match="homogeneous"):
        Router([a, b])
    with pytest.raises(ValueError, match="at least one"):
        Router([])


def test_router_bounded_queue_backpressure_absorbed():
    """run() on a bounded global queue holds refused requests back and
    resubmits as the queue drains — everything completes."""
    rng = np.random.RandomState(13)
    reqs = _random_trace(rng, 30)
    router = Router(_fleet(2), queue=AdmissionQueue(max_pending=3))
    comps = router.run(reqs)
    _assert_tokens_expected(reqs, comps)


def test_router_validates_requests_loudly():
    router = Router(_fleet(2, max_len=16))
    with pytest.raises(ValueError, match="max_new"):
        router.submit(Request(rid=0, tokens=np.arange(4), max_new=0))
    with pytest.raises(ValueError):
        router.submit(Request(rid=1, tokens=np.arange(4), max_new=64))


def test_router_stats_aggregate_modeled_concurrency():
    """agg_tokens_per_s = tokens / (router_s + max busy): the modeled
    data-parallel wall — slowest replica plus routing overhead."""
    rs = RouterStats(n_replicas=2)
    rs.busy_s = [2.0, 1.0]
    rs.router_s = 1.0
    rep = rs.aggregate([{"decode_tokens": 10, "prefills": 2},
                        {"decode_tokens": 8, "prefills": 1}])
    assert rep["tokens"] == 21
    assert rep["busy_s_max"] == 2.0
    assert rep["agg_tokens_per_s"] == pytest.approx(21 / 3.0)
    assert json.dumps(rep, allow_nan=False)


def test_router_report_carries_per_replica_rows():
    rng = np.random.RandomState(14)
    reqs = _random_trace(rng, 20)
    router = Router(_fleet(2))
    router.run(reqs)
    rep = router.report()
    assert rep["replicas"] == 2
    assert rep["completed"] == len(reqs)
    assert sum(rep["routed"]) == len(reqs)
    assert len(rep["per_replica"]) == 2
    assert rep["per_replica"][0]["replica"] == 0
    assert rep["per_replica"][0]["routed"] == rep["routed"][0]
    assert json.dumps(rep, allow_nan=False)


# ---------------------------------------------------------------------------
# satellite: AdmissionQueue boundary paths
# ---------------------------------------------------------------------------

def test_admission_queue_empty_boundaries():
    q = AdmissionQueue()
    assert len(q) == 0
    assert q.peek(0) is None
    assert q.pop(0) is None
    assert q.next_arrival() is None


def test_admission_queue_all_future_and_exact_arrival_tick():
    q = AdmissionQueue()
    r5 = Request(rid=0, tokens=[1], max_new=1, arrival=5)
    r9 = Request(rid=1, tokens=[1], max_new=1, arrival=9)
    assert q.submit(r9) and q.submit(r5)
    # all-future: nothing eligible, next_arrival is the earliest future
    assert q.peek(4) is None and q.pop(4) is None
    assert q.next_arrival() == 5
    assert len(q) == 2
    # pop at the exact arrival tick succeeds; the later one stays future
    assert q.peek(5) is r5
    assert q.pop(5) is r5
    assert q.pop(5) is None
    assert q.next_arrival() == 9
    assert q.pop(9) is r9


def test_admission_queue_next_arrival_mixed_ready_and_future():
    q = AdmissionQueue()
    q.submit(Request(rid=0, tokens=[1], max_new=1, arrival=7))
    q.submit(Request(rid=1, tokens=[1], max_new=1, arrival=2))
    q.peek(3)          # migrates rid 1 to the ready heap
    assert q.next_arrival() == 2    # ready beats the future heap's 7


def test_admission_queue_drain_returns_pop_order():
    q = AdmissionQueue()
    q.submit(Request(rid="lo", tokens=[1], max_new=1, priority=0, arrival=0))
    q.submit(Request(rid="hi", tokens=[1], max_new=1, priority=1, arrival=0))
    q.submit(Request(rid="fut", tokens=[1], max_new=1, arrival=50))
    q.peek(0)          # migrate the arrived pair
    assert [r.rid for r in q.drain()] == ["hi", "lo", "fut"]
    assert len(q) == 0


def test_admission_queue_force_submit_bypasses_bound():
    q = AdmissionQueue(max_pending=1)
    assert q.submit(Request(rid=0, tokens=[1], max_new=1))
    assert not q.submit(Request(rid=1, tokens=[1], max_new=1))
    assert q.submit(Request(rid=1, tokens=[1], max_new=1), force=True)
    assert len(q) == 2


# ---------------------------------------------------------------------------
# satellite: EngineStats empty-report hardening
# ---------------------------------------------------------------------------

def test_engine_stats_empty_report_is_json_clean():
    """An engine that admitted nothing reports the explicit empty latency
    shape (all-None percentiles, n=0) and a NaN-free JSON document."""
    rep = EngineStats(n_slots=2).report()
    assert rep["ttft_s"] == EMPTY_PERCENTILES
    assert rep["tpot_s"] == EMPTY_PERCENTILES
    assert rep["mean_occupancy"] == 0.0
    assert rep["preempted"] == 0
    json.dumps(rep, allow_nan=False)    # raises on NaN/inf


def test_engine_stats_zero_slots_no_division_error():
    rep = EngineStats(n_slots=0).report()
    assert rep["mean_occupancy"] == 0.0
    json.dumps(rep, allow_nan=False)


def test_engine_stats_percentiles_filter_non_finite():
    s = EngineStats(n_slots=1)
    s.ttft_s = [0.1, float("nan"), 0.3, float("inf")]
    lat = s.latency_report()
    assert lat["ttft"]["n"] == 2
    assert lat["ttft"]["p50"] == pytest.approx(0.2)
    s.ttft_s = [float("nan")]
    assert s.latency_report()["ttft"] == EMPTY_PERCENTILES


# ---------------------------------------------------------------------------
# satellite: per-replica obs labels + balanced spans across preempt
# ---------------------------------------------------------------------------

def test_recorder_replica_labels_and_balanced_preempt_spans():
    """for_replica children label engine metrics per replica in one shared
    registry, and a preempted+requeued request keeps its async trace
    begin/end counts balanced (end reason "preempt", then a fresh span)."""
    parent = EngineRecorder()
    router = Router(_fleet(2, recorder=parent), recorder=parent)
    reqs = [Request(rid=i, tokens=np.arange(1, 9, dtype=np.int64),
                    max_new=12, arrival=0) for i in range(4)]
    router.schedule_drain(1, 6)
    comps = router.run(reqs)
    _assert_tokens_expected(reqs, comps)
    assert router.stats.requeued > 0

    keys = parent.metrics.snapshot()["metrics"].keys()
    assert "serve_submitted_total" in keys               # router-level, bare
    assert 'serve_prefill_total{replica="0"}' in keys    # replica-labelled
    assert 'serve_prefill_total{replica="1"}' in keys
    assert 'serve_preempted_total{replica="1"}' in keys

    opens = {}
    preempt_ends = 0
    for ev in parent.trace.events():
        if ev.get("ph") == "b" and ev.get("cat") == "request":
            opens[ev["id"]] = opens.get(ev["id"], 0) + 1
        elif ev.get("ph") == "e" and ev.get("cat") == "request":
            opens[ev["id"]] = opens.get(ev["id"], 0) - 1
            if (ev.get("args") or {}).get("reason") == "preempt":
                preempt_ends += 1
    assert preempt_ends == router.stats.requeued
    assert all(v == 0 for v in opens.values()), opens


# ---------------------------------------------------------------------------
# real engines (jax): small smoke versions of (a) and (c)
# ---------------------------------------------------------------------------

def _real_fleet(n, params, m, **kw):
    from repro.serve.engine import Engine
    fleet = [Engine(params, m, **kw)]
    for _ in range(n - 1):
        fleet.append(Engine(fleet[0].params, m, **kw)
                     .adopt_compiled(fleet[0]))
    return fleet


def test_router_real_engines_match_single_engine():
    """Two real-Engine replicas (shared deployed params, warm-adopted jit
    caches) reproduce a single engine's tokens on a shared-prefix trace."""
    import jax
    from repro.configs import get_arch
    from repro.models import transformer as tfm
    from repro.serve.engine import Engine, synth_trace

    m = get_arch("mistral_nemo_12b", smoke=True).model
    params = tfm.init_model(jax.random.PRNGKey(0), m)
    reqs = synth_trace(m.vocab, 8, max_prompt=10, min_prompt=4, max_new=6,
                       min_new=3, stagger=2, common_prefix=8, seed=3)
    kw = dict(n_slots=2, max_len=24, page_size=4)
    ref = _completion_map(Engine(params, m, **kw).run(reqs))
    router = Router(_real_fleet(2, params, m, **kw))
    got = _completion_map(router.run(reqs))
    assert got == ref
    rep = router.report()
    assert rep["completed"] == len(reqs)
    assert rep["affinity_hits"] > 0      # the shared prefix concentrated


def test_router_real_engines_drain_keeps_tokens():
    """Draining a real replica mid-trace requeues its in-flight work and
    the rerun emits identical tokens (greedy decode is deterministic)."""
    import jax
    from repro.configs import get_arch
    from repro.models import transformer as tfm
    from repro.serve.engine import Engine, synth_trace

    m = get_arch("mamba2_1p3b", smoke=True).model
    params = tfm.init_model(jax.random.PRNGKey(1), m)
    reqs = synth_trace(m.vocab, 6, max_prompt=10, min_prompt=4, max_new=6,
                       min_new=4, stagger=1, seed=5)
    kw = dict(n_slots=2, max_len=24)
    ref = _completion_map(Engine(params, m, **kw).run(reqs))
    router = Router(_real_fleet(2, params, m, **kw))
    router.schedule_drain(1, 4)
    got = _completion_map(router.run(reqs))
    assert got == ref
    assert router.stats.drains == 1
    assert router.replicas[1].stats.preempted + router.stats.requeued >= 0
    for c_tokens in got.values():
        assert len(c_tokens) > 0


def test_make_replicas_pins_each_replica_to_its_own_device():
    """``make_replicas`` (what ``launch.serve --replicas`` builds) puts
    replica i's params and cache on ``jax.devices()[i]``; the pinned fleet,
    recorded (per-device AOT executables), still matches one engine."""
    import jax
    from repro.configs import get_arch
    from repro.models import transformer as tfm
    from repro.serve.engine import Engine, make_replicas, synth_trace

    devices = jax.devices()
    assert len(devices) >= 4, "conftest forces 8 host devices"
    m = get_arch("mamba2_1p3b", smoke=True).model
    params = tfm.init_model(jax.random.PRNGKey(2), m)
    kw = dict(n_slots=1, max_len=16)
    parent = EngineRecorder()
    fleet = make_replicas(params, m, 4, recorder_for=parent.for_replica, **kw)
    for i, eng in enumerate(fleet):
        placed = {d for leaf in jax.tree.leaves((eng.params, eng.cache))
                  for d in leaf.devices()}
        assert placed == {devices[i]}, (i, placed)
    reqs = synth_trace(m.vocab, 4, max_prompt=8, min_prompt=8, max_new=3,
                       min_new=3, stagger=0, seed=7)
    ref = _completion_map(Engine(params, m, **kw).run(reqs))
    router = Router(fleet)
    assert _completion_map(router.run(reqs)) == ref
    assert all(n == 1 for n in router.report()["routed"])


# ---------------------------------------------------------------------------
# HealthMonitor: closed-loop auto-drain
# ---------------------------------------------------------------------------

from repro.obs.slo import SLOObjective  # noqa: E402


def _quiet_slos():
    """An SLO set that can never trip (no latency samples arrive from the
    FakeEngine) so drift is the only drain signal under test."""
    return (SLOObjective("ttft", threshold=1e9),)


class FakeProbe:
    """Duck-typed chip-health source: canary deviation ramps linearly with
    age (``rel_dev = rate * age``), standing in for ``hw.health
    .ChipHealth`` so the router tests stay host-only and instant."""

    def __init__(self, rate=0.0):
        self.rate = rate
        self.probes = 0

    def probe(self, age):
        self.probes += 1
        return {"age": float(age),
                "max_rel_dev": round(self.rate * age, 6),
                "adc_saturation": 0, "adc_saturation_total": 0,
                "tiles": []}


def test_health_drift_drain_zero_lost_requests():
    """A replica whose canary deviation crosses the threshold mid-trace is
    auto-drained; every in-flight request is requeued and the fleet's
    completion multiset still equals a healthy single-engine run."""
    rng = np.random.RandomState(3)
    reqs = _random_trace(rng, 40)
    single = _completion_map(Router(_fleet(1)).run(reqs))
    router = Router(_fleet(2))
    mon = router.enable_health(poll_every=2, drift_threshold=0.05,
                               slos=_quiet_slos)
    mon.attach_chip(1, FakeProbe(rate=0.01))    # crosses 0.05 at age > 5
    comps = router.run(reqs)
    assert router.draining[1]
    assert router.stats.drained_for_health == 1
    drained = [e for e in mon.events if e["action"] == "drained"]
    assert len(drained) == 1
    assert drained[0]["replica"] == 1
    assert drained[0]["reasons"] and \
        drained[0]["reasons"][0].startswith("drift:")
    assert drained[0]["tick"] == 6              # first poll past dev 0.05
    _assert_tokens_expected(reqs, comps)
    assert _completion_map(comps) == single
    _assert_fleet_clean(router)
    # drained replica is skipped by later polls: probe age froze at drain
    assert mon.last_probe[1]["age"] == 6.0
    assert mon.summary()["events"] == mon.events


def test_health_never_drains_last_replica():
    """Breach everywhere: the first replica drains, the survivor's breach
    is suppressed — a degraded replica beats a deadlocked fleet."""
    rng = np.random.RandomState(4)
    reqs = _random_trace(rng, 20)
    router = Router(_fleet(2))
    mon = router.enable_health(poll_every=2, drift_threshold=0.05,
                               slos=_quiet_slos)
    mon.attach_chip(0, FakeProbe(rate=1.0))     # breaching from age 2
    mon.attach_chip(1, FakeProbe(rate=1.0))
    comps = router.run(reqs)
    assert router.stats.drained_for_health == 1
    assert router.draining[0] and not router.draining[1]
    actions = [(e["replica"], e["action"]) for e in mon.events]
    assert actions[0] == (0, "drained")
    assert (1, "suppressed_last_replica") in actions
    assert all(a == "suppressed_last_replica"
               for r, a in actions if r == 1)
    _assert_tokens_expected(reqs, comps)
    _assert_fleet_clean(router)


def test_health_slo_burn_drains():
    """A burning SLO drains a replica just like drift does. queue_wait
    with threshold -1 scores every poll bad; at objective 0.9 the all-bad
    stream burns at 10x — far over the default factor 2 (at objective 0.5
    it would burn at exactly 2.0, deliberately NOT strictly above)."""
    def bad_slos():
        return (SLOObjective("queue_wait", objective=0.9, threshold=-1.0,
                             long_window=8, short_window=2, min_events=4),)

    rng = np.random.RandomState(5)
    reqs = _random_trace(rng, 30)
    router = Router(_fleet(2))
    mon = router.enable_health(poll_every=1, slos=bad_slos)
    comps = router.run(reqs)
    drained = [e for e in mon.events if e["action"] == "drained"]
    assert len(drained) == 1
    assert drained[0]["reasons"] == ["slo:queue_wait"]
    assert router.stats.drained_for_health == 1
    # the survivor burns too but is protected by the last-replica rule
    assert any(e["action"] == "suppressed_last_replica"
               for e in mon.events)
    verdicts = mon.summary()["slo_verdicts"]
    assert "burning" in verdicts[str(drained[0]["replica"])].values() or \
        "burning" in verdicts[str(1 - drained[0]["replica"])].values()
    _assert_tokens_expected(reqs, comps)
    _assert_fleet_clean(router)


def test_report_fleet_sketch_and_health_section():
    """Router.report() merges per-replica latency sketches into one fleet
    snapshot (count-exact merge) and carries the health summary."""
    from repro.obs.sketch import QuantileSketch

    router = Router(_fleet(2))
    router.enable_health(poll_every=4)
    router.replicas[0].stats.ttft_s = [0.1] * 50
    router.replicas[1].stats.ttft_s = [0.3] * 50
    rep = router.report()
    fleet = rep["fleet"]["ttft_sketch"]
    assert fleet["n"] == 100
    assert fleet["p50"] == pytest.approx(0.1, rel=0.02)
    assert fleet["p95"] == pytest.approx(0.3, rel=0.02)
    # merge equals sketching the concatenated per-replica streams
    whole = QuantileSketch.from_samples([0.1] * 50 + [0.3] * 50)
    assert fleet == whole.percentiles()
    assert rep["fleet"]["tpot_sketch"] is None   # no samples -> no sketch
    assert rep["drained_for_health"] == 0
    assert rep["health"]["polls"] == 0
    assert set(rep["health"]["slo_verdicts"]) == {"0", "1"}
    # without a monitor the report has a fleet section but no health one
    bare = Router(_fleet(1)).report()
    assert "fleet" in bare and "health" not in bare
