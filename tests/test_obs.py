"""repro.obs: metrics registry, trace flight recorder, compile events,
profiler spans, and their integration with the serving engine + scheduler.

Also pins the two scheduler changes that rode in with the obs layer:
* heap-backed AdmissionQueue == the old O(n) list implementation on random
  traces (property test);
* Engine.run() fast-forwards idle stretches to the next arrival without
  changing tokens or occupancy math (sparse-trace test).
"""
import contextlib
import functools
import glob
import json
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import transformer as tfm
from repro.obs import (DEFAULT_LATENCY_BUCKETS, EngineRecorder, Histogram,
                       MetricsRegistry, NullRecorder, TraceRecorder,
                       log_buckets)
from repro.obs import recorder as recorder_lib
from repro.serve import engine as engine_lib
from repro.serve.engine import Engine, synth_trace
from repro.serve.scheduler import AdmissionQueue, Request

# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_log_buckets_edges():
    b = log_buckets(1e-3, 1.0, per_decade=3)
    assert b[0] == pytest.approx(1e-3)
    assert b[-1] >= 1.0
    assert len(b) == 10                       # 3 decades * 3 + fencepost
    ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
    assert all(r == pytest.approx(10 ** (1 / 3)) for r in ratios)
    # default scheme covers µs .. 100 s
    assert DEFAULT_LATENCY_BUCKETS[0] == pytest.approx(1e-6)
    assert DEFAULT_LATENCY_BUCKETS[-1] >= 100.0


def test_histogram_bucket_assignment_and_edges():
    h = Histogram("h", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0):                      # <= first bound -> bucket 0
        h.observe(v)
    h.observe(5.0)                            # (1, 10]   -> bucket 1
    h.observe(10.0)                           # boundary lands in its bucket
    h.observe(1000.0)                         # > last    -> overflow
    assert h.counts == [2, 2, 0, 1]
    assert h.count == 5 and h.min == 0.5 and h.max == 1000.0
    cum = h.cumulative()
    assert cum[-1] == (math.inf, 5)
    assert [c for _, c in cum] == [2, 4, 4, 5]


def test_histogram_percentiles_log_interpolated():
    h = Histogram("h")
    for _ in range(100):
        h.observe(1e-3)                       # all mass in one bucket
    p50 = h.percentile(50)
    # clamped to observed range: exactly the single observed value
    assert p50 == pytest.approx(1e-3)
    assert h.percentile(99) == pytest.approx(1e-3)
    empty = Histogram("e")
    assert empty.percentile(50) is None


def test_registry_identity_and_kinds():
    reg = MetricsRegistry()
    c1 = reg.counter("x", "help")
    c2 = reg.counter("x")
    assert c1 is c2
    c1.inc(2)
    assert reg.counter("x").value == 2
    # labels make distinct series; same name must keep one kind
    la = reg.counter("y", labels={"phase": "a"})
    lb = reg.counter("y", labels={"phase": "b"})
    assert la is not lb
    with pytest.raises(ValueError, match="already registered|already used"):
        reg.gauge("x")
    with pytest.raises(ValueError, match="negative"):
        c1.inc(-1)


def test_snapshot_exposition_round_trip():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "requests").inc(3)
    reg.gauge("slots", "active slots").set(2.5)
    h = reg.histogram("lat_seconds", "latency")
    h.observe(0.01)
    h.observe(0.5)
    snap = reg.snapshot()
    assert snap["schema"] == "obs-metrics/v1"
    # snapshot is JSON-clean and carries the histogram percentiles
    again = json.loads(json.dumps(snap))
    assert again["metrics"]["reqs_total"]["value"] == 3
    hist = again["metrics"]["lat_seconds"]
    assert hist["count"] == 2 and hist["p50"] is not None
    assert hist["buckets"][-1][0] == "+Inf"
    assert hist["buckets"][-1][1] == 2
    # Prometheus text exposition
    text = reg.exposition()
    assert "# TYPE reqs_total counter" in text
    assert "reqs_total 3" in text
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert "lat_seconds_count 2" in text


def test_exposition_prometheus_conformance():
    """Text-format conformance: label values escaped (backslash, quote,
    newline), HELP escaped, value specials rendered as +Inf/-Inf/NaN, and
    histogram buckets CUMULATIVE up to an explicit +Inf bucket whose count
    equals _count, with a numeric _sum line."""
    reg = MetricsRegistry()
    reg.counter("c_total", 'help with \\ and\nnewline',
                labels={"path": 'a"b\\c\nd'}).inc(1)
    reg.gauge("g_inf").set(float("inf"))
    reg.gauge("g_ninf").set(float("-inf"))
    reg.gauge("g_nan").set(float("nan"))
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.exposition()
    lines = text.splitlines()
    # label-value escaping: backslash -> \\, quote -> \", newline -> \n
    assert 'c_total{path="a\\"b\\\\c\\nd"} 1.0' in lines
    # HELP escaping: backslash and newline only (quotes stay raw)
    assert "# HELP c_total help with \\\\ and\\nnewline" in lines
    # value specials
    assert "g_inf +Inf" in lines
    assert "g_ninf -Inf" in lines
    assert "g_nan NaN" in lines
    # cumulative le buckets + _sum/_count
    assert 'lat_seconds_bucket{le="0.1"} 1' in lines
    assert 'lat_seconds_bucket{le="1.0"} 2' in lines
    assert 'lat_seconds_bucket{le="+Inf"} 3' in lines
    assert "lat_seconds_count 3" in lines
    sum_line = next(ln for ln in lines if ln.startswith("lat_seconds_sum "))
    assert float(sum_line.split()[1]) == pytest.approx(5.55)
    # every non-comment line is "name{labels} value" with a parseable value
    for ln in lines:
        if not ln or ln.startswith("#"):
            continue
        val = ln.rsplit(" ", 1)[1]
        assert val in ("+Inf", "-Inf", "NaN") or float(val) is not None


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------


def test_span_nesting_and_chrome_schema():
    tr = TraceRecorder(capacity=64, pid=7)
    with tr.span("outer"):
        with tr.span("inner"):
            tr.instant("marker")
    tr.begin_async("request", "r1", args={"rid": "r1"})
    tr.end_async("request", "r1")
    ct = tr.chrome_trace()
    evs = ct["traceEvents"]
    assert ct["displayTimeUnit"] == "ms"
    by_name = {e["name"]: e for e in evs if e.get("ph") in "Xibe"}
    # inner closes before outer -> recorded first; spans nest in time
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["ph"] == inner["ph"] == "X"
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    # async pair shares id + cat
    b = next(e for e in evs if e["ph"] == "b")
    e = next(e for e in evs if e["ph"] == "e")
    assert b["id"] == e["id"] == "r1" and b["cat"] == e["cat"]
    # metadata names the lanes for Perfetto
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in evs)
    json.dumps(ct)                            # schema is JSON-clean


def test_ring_buffer_eviction_counts_drops():
    tr = TraceRecorder(capacity=8)
    for i in range(20):
        tr.instant(f"e{i}")
    assert len(tr) == 8
    assert tr.dropped == 12
    names = [e["name"] for e in tr.events()]
    assert names == [f"e{i}" for i in range(12, 20)]   # most recent window
    ct = tr.chrome_trace()
    assert ct["otherData"]["dropped_events"] == 12
    # the eviction count also rides as a metadata event so a Perfetto
    # session (which never shows otherData) still flags the truncation
    trunc = [e for e in ct["traceEvents"]
             if e["ph"] == "M" and e["name"] == "trace_truncation"]
    assert len(trunc) == 1
    assert trunc[0]["args"] == {"dropped_events": 12, "capacity": 8}


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


def _model(arch_id="mamba2_1p3b", seed=0):
    m = get_arch(arch_id, smoke=True).model
    params = tfm.init_model(jax.random.PRNGKey(seed), m)
    return m, params


def test_engine_defaults_to_null_recorder():
    m, params = _model()
    eng = Engine(params, m, n_slots=1, max_len=12)
    assert isinstance(eng.obs, NullRecorder) and not eng.obs.enabled
    eng.run([Request(rid=0, tokens=np.arange(4), max_new=3)])
    rep = eng.stats.report()
    # unrecorded runs carry no latency samples: percentile columns are None
    assert rep["ttft_s"]["n"] == 0 and rep["ttft_s"]["p50"] is None
    assert rep["tpot_s"]["n"] == 0
    assert eng.obs.snapshot() == {}


def test_recorded_engine_run_full_stack():
    """One recorded run: TTFT/TPOT samples consistent with completions,
    compile events captured per distinct prompt length, valid Chrome trace,
    and recorded tokens identical to an unrecorded engine's."""
    m, params = _model()
    reqs = synth_trace(m.vocab, 5, max_prompt=9, min_prompt=4, max_new=6,
                       min_new=3, stagger=2, seed=3)
    prompt_lens = {int(np.asarray(r.tokens).shape[-1]) for r in reqs}
    rec = EngineRecorder(trace_capacity=4096)
    eng = Engine(params, m, n_slots=2, max_len=16, recorder=rec)
    comps = eng.run(list(reqs))
    assert len(comps) == len(reqs)

    # --- latency samples are consistent with the tick bookkeeping --------
    stats = eng.stats
    assert len(stats.ttft_s) == stats.completed == len(reqs)
    assert all(t > 0 for t in stats.ttft_s)
    # every decode token experienced exactly one tick's TPOT
    assert len(stats.tpot_s) == stats.decode_tokens
    assert stats.decode_tokens == sum(len(c.tokens) - 1 for c in comps)
    for c in comps:
        # the prefill token AND the first decode token both land on the
        # admission tick (admit runs at the start of step()), then one
        # token per tick; immediate eviction (max_new=1) spans 0 ticks
        assert c.finished_tick - c.admitted_tick == max(len(c.tokens) - 2, 0)
    rep = stats.report()
    for fam in ("ttft_s", "tpot_s"):
        assert rep[fam]["p50"] <= rep[fam]["p95"] <= rep[fam]["p99"]
    # wall-clock sanity: no single TTFT exceeds the whole run's wall time
    assert max(stats.ttft_s) <= stats.wall_s + 1e-6

    # --- compile events: one prefill per distinct prompt length ----------
    prefill_events = [e for e in rec.compile_events
                      if e.name.startswith("prefill")]
    assert len(prefill_events) == len(prompt_lens)
    assert {e.name for e in prefill_events} == {
        f"prefill_len{n}" for n in prompt_lens}
    # chunk-exact archs (mamba2 here) prefill through prefill_chunk jits,
    # so no whole-prompt scatter ("cache_write") ever compiles
    assert "decode_tick" in {e.name for e in rec.compile_events}
    assert all(e.wall_s > 0 for e in rec.compile_events)

    # --- snapshot describes the run --------------------------------------
    snap = rec.snapshot()
    assert snap["schema"] == "obs/v1"
    mtr = snap["metrics"]
    assert mtr["serve_ttft_seconds"]["count"] == len(reqs)
    assert mtr["serve_tpot_seconds"]["count"] == stats.decode_tokens
    assert mtr["serve_submitted_total"]["value"] == len(reqs)
    assert mtr['serve_completed_total{reason="length"}']["value"] == len(reqs)
    assert mtr["serve_queue_wait_ticks"]["count"] == len(reqs)
    for phase in ("admit", "prefill", "decode", "host"):
        assert mtr[f'serve_tick_phase_seconds{{phase="{phase}"}}']["count"] > 0
    json.dumps(snap)

    # --- Chrome trace: balanced request lifecycles ------------------------
    ct = rec.trace.chrome_trace()
    evs = ct["traceEvents"]
    assert sum(1 for e in evs if e.get("ph") == "b") == len(reqs)
    assert sum(1 for e in evs if e.get("ph") == "e") == len(reqs)
    assert {e["name"] for e in evs if e.get("ph") == "X"} >= {
        "admit", "prefill", "decode", "host"}

    # --- recording must not change the jits or the tokens -----------------
    plain = Engine(params, m, n_slots=2, max_len=16)
    for attr in ("_decode_jit", "_scatter_jit", "_copy_jit"):
        assert type(getattr(eng, attr)) is type(getattr(plain, attr))
        assert getattr(eng, attr).__name__ == getattr(plain, attr).__name__
    assert all(type(fn) is type(plain._decode_jit)
               for fn in eng._chunk_jit.values())
    comps2 = plain.run(synth_trace(m.vocab, 5, max_prompt=9, min_prompt=4,
                                   max_new=6, min_new=3, stagger=2, seed=3))
    ref = {c.rid: list(c.tokens) for c in comps2}
    assert {c.rid: list(c.tokens) for c in comps} == ref


def test_compile_event_on_second_prompt_length():
    """A new prompt length is a new silent XLA compile — the recorder's
    ``jax.monitoring`` listener must surface exactly one new prefill event
    for it, under the jit's own name, and none for a repeat. Several
    recorders share one process-wide listener."""
    m, params = _model()
    rec = EngineRecorder()
    EngineRecorder()        # a second listener would count every compile twice
    eng = Engine(params, m, n_slots=1, max_len=16, recorder=rec)
    eng.run([Request(rid=0, tokens=np.arange(4) % m.vocab, max_new=2)])
    n0 = len([e for e in rec.compile_events if e.name.startswith("prefill")])
    assert n0 == 1
    eng.run([Request(rid=1, tokens=np.arange(6) % m.vocab, max_new=2)])
    names = [e.name for e in rec.compile_events
             if e.name.startswith("prefill")]
    assert names == ["prefill_len4", "prefill_len6"]
    # repeat length: cache hit, no new compile event
    eng.run([Request(rid=2, tokens=np.arange(6, 12) % m.vocab, max_new=2)])
    assert len([e for e in rec.compile_events
                if e.name.startswith("prefill")]) == 2
    assert rec.metrics.get("compile_total", {"fn": "prefill_len6"}).value == 1
    # a compile outside the engine's phases belongs to no recorder
    n = len(rec.compile_events)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0))
    assert len(rec.compile_events) == n


def test_adopt_compiled_keeps_warm_caches_and_rebinds_recorder():
    m, params = _model()
    rec = EngineRecorder()
    eng = Engine(params, m, n_slots=1, max_len=12, recorder=rec)
    eng.run([Request(rid=0, tokens=np.arange(4) % m.vocab, max_new=3)])
    n_compiles = len(rec.compile_events)
    rec2 = EngineRecorder()
    eng2 = Engine(params, m, n_slots=1, max_len=12,
                  recorder=rec2).adopt_compiled(eng)
    # the very jit objects: nothing rebinds them to the new recorder
    assert eng2._decode_jit is eng._decode_jit
    assert eng2._chunk_jit is eng._chunk_jit
    comps = eng2.run([Request(rid=1, tokens=np.arange(4) % m.vocab,
                              max_new=3)])
    assert len(comps) == 1
    # warm caches: the adopting engine recompiled nothing, which the
    # listener would have handed to rec2 (its phases were the open ones)...
    assert len(rec.compile_events) == n_compiles
    assert rec2.compile_events == []
    # ...but its own recorder captured the run's latencies
    assert rec2.metrics.get("serve_ttft_seconds").count == 1


# ---------------------------------------------------------------------------
# program names, profiler spans, named scopes
# ---------------------------------------------------------------------------


def _decode_args(eng):
    n = eng.n_slots
    return (eng.params, eng.cache, jnp.zeros(n, jnp.int32),
            jnp.zeros(n, jnp.int32),
            jnp.zeros((n, eng.n_slot_pages), jnp.int32))


def test_engine_programs_carry_stable_names():
    """Every engine jit has a real name: the XLA module is ``jit_<name>``
    and jax's compile events carry it as ``fun_name``."""
    m, params = _model("mistral_nemo_12b")
    names = []

    def on(event, duration, **kw):
        if event == recorder_lib.COMPILE_EVENT:
            names.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        eng = Engine(params, m, n_slots=2, max_len=24, page_size=4)
        assert eng.chunk_tokens == 4
        # 10 prompt tokens: a first chunk, an interior one, a last of 2
        eng.run([Request(rid=0, tokens=np.arange(10) % m.vocab, max_new=3)])
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert {"jit(decode_tick)", "jit(prefill_chunk4_first)",
            "jit(prefill_chunk4)", "jit(prefill_chunk2_last)"} <= set(names)
    assert "module @jit_decode_tick" in eng._decode_jit.lower(
        *_decode_args(eng)).as_text()
    assert eng._scatter_jit.__name__ == "cache_write"
    assert eng._copy_jit.__name__ == "copy_page"
    assert recorder_lib.program_name("jit(prefill_len12)") == "prefill_len12"


def test_profiler_trace_holds_nested_engine_spans(tmp_path):
    """A ``jax.profiler`` session over a recorded run holds every phase as
    an ``engine.<phase>`` host span, nested inside the caller's span; the
    chunks nest in ``prefill`` and carry rid/slot/start/length."""
    from jax.profiler import ProfileData
    m, params = _model("mistral_nemo_12b")
    eng = Engine(params, m, n_slots=2, max_len=24, page_size=4,
                 recorder=EngineRecorder())
    eng.run([Request(rid="warm", tokens=np.arange(10) % m.vocab,
                     max_new=3)])           # compiles outside the trace
    eng.submit(Request(rid="r1", tokens=(np.arange(10) + 1) % m.vocab,
                       max_new=3))
    jax.profiler.start_trace(str(tmp_path))
    try:
        while eng.active.any() or eng.prefilling.any() or len(eng.queue):
            with jax.profiler.TraceAnnotation("engine.step"):
                eng.step()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(path[0]).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("engine.")]
    steps = [s for s in spans if s[0] == "engine.step"]
    phases = [s for s in spans if s[0] != "engine.step"]
    assert len(steps) == 4          # 3 chunks, then 2 decode-only ticks
    assert {s[0] for s in phases} == {
        "engine.admit", "engine.prefill", "engine.prefill_chunk",
        "engine.inputs", "engine.decode", "engine.readback", "engine.host"}

    def inside(inner, outer):
        return outer[1] <= inner[1] and inner[2] <= outer[2]

    assert all(any(inside(p, st) for st in steps) for p in phases)
    prefill = [s for s in phases if s[0] == "engine.prefill"]
    chunks = [s for s in phases if s[0] == "engine.prefill_chunk"]
    assert all(any(inside(c, p) for p in prefill) for c in chunks)
    assert [(c[3]["rid"], c[3]["start"], c[3]["length"]) for c in
            sorted(chunks, key=lambda c: c[1])] == [
        ("r1", 0, 4), ("r1", 4, 4), ("r1", 8, 2)]
    # two readbacks of tokens: the last chunk's (in prefill), each decode's
    reads = [s for s in phases if s[0] == "engine.readback"]
    assert sum(any(inside(r, p) for p in prefill) for r in reads) == 1
    assert len(reads) == 1 + sum(s[0] == "engine.decode" for s in phases)


def test_null_recorder_tick_makes_no_timing_call_or_annotation(
        monkeypatch):
    """With the default recorder a tick neither reads a clock nor opens a
    profiler annotation: the untraced program is the one measured."""
    m, params = _model()
    eng = Engine(params, m, n_slots=1, max_len=12)
    eng.run([Request(rid=0, tokens=np.arange(4) % m.vocab, max_new=3)])

    def forbidden(*a, **k):
        raise AssertionError("the NullRecorder tick timed or annotated")

    eng.submit(Request(rid=1, tokens=np.arange(1, 5) % m.vocab, max_new=3))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", forbidden)
    monkeypatch.setattr(recorder_lib.time, "perf_counter", forbidden)
    while eng.active.any() or eng.prefilling.any() or len(eng.queue):
        eng.step()
    assert eng.stats.completed == 2


def _canonical_hlo(text: str) -> str:
    """Optimized HLO text without metadata, module name or instruction
    numbering (a scope may change the order in which jax names ops)."""
    text = re.sub(r"(?s)\nFileNames\n.*?\n\n(?=%|ENTRY)", "\n", text)
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"^HloModule \S+", "HloModule m", text)
    names = {}
    return re.sub(r"%[\w\-.]+", lambda mt: names.setdefault(
        mt.group(0), f"%v{len(names)}"), text)


@pytest.mark.parametrize("arch,scopes", [
    ("mamba2_1p3b", ("mixer", "cache_update", "unembed")),      # no FFN
    ("mistral_nemo_12b", ("mixer", "cache_update", "ffn", "unembed"))])
def test_named_scopes_change_only_op_metadata(arch, scopes, monkeypatch):
    """The decode tick's ops carry the layer scopes in their metadata, and
    its compiled program is the same without them."""
    m, params = _model(arch)
    eng = Engine(params, m, n_slots=2, max_len=16, page_size=4)
    args = _decode_args(eng)
    hlo = eng._decode_jit.lower(*args).compile().as_text()
    for scope in scopes:
        assert re.search(rf'op_name="jit\(decode_tick\)/[^"]*\b{scope}/',
                         hlo), scope
    scoped = _canonical_hlo(hlo)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = engine_lib._named_jit(
        functools.partial(engine_lib._decode_fn, cfg=m), "decode_tick",
        donate_argnums=1)
    assert _canonical_hlo(bare.lower(*args).compile().as_text()) == scoped


# ---------------------------------------------------------------------------
# scheduler satellites: heap queue + idle fast-forward
# ---------------------------------------------------------------------------


class _ListQueue:
    """The previous O(n) scan-and-remove implementation — the semantic
    reference for the heap-backed AdmissionQueue."""

    def __init__(self, max_pending=None):
        self.max_pending = max_pending
        self._items = []
        self._n = 0

    def __len__(self):
        return len(self._items)

    def submit(self, req):
        if self.max_pending is not None and len(self._items) >= self.max_pending:
            return False
        self._items.append(((-req.priority, self._n), req))
        self._n += 1
        return True

    def pop(self, tick):
        ready = [it for it in self._items if it[1].arrival <= tick]
        if not ready:
            return None
        item = min(ready, key=lambda it: it[0])
        self._items.remove(item)
        return item[1]

    def next_arrival(self):
        return min((it[1].arrival for it in self._items), default=None)


def test_admission_queue_property_equivalence():
    """Random submit/pop interleavings: the heap-backed queue must produce
    exactly the old implementation's pop sequence, lengths, and
    next_arrival at every step. Ticks advance monotonically, as the engine's
    do — the heap's future->ready migration is permanent, so equivalence is
    defined (and required) only for non-decreasing ticks."""
    rng = np.random.RandomState(0)
    for trial in range(25):
        cap = [None, 4, 8][trial % 3]
        heap_q, list_q = AdmissionQueue(cap), _ListQueue(cap)
        rid = 0
        tick = 0
        for step in range(60):
            op = rng.rand()
            tick += int(rng.randint(0, 4))      # monotone engine clock
            if op < 0.55:
                req = Request(rid=rid, tokens=(),
                              max_new=1,
                              priority=int(rng.randint(0, 4)),
                              arrival=int(rng.randint(0, 30)))
                rid += 1
                assert heap_q.submit(req) == list_q.submit(req)
            else:
                a, b = heap_q.pop(tick), list_q.pop(tick)
                assert (a.rid if a else None) == (b.rid if b else None), (
                    trial, step, tick)
            assert len(heap_q) == len(list_q)
            assert heap_q.next_arrival() == list_q.next_arrival()


def test_fifo_within_priority_across_arrival_migration():
    """A request submitted first but arriving later must still pop first
    among priority-equals once both are eligible (global FIFO seq)."""
    q = AdmissionQueue()
    q.submit(Request(rid="early-sub-late-arrival", tokens=(), max_new=1,
                     arrival=10))
    q.submit(Request(rid="late-sub-early-arrival", tokens=(), max_new=1,
                     arrival=0))
    assert q.pop(5).rid == "late-sub-early-arrival"   # only one eligible
    q.submit(Request(rid="third", tokens=(), max_new=1, arrival=0))
    assert q.pop(20).rid == "early-sub-late-arrival"  # FIFO by submission
    assert q.pop(20).rid == "third"
    assert q.pop(20) is None


def test_run_fast_forwards_sparse_trace():
    """Sparse arrivals (stagger >> decode length): run() must skip the idle
    stretches via next_arrival() instead of ticking through them, with
    identical tokens and unchanged occupancy accounting."""
    m, params = _model()
    stagger = 50
    reqs = [Request(rid=i, tokens=(np.arange(4) + i) % m.vocab, max_new=3,
                    arrival=i * stagger) for i in range(3)]
    eng = Engine(params, m, n_slots=2, max_len=12)
    comps = eng.run(list(reqs))
    assert len(comps) == 3
    # the idle gaps were fast-forwarded, not stepped: ~2*(50-3) skipped
    assert eng.stats.ff_ticks > 2 * (stagger - 10)
    assert eng.stats.idle_ticks >= eng.stats.ff_ticks
    # tick accounting is unchanged by the skip: the last request arrives at
    # tick 100 and decodes 2 more ticks
    assert eng.stats.ticks >= 2 * stagger + 2
    assert 0.0 < eng.stats.mean_occupancy() <= 1.0
    # tokens identical to a solo engine per request (invariance holds
    # through the fast-forward path)
    for c in comps:
        solo = Engine(params, m, n_slots=2, max_len=12).adopt_compiled(eng)
        ref = solo.run([Request(rid="s", tokens=reqs[c.rid].tokens,
                                max_new=3)])
        assert list(c.tokens) == list(ref[0].tokens)
    # step() burned only ~3 admission+decode ticks' worth of host loops
    assert eng.stats.ticks - eng.stats.ff_ticks < 15


# ---------------------------------------------------------------------------
# chip telemetry through the same registry
# ---------------------------------------------------------------------------


def test_chip_report_publishes_into_registry():
    from repro.core import kan
    from repro.core.quant import ASPConfig
    from repro.hw import chip as chip_lib
    from repro.hw.tiles import TileConfig
    from repro.hw.variation import VariationConfig

    ccfg = chip_lib.ChipConfig(tile=TileConfig(array_size=64, tile_cols=32),
                               variation=VariationConfig(sigma=0.0))
    spec = kan.KANSpec.single(16, 8, ASPConfig(grid_size=4),
                              backend="cim_tiled", cim=ccfg)
    params = kan.init(jax.random.PRNGKey(0), spec)
    deployed = kan.deploy(params, spec)
    report = chip_lib.chip_report(deployed)

    reg = MetricsRegistry()
    chip_lib.publish_report(report, reg)
    snap = reg.snapshot()["metrics"]
    assert snap["chip_tiles_used"]["value"] == report["tiles_used"]
    assert snap["chip_utilization"]["value"] == pytest.approx(
        report["utilization"])
    layer_keys = [k for k in snap if k.startswith("chip_layer_utilization")]
    assert len(layer_keys) == len(report["layers"])
    # the same registry can hold serving metrics: one snapshot, whole stack
    reg.counter("serve_submitted_total").inc()
    assert "serve_submitted_total" in reg.snapshot()["metrics"]


# ---------------------------------------------------------------------------
# sketch twins in EngineStats.report()
# ---------------------------------------------------------------------------


def test_report_sketch_twins_track_numpy_percentiles():
    """The DDSketch twins ``ttft_sketch``/``tpot_sketch`` in
    ``EngineStats.report()`` must track the exact numpy percentiles the
    dashboards already plot. At n=500 the rank-based sketch estimate and
    numpy's interpolated percentile agree to well under the 2% asserted
    here (the documented sketch bound is 1% relative to the rank-based
    order statistic)."""
    from repro.serve.scheduler import EngineStats

    rng = np.random.default_rng(7)
    st = EngineStats(n_slots=2)
    st.ttft_s = list(rng.lognormal(mean=-3.0, sigma=0.8, size=500))
    st.tpot_s = list(rng.lognormal(mean=-5.0, sigma=0.5, size=500))
    st.completed = 500
    rep = st.report()
    for exact_key, sk_key in (("ttft_s", "ttft_sketch"),
                              ("tpot_s", "tpot_sketch")):
        sk = rep[sk_key]
        assert sk["n"] == 500
        assert 0 < sk["alpha"] < 1
        for p in ("p50", "p95", "p99"):
            exact = rep[exact_key][p]
            assert sk[p] == pytest.approx(exact, rel=0.02)


def test_report_sketch_twins_empty_stats():
    from repro.serve.scheduler import EngineStats

    rep = EngineStats(n_slots=1).report()
    assert rep["ttft_sketch"]["n"] == 0
    assert rep["ttft_sketch"]["p95"] is None
    assert rep["tpot_sketch"]["n"] == 0
