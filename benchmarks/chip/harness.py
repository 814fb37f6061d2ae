"""The benchmark's machinery: find a cell's files, build the program from
the seed, warm it up, drive it open loop on the wall clock, and check what
it served against the plain reference.

The program is driven only through ``get_arch``, ``init_model``,
``Engine(...)``, ``Engine.submit``, ``Engine.step`` and the engine's
public per-slot state (``slot_req``, ``slot_tokens``, ``slot_pos``,
``slot_admitted``, ``index``, ``prefilling``, ``stats``, ``queue``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
REF_BUCKET = 512            # the reference pads sequences to a multiple
SAMPLE_TOKENS = 320         # served tokens the check compares, at least
SAMPLE_MIN = 6              # requests it compares, at least
SAMPLE_MAX = 12             # requests it compares, at most
DRAIN_S = 150.0             # longest wait for the window's requests

clock = time.perf_counter


def load_json(*parts) -> dict:
    with open(BENCH.joinpath(*parts)) as f:
        return json.load(f)


def load_module(subdir: str, name: str):
    """Import ``<subdir>/<name>.py`` of the benchmark (names may hold dots)."""
    path = BENCH / subdir / f"{name}.py"
    if str(path.parent) not in sys.path:
        sys.path.insert(0, str(path.parent))
    spec = importlib.util.spec_from_file_location(
        f"bench_{subdir}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict             # the workload's entry in BENCHMARK.json
    conf: dict              # configs/<config>.json
    mix: dict               # traffic/<traffic>.json
    bench: dict

    @property
    def model(self) -> dict:
        return self.conf["model"]

    @property
    def open_loop(self) -> bool:
        return self.mix["arrivals"]["kind"] != "offline"

    def metrics(self, section: str) -> List[dict]:
        """The cell's metrics of ``section`` (end_to_end or per_layer)."""
        return [m for m in self.bench[section]
                if self.name in m.get("workloads", [self.name])]


def load_cell(name: str) -> Cell:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    return Cell(name, entry, load_json("configs", entry["config"] + ".json"),
                load_json("traffic", entry["traffic"] + ".json"), bench)


# -- the program ------------------------------------------------------------

def import_program():
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401  (fails in a checkout without the program)


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file, checked against
    every size the file states."""
    import jax.numpy as jnp
    from repro.configs import get_arch
    arch = get_arch(conf["arch"], smoke=conf.get("smoke", False))
    cfg = dataclasses.replace(arch.model,
                              **conf["overrides"])
    derived = {"ssm_expand": lambda c: c.ssd_cfg.expand,
               "ssm_conv_width": lambda c: c.ssd_cfg.conv_width}
    for key, want in conf["model"].items():
        if key in derived:
            got = derived[key](cfg)
        elif key in ("dtype", "param_dtype"):
            got, want = jnp.dtype(getattr(cfg, key)).name, want
        elif hasattr(cfg, key):
            got = getattr(cfg, key)
        else:
            continue                     # e.g. norm_eps: fixed in the code
        if got != want:
            raise ValueError(f"{conf['name']}: the program runs {key}={got!r}"
                             f" but the configuration states {want!r}")
    return cfg


def init_params(cfg, seed: int):
    """Random weights from the seed, made on the device in one program."""
    import jax
    from repro.models import transformer as tfm
    common = load_module("reference", "_common")
    params = jax.jit(functools.partial(tfm.init_model, cfg=cfg))(
        common.model_key(seed))
    jax.block_until_ready(params)
    return params


def make_engine(cell: Cell, params, cfg, recorder=None):
    """The cell's engine; ``recorder`` (a ``repro.obs.EngineRecorder``)
    marks its phases in the profiler's trace."""
    from repro.serve.engine import Engine
    geo = cell.mix["engine"]
    return Engine(params, cfg, n_slots=geo["n_slots"], max_len=geo["max_len"],
                  page_size=cell.conf.get("engine", {}).get("page_size", 64),
                  recorder=recorder)


def chunk_keys(length: int, start: int, chunk: int):
    keys, pos = [], start
    while pos < length:
        n = min(chunk, length - pos)
        keys.append((n, pos == 0, pos + n == length))
        pos += n
    return keys


def warmup_prompts(lengths: List[int], chunk: int, page: int,
                   share: bool) -> List[int]:
    """Prompt lengths, drawn from those the mix can produce, that together
    run every chunk program the mix can run."""
    need = {}
    for n in lengths:
        starts = range(0, n, page) if share else (0,)
        for s in starts:
            for k in chunk_keys(n, s, chunk):
                need.setdefault(k, n)
    chosen, covered = [], set()
    for k, n in sorted(need.items(), key=lambda kv: -kv[1]):
        if k in covered:
            continue
        chosen.append(n)
        covered.update(chunk_keys(n, 0, chunk))
        if share:               # a repeat of the prompt starts after a hit
            for s in range(page, n, page):
                covered.update(chunk_keys(n, s, chunk))
    return sorted(chosen)


class Compiles:
    """Counts programs lowered while ``armed`` (jit cache misses)."""
    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",)

    def __init__(self):
        import jax.monitoring
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.count += 1


class GcPauses:
    """The longest garbage collection of the interpreter while ``armed``
    (a host stall the engine's steps cannot show by themselves)."""

    def __init__(self):
        self.armed = False
        self.longest = 0.0
        self._start = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._start = clock()
        elif self.armed:
            self.longest = max(self.longest, clock() - self._start)

    def close(self) -> None:
        gc.callbacks.remove(self._on)


# -- the driver -------------------------------------------------------------

@dataclasses.dataclass
class Rec:
    req: object             # traffic.Req
    due: float              # absolute, on ``clock``
    submit: float = math.nan
    admit: float = math.nan
    tokens: List = dataclasses.field(default_factory=list)   # (t, count)
    done: float = math.nan
    served: Optional[np.ndarray] = None


@dataclasses.dataclass
class Tick:
    t0: float
    t1: float
    n_out: int              # tokens produced (first tokens included)
    n_dec: int              # tokens from the fused decode
    ctx_sum: int            # keys attended by those decode tokens
    chunks: List            # (start, length, last) prompt chunks run


class Driver:
    """Offers a ``Traffic`` to an ``Engine`` on the wall clock and records
    what happens, with the harness's own spans when ``spans`` is on."""

    def __init__(self, eng, traffic, t0: float, spans: bool = False):
        self.eng, self.traffic, self.t0 = eng, traffic, t0
        self.recs: Dict[int, Rec] = {}
        self.ticks: List[Tick] = []
        self.tick_wall: Dict[int, float] = {}
        self.seen = [(None, 0)] * eng.n_slots     # (rid, tokens) per slot
        self.decoded = eng.stats.decode_tokens
        self.chunk = eng.chunk_tokens
        if spans:
            import jax
            self.span = jax.profiler.TraceAnnotation
        else:
            self.span = lambda name: contextlib.nullcontext()

    def idle(self) -> bool:
        e = self.eng
        return not (e.active.any() or e.prefilling.any() or len(e.queue))

    def offer(self, now: float) -> None:
        from repro.serve.scheduler import Request
        with self.span("harness.generate"):
            due = self.traffic.pop_due(now - self.t0, len(self.eng.queue))
        for r in due:
            rec = Rec(r, self.t0 + r.due)
            with self.span("harness.submit"):
                self.eng.submit(Request(rid=r.rid, tokens=r.tokens,
                                        max_new=r.max_new,
                                        arrival=self.eng.tick_no))
            rec.submit = clock()
            self.recs[r.rid] = rec

    def step(self) -> None:
        e = self.eng
        tick = e.tick_no
        was_prefilling = e.prefilling.copy()
        before = [None if r is None else r.rid for r in e.slot_req]
        t0 = clock()
        self.tick_wall[tick] = t0
        with self.span("engine.step"):
            done = e.step()
        t1 = clock()
        with self.span("harness.readback"):
            self._record(tick, t0, t1, was_prefilling, before, done)

    def _record(self, tick, t0, t1, was_prefilling, before, done):
        e = self.eng
        chunks, n_out, ctx = [], 0, 0
        for slot, req in enumerate(e.slot_req):
            if req is None:
                continue
            rec = self.recs[req.rid]
            if before[slot] != req.rid:                 # admitted this tick
                rec.admit = self.tick_wall[int(e.slot_admitted[slot])]
            if (was_prefilling[slot] and before[slot] == req.rid) or \
                    before[slot] != req.rid:
                pos = int(e.slot_pos[slot])
                start = (pos - 1) // self.chunk * self.chunk
                chunks.append((start, pos - start, pos == len(req.tokens)))
            if e.active[slot]:
                ctx += int(e.index[slot])
            n = len(e.slot_tokens[slot])
            prev = self.seen[slot][1] if self.seen[slot][0] == req.rid else 0
            if n > prev:
                rec.tokens.append((t1, n - prev))
                n_out += n - prev
            self.seen[slot] = (req.rid, n)
        for c in done:
            rec = self.recs[c.rid]
            prev = self.seen[c.slot][1] if self.seen[c.slot][0] == c.rid else 0
            if len(c.tokens) > prev:
                rec.tokens.append((t1, len(c.tokens) - prev))
                n_out += len(c.tokens) - prev
            self.seen[c.slot] = (None, 0)
            ctx += len(rec.req.tokens) + len(c.tokens) - 1
            rec.done, rec.served = t1, np.asarray(c.tokens)
            self.traffic.on_complete(rec.req, rec.served, t1 - self.t0)
        n_dec = e.stats.decode_tokens - self.decoded
        self.decoded = e.stats.decode_tokens
        self.ticks.append(Tick(t0, t1, n_out, n_dec, ctx, chunks))

    def run(self, until) -> None:
        """Offer and step until ``until(now)`` holds. When nothing is in
        flight, sleep to the next due time instead of stepping."""
        while True:
            now = clock()
            self.offer(now)
            if until(now):
                return
            if self.idle():
                nxt = self.traffic.next_due()
                wait = 0.05 if nxt is None else self.t0 + nxt - now
                with self.span("harness.sleep"):
                    time.sleep(min(max(wait, 0.0), 0.05))
                continue
            self.step()


def warm_up(eng, lengths: List[int], vocab: int) -> int:
    """Run one short request of each warm-up length through the engine, so
    that every program the window can call is compiled and has run."""
    from repro.serve.scheduler import Request
    rng = np.random.default_rng(0)
    prompts = warmup_prompts(lengths, eng.chunk_tokens, eng.page_size,
                             eng.share_ok)
    for i, n in enumerate(prompts):
        eng.submit(Request(rid=f"warm{i}", max_new=2,
                           tokens=rng.integers(0, vocab, n, dtype=np.int32)))
    while eng.active.any() or eng.prefilling.any() or len(eng.queue):
        eng.step()
    return len(prompts)


def stats_snapshot(eng) -> dict:
    s = eng.stats
    return {k: getattr(s, k) for k in (
        "ticks", "idle_ticks", "occupancy_ticks", "decode_tokens",
        "prefill_chunks", "prefix_hit_pages", "prefix_eligible_pages",
        "completed", "prefills", "rejected")}


# -- the check --------------------------------------------------------------

def pick_sample(finished: List[Rec], seed: int) -> List[Rec]:
    """The requests the check compares: the one with the most served
    tokens, the one with the longest prompt, then others drawn from the
    seed until there are ``SAMPLE_MIN`` requests and ``SAMPLE_TOKENS``
    served tokens, or ``SAMPLE_MAX`` requests."""
    if not finished:
        return []
    by_served = max(finished, key=lambda r: (len(r.served), len(r.req.tokens)))
    by_prompt = max(finished, key=lambda r: (len(r.req.tokens), len(r.served)))
    out = [by_served] + ([by_prompt] if by_prompt is not by_served else [])
    rest = [r for r in finished if r not in out]
    order = np.random.default_rng(seed).permutation(len(rest))
    for i in order:
        enough = (sum(len(r.served) for r in out) >= SAMPLE_TOKENS
                  and len(out) >= SAMPLE_MIN)
        if enough or len(out) >= SAMPLE_MAX:
            break
        out.append(rest[i])
    return out


def reference_gaps(cell: Cell, seed: int, sample: List[Rec],
                   control: bool = False) -> List[dict]:
    """Run the configuration's plain reference over each sampled prompt and
    its served tokens."""
    ref = load_module("reference", cell.conf["family"])
    seqs = [np.concatenate([r.req.tokens, r.served]) for r in sample]
    n = [len(r.served) for r in sample]
    bucket = -(-(cell.mix["engine"]["max_len"] + 1) // REF_BUCKET) * REF_BUCKET
    return ref.evaluate(cell.model, seed, seqs, n, bucket, control=control)
