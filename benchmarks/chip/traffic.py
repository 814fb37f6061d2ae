"""The one traffic generator. A mix is a data file, ``traffic/<mix>.json``;
this module reads it and yields requests on the wall clock.

Every length comes from a stratified pool: the pool holds the quantiles
``(i + 0.5) / n`` of the mix's distribution, in a random order. Open-loop
arrivals are a Poisson process conditioned on its count: the ramp, the
measured window and the time after it each hold their expected number of
arrivals, at random instants, with a pool of sizes of their own. For the
open-loop kinds that schedule (instants, lengths, session turns and think
times) is drawn once from the mix's ``schedule_seed``; ``--seed`` draws
the token ids (and the program's weights). So every seed offers the same
arrivals and sizes with other contents: the tail latencies of a cell
depend on the schedule, and one drawn per run made seeds differ far more
than runs of one seed. The offline kind permutes its pools by ``--seed``.
Times are seconds after the start of the ramp.

Arrival kinds:

* ``poisson``: open loop at ``rate_per_s`` on average. An optional
  ``burst`` {period_s, length_s, factor} raises the rate by ``factor`` for
  ``length_s`` of every ``period_s`` (the phase is drawn from the seed);
  the base rate is scaled so that the average stays ``rate_per_s``. A
  window that spans whole periods holds the same number of bursts for
  every phase.
* ``sessions``: sessions start open loop at ``rate_per_s``; each runs
  ``turns`` [lo, hi] turns as a closed loop. Turn k+1 is due ``think_s``
  after turn k's last token, and its prompt is the shared system prompt,
  every earlier user message and answer, then the new user message.
* ``offline``: ``backlog`` requests are due at the start, and the queue is
  refilled to ``backlog`` whenever it runs short, so it never empties.

Length specs: ``{"dist": "lognormal", "median", "sigma"}``,
``{"dist": "loguniform"}`` or ``{"dist": "uniform"}``, each with ``min``
and ``max`` (inclusive) and an optional ``round`` (lengths are rounded up
to a multiple of it).
"""
from __future__ import annotations

import dataclasses
import heapq
import math
import statistics
from typing import Dict, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Req:
    rid: int
    due: float                  # seconds after the ramp's start
    tokens: np.ndarray          # int32 prompt
    max_new: int
    session: int = -1
    turn: int = 0


def quantile(spec: dict, u: float) -> int:
    lo, hi = spec["min"], spec["max"]
    dist = spec["dist"]
    if dist == "lognormal":
        v = spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
    elif dist == "loguniform":
        v = lo * (hi / lo) ** u
    elif dist == "uniform":
        v = lo + math.floor(u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    v = min(max(v, lo), hi)
    r = spec.get("round", 1)
    return int(min(math.ceil(v / r) * r, hi))


def pool(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    vals = np.array([quantile(spec, (i + 0.5) / n) for i in range(n)])
    return rng.permutation(vals)


def exp_pool(n: int, rng: np.random.Generator) -> np.ndarray:
    """Unit-mean exponential gaps, stratified and permuted."""
    return rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))


def length_range(spec: dict) -> List[int]:
    """Every length a spec can produce."""
    r = spec.get("round", 1)
    lo = math.ceil(spec["min"] / r) * r
    return list(range(lo, spec["max"] + 1, r))


class Intensity:
    """The arrival rate over time: ``rate`` on average, raised by
    ``factor`` for ``length_s`` of every ``period_s`` from ``phase`` on
    when a burst is given, the base scaled to keep the average."""

    def __init__(self, rate: float, burst: Optional[dict], phase: float):
        self.burst, self.phase = burst, phase
        if burst:
            p, ln, f = burst["period_s"], burst["length_s"], burst["factor"]
            self.base = rate * p / (p - ln + f * ln)
        else:
            self.base = rate

    def cumulative(self, t: float) -> float:
        """Expected arrivals in [0, t]."""
        if not self.burst:
            return self.base * t
        p, ln, f = (self.burst["period_s"], self.burst["length_s"],
                    self.burst["factor"])

        def from_phase(x):              # arrivals in [phase, phase + x]
            k, r = divmod(x, p)
            return self.base * (k * (p + (f - 1) * ln)
                                + r + (f - 1) * min(r, ln))
        return from_phase(t - self.phase) - from_phase(-self.phase)

    def inverse(self, n: float) -> float:
        lo, hi = 0.0, 1.0
        while self.cumulative(hi) < n:
            hi *= 2
        for _ in range(60):
            mid = (lo + hi) / 2
            lo, hi = (mid, hi) if self.cumulative(mid) < n else (lo, mid)
        return hi


class Traffic:
    def __init__(self, mix: dict, seed: int, vocab: int, horizon_s: float,
                 window: Optional[tuple] = None):
        self.mix = mix
        self.arr = mix["arrivals"]
        self.kind = self.arr["kind"]
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        shape = (np.random.default_rng(mix["schedule_seed"])
                 if "schedule_seed" in mix else self.rng)
        self._rid = 0
        self.closed = False
        self._heap: List = []           # (due, rid, Req)
        self.issued: List[Req] = []
        rate = self.arr.get("rate_per_s", 0.0)
        if self.kind == "poisson":
            burst = self.arr.get("burst")
            phase = float(shape.uniform(0, burst["period_s"])) if burst \
                else 0.0
            for t, s, m in self._arrivals(Intensity(rate, burst, phase),
                                          horizon_s, window, True, shape):
                self._push(t, self._draw(int(s)), int(m))
        elif self.kind == "sessions":
            lo, hi = self.arr["turns"]
            starts = [t for t, _, _ in self._arrivals(
                Intensity(rate, None, 0.0), horizon_s, window, False, shape)]
            n = len(starts)
            turns = pool({"dist": "uniform", "min": lo, "max": hi}, n, shape)
            self.user = pool(mix["user"], n * hi, shape)
            self.mnew = pool(mix["max_new"], n * hi, shape)
            self.think = exp_pool(n * hi, shape) * self.arr["think_s"]
            self.system = self._draw(mix["system_prompt"])
            self.sessions: Dict[int, dict] = {}
            self._k = 0                 # next entry of the per-turn pools
            for i, t in enumerate(starts):
                self.sessions[i] = {"turns": int(turns[i]),
                                    "history": self.system}
                self._turn(i, t)
        elif self.kind == "offline":
            self.backlog = self.arr["backlog"]
            self.plen = pool(mix["prompt"], 4096, self.rng)
            self.mnew = pool(mix["max_new"], 4096, self.rng)
        else:
            raise ValueError(f"unknown arrival kind {self.kind!r}")

    def _arrivals(self, intensity: Intensity, horizon: float,
                  window: Optional[tuple], sizes: bool, rng):
        """(due, prompt length, max_new) of the open-loop arrivals, sorted.
        Each stretch (before, in and after the window) holds its expected
        count of arrivals, rounded, at uniform draws of operational time,
        and its own stratified pool of sizes: every seed offers the
        window the same amount of work."""
        edges = [0.0, horizon]
        if window:
            edges = [0.0, window[0], window[0] + window[1],
                     max(horizon, window[0] + window[1])]
        out = []
        for a, b in zip(edges, edges[1:]):
            la, lb = intensity.cumulative(a), intensity.cumulative(b)
            n = int(round(lb - la))
            if n <= 0:
                continue
            ops = np.sort(rng.uniform(la, lb, n))
            plen = pool(self.mix["prompt"], n, rng) if sizes else [0] * n
            mnew = pool(self.mix["max_new"], n, rng) if sizes else [0] * n
            out += [(intensity.inverse(x), s, m)
                    for x, s, m in zip(ops, plen, mnew)]
        return out

    def _draw(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, size=n, dtype=np.int32)

    def _push(self, due: float, tokens, max_new: int, session=-1, turn=0):
        req = Req(self._rid, due, tokens, max_new, session, turn)
        self._rid += 1
        heapq.heappush(self._heap, (due, req.rid, req))

    def _turn(self, sid: int, due: float) -> None:
        s = self.sessions[sid]
        k = self._k % len(self.user)
        self._k += 1
        s["prompt"] = np.concatenate([s["history"],
                                      self._draw(int(self.user[k]))])
        s["done"] = s.get("done", 0)
        s["think"] = float(self.think[k])
        self._push(due, s["prompt"], int(self.mnew[k]), sid, s["done"] + 1)

    def close(self) -> None:
        """Offer nothing more: drop what is pending, end every session."""
        self._heap.clear()
        self.closed = True

    def pop_due(self, now: float, queued: int = 0) -> List[Req]:
        """Requests due at or before ``now``. ``queued`` is the number of
        requests waiting for a slot (the offline mix refills to its
        backlog)."""
        if self.kind == "offline" and not self.closed:
            for _ in range(max(0, self.backlog - queued)):
                k = self._rid % len(self.plen)
                self._push(now, self._draw(int(self.plen[k])),
                           int(self.mnew[k]))
        out = []
        while self._heap and self._heap[0][0] <= now:
            out.append(heapq.heappop(self._heap)[2])
        self.issued += out
        return out

    def next_due(self) -> Optional[float]:
        return self._heap[0][0] if self._heap else None

    def on_complete(self, req: Req, served: np.ndarray, now: float) -> None:
        """A request's last token arrived at ``now``: a session schedules
        its next turn."""
        if self.kind != "sessions" or req.session < 0 or self.closed:
            return
        s = self.sessions[req.session]
        s["done"] += 1
        if s["done"] >= s["turns"]:
            return
        s["history"] = np.concatenate([s["prompt"],
                                       np.asarray(served, np.int32)])
        self._turn(req.session, now + s["think"])

    def prompt_lengths(self) -> List[int]:
        """Every prompt length the mix can produce."""
        if self.kind == "sessions":
            step = self.mix["user"].get("round", 1)
            lo = self.mix["system_prompt"] + self.mix["user"]["min"]
            hi = (self.mix["system_prompt"] + self.arr["turns"][1]
                  * (self.mix["user"]["max"] + self.mix["max_new"]["max"]))
            return list(range(lo, hi + 1, step))
        return length_range(self.mix["prompt"])


def describe(reqs: List[Req], seconds: float) -> dict:
    """Realised lengths and offered rate of the requests due in a window."""
    if not reqs:
        return {"requests": 0}
    pl = np.array([len(r.tokens) for r in reqs])
    mn = np.array([r.max_new for r in reqs])

    def q(a):
        return {"p50": int(np.percentile(a, 50)),
                "p90": int(np.percentile(a, 90)),
                "min": int(a.min()), "max": int(a.max())}
    out = {"requests": len(reqs), "offered_per_s": len(reqs) / seconds,
           "prompt": q(pl), "max_new": q(mn)}
    if any(r.session >= 0 for r in reqs):
        out["sessions"] = len({r.session for r in reqs})
        out["later_turns"] = sum(r.turn > 1 for r in reqs)
    return out
