"""From a profiler trace to the engine's phases and the model step's scopes.

``trace_reduce`` puts each idle gap down to the harness span that covers
most of it and assumes the spans are disjoint. An engine built with a
recorder (``repro.obs.EngineRecorder``) marks the phases of its tick as
``engine.<phase>`` spans nested inside the harness's ``engine.step``, and
its programs carry ``jax.named_scope`` paths in their op metadata. This
module reads both, and its ``reduce`` is the reduction a traced run uses:

* ``load``: ``trace_reduce.load``'s lists, each device op with a fourth
  field, the op's name path. ``jax.profiler.ProfileData`` shows an
  event's own stats only, and a TPU op's path is a stat of its event
  metadata, so ``op_stats`` reads those from the ``.xplane.pb`` itself.
* ``attribute``: the innermost span under which most of an idle gap lies.
* ``reduce``: ``trace_reduce.reduce``'s numbers (busy, programs, the
  breakdown's device ops, labelled by scope), with the breakdown's idle
  gaps labelled by innermost span instead; beside them idle seconds by
  innermost host span (exact, instant by instant), ``host_gap_ms``
  (device-idle ms per engine step under the engine's own phase spans;
  ``engine.step``'s remainder is the harness's and is left out) and device
  ms per call of each program from each op's own time (its duration less
  that of the ops it contains, as a loop holds its body): ``scope_ms`` by
  scope (``mixer``, ``cache_update``, ``ffn``, ``unembed``, else
  ``unscoped``), a partition, and ``by_name`` by every segment of the ops'
  name paths, so that a scope nested in another (``kan_ffn`` in ``ffn``)
  or added later is read by name.

Everything reads None or empty where the program has no such span or scope.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

import trace_reduce as tr
from trace_reduce import SCOPES, scope_of

#: stats of a device op's metadata that may carry its name path
SCOPE_STATS = ("tf_op",)
STEP_SPAN = "engine.step"
_OP_TYPE = re.compile(r":[^/]*$")     # ``name:OpType`` at a path's end


def op_path(stats: Dict[str, str]) -> str:
    """The name path among an op's stats: the first of ``SCOPE_STATS``,
    else the first value that reads like one (``jit(<name>)/...`` or one
    holding a scope)."""
    for k in SCOPE_STATS:
        if k in stats:
            return stats[k]
    return next((v for v in stats.values()
                 if v.startswith("jit(") or scope_of(v) != "unscoped"), "")


def _varint(buf, i: int):
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of a protobuf message: an int for a varint, a
    memoryview for any other wire type."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        else:                               # fixed64 (1) or fixed32 (5)
            size = 8 if kind == 1 else 4
            val, i = buf[i:i + size], i + size
        yield key >> 3, val


def _map_values(entry) -> Tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for num, val in _fields(entry):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def op_stats(data: bytes) -> Dict[str, Dict[str, Dict[str, str]]]:
    """Per device plane of a serialized ``XSpace``: event name -> the string
    stats of its event metadata (``XPlane.event_metadata``; a stat's name
    and a referenced value come from ``XPlane.stat_metadata``)."""
    out: Dict[str, Dict[str, Dict[str, str]]] = {}
    for num, plane in _fields(memoryview(data)):
        if num != 1:                        # XSpace.planes
            continue
        name, stat_names, metas = "", {}, []
        for f, val in _fields(plane):
            if f == 2:                      # XPlane.name
                name = bytes(val).decode()
            elif f == 4:                    # XPlane.event_metadata
                metas.append(_map_values(val)[1])
            elif f == 5:                    # XPlane.stat_metadata
                sid, meta = _map_values(val)
                stat_names[sid] = next((bytes(v).decode() for k, v in
                                        _fields(meta) if k == 2), "")
        if not name.startswith("/device:"):
            continue
        events = out.setdefault(name, {})
        for meta in metas:
            ev_name, stats = "", {}
            for f, val in _fields(meta):
                if f == 2:                  # XEventMetadata.name
                    ev_name = bytes(val).decode()
                elif f == 5:                # XEventMetadata.stats
                    sid, text = 0, None
                    for k, v in _fields(val):
                        if k == 1:
                            sid = v
                        elif k == 5:        # str_value
                            text = bytes(v).decode()
                        elif k == 7:        # ref_value
                            text = stat_names.get(v, "")
                    if text is not None:
                        stats[stat_names.get(sid, str(sid))] = text
            events[ev_name] = stats
    return out


def load(path: str) -> dict:
    """``trace_reduce.load``'s lists, each op as ``[name, start, duration,
    path]``: the host's ``harness.*`` and ``engine.*`` spans and the device
    events that overlap the window span."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    with open(path, "rb") as f:
        paths = {plane: {ev: op_path(st) for ev, st in events.items()}
                 for plane, events in op_stats(f.read()).items()}
    host = [[e.name, e.start_ns, e.duration_ns]
            for plane in planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(tr.HOST_PREFIXES)]
    win = [h for h in host if h[0] == tr.WINDOW_SPAN]
    lo, hi = ((win[0][1], win[0][1] + win[0][2]) if win
              else (float("-inf"), float("inf")))
    devices = []
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        dev = {"name": plane.name, "ops": [], "modules": []}
        named = paths.get(plane.name, {})
        for line in plane.lines:
            if line.name == "XLA Ops":
                dev["ops"] = [[e.name, e.start_ns, e.duration_ns,
                               named.get(e.name, "")] for e in line.events
                              if e.start_ns < hi and e.end_ns > lo]
            elif line.name == "XLA Modules":
                dev["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                  for e in line.events
                                  if e.start_ns < hi and e.end_ns > lo]
        if dev["ops"] or dev["modules"]:
            devices.append(dev)
    return {"devices": devices, "host": host}


def attribute(spans, a: float, b: float) -> str:
    """The innermost span under which most of [a, b] lies: each instant
    goes to the innermost span covering it (see ``split``), and the gap to
    the span that took the most. ``spans`` are ``[name, start, duration]``
    and may nest."""
    share: Dict[str, float] = {}
    split(spans, a, b, share)
    return max(share, key=share.get)


class _Spans:
    """Host spans sorted by start, for the ones that overlap an interval."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda h: h[1])
        self.starts = [h[1] for h in self.spans]
        self.longest = max((h[2] for h in self.spans), default=0.0)

    def over(self, a: float, b: float):
        i = bisect.bisect_left(self.starts, a - self.longest)
        j = bisect.bisect_left(self.starts, b)
        return [h for h in self.spans[i:j] if h[1] + h[2] > a]


def split(spans, a: float, b: float, out: Dict[str, float]) -> None:
    """Add [a, b] to ``out`` instant by instant, each to the innermost
    (shortest) span covering it, else to ``host.other``."""
    cuts = sorted({a, b} | {x for _, s, d in spans for x in (s, s + d)
                            if a < x < b})
    for x, y in zip(cuts, cuts[1:]):
        mid = (x + y) / 2
        cover = [h for h in spans if h[1] <= mid < h[1] + h[2]]
        name = min(cover, key=lambda h: h[2])[0] if cover else "host.other"
        out[name] = out.get(name, 0.0) + (y - x) * 1e-9


def own_time(ops) -> List[float]:
    """Each op's duration less that of the ops it contains (a ``while``
    holds its body's ops, a fusion none): ``ops`` sorted by start."""
    own = [float(o[2]) for o in ops]
    stack: List[int] = []
    for i, (_, start, dur, *_) in enumerate(ops):
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= start:
            stack.pop()
        if stack and start + dur <= ops[stack[-1]][1] + ops[stack[-1]][2]:
            own[stack[-1]] -= dur
        stack.append(i)
    return own


def program_role(module: str) -> Optional[str]:
    """``decode`` or ``prefill_chunk`` for the engine's named programs."""
    name = tr.program_name(module)
    if name == "decode_tick":
        return "decode"
    if name.startswith(("prefill_chunk", "prefill_len")):
        return "prefill_chunk"
    return None


def _path(op) -> str:
    return op[3] if len(op) > 3 else ""


def scope_ms(ev: dict, lo: float, hi: float):
    """Device ms per call of each engine program that ran wholly inside
    [lo, hi], from its ops' own time, as ``(by_scope, by_name)``.
    ``by_scope[role]`` partitions it over ``SCOPES`` and ``unscoped``
    (every key present), with ``calls`` and ``device_ms`` (module time per
    call) beside; ``by_name[role]`` holds each segment of the ops' name
    paths, an op counted once in every segment on its path. Both are empty
    where no op carries a path (the program has no scopes)."""
    by_scope: Dict[str, Dict[str, float]] = {}
    by_name: Dict[str, Dict[str, float]] = {}
    if not any(_path(op) for d in ev["devices"] for op in d["ops"]):
        return by_scope, by_name
    n_dev = len(ev["devices"])
    for dev in ev["devices"]:
        mods = sorted(dev["modules"], key=lambda m: m[1])
        mstart = [m[1] for m in mods]
        roles = [program_role(name) if lo <= start and start + dur <= hi
                 else None for name, start, dur in mods]
        for (_, _, dur), role in zip(mods, roles):
            if role:
                r = by_scope.setdefault(role, dict.fromkeys(
                    ("calls", "device_ms", *SCOPES, "unscoped"), 0.0))
                r["calls"] += 1 / n_dev
                r["device_ms"] += dur * 1e-6 / n_dev
        ops = sorted(dev["ops"], key=lambda o: (o[1], -o[2]))
        for op, own in zip(ops, own_time(ops)):
            i = bisect.bisect_right(mstart, op[1]) - 1
            if i < 0 or not roles[i]:
                continue
            ms = own * 1e-6 / n_dev
            path = _path(op)
            by_scope[roles[i]][scope_of(path)] += ms
            names = by_name.setdefault(roles[i], {})
            for seg in set(_OP_TYPE.sub("", path).split("/")) - {""}:
                names[seg] = names.get(seg, 0.0) + ms
    for role, r in by_scope.items():
        calls = r["calls"]
        for k in r:
            if k != "calls":
                r[k] /= calls
        for k in by_name.get(role, {}):
            by_name[role][k] /= calls
    return by_scope, by_name


def reduce(ev: dict, ticks=None, top: int = 10) -> Optional[dict]:
    """``trace_reduce.reduce(ev, ticks, top)`` with the breakdown's idle
    gaps labelled by innermost span, and beside it idle time by innermost
    host span, ``host_gap_ms`` and the programs' device ms per call by
    scope and by name; None when no device ran."""
    red = tr.reduce(ev, ticks, top)
    if red is None:
        return None
    win = [h for h in ev["host"] if h[0] == tr.WINDOW_SPAN]
    if win:
        lo, hi = win[0][1], win[0][1] + win[0][2]
    else:
        spans = [e for d in ev["devices"] for e in d["ops"] + d["modules"]]
        lo = min(e[1] for e in spans)
        hi = max(e[1] + e[2] for e in spans)
    host = _Spans([h for h in ev["host"] if h[0] != tr.WINDOW_SPAN])
    idle: Dict[str, float] = {}
    gaps = []
    for dev in ev["devices"]:
        events = dev["ops"] or dev["modules"]
        busy = tr.union([[a, b] for _, _, a, b in tr._clip(events, lo, hi)])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                share: Dict[str, float] = {}
                split(host.over(a, b), a, b, share)
                for k, v in share.items():
                    idle[k] = idle.get(k, 0.0) + v
                gaps.append([max(share, key=share.get), (b - a) * 1e-9])
    n_dev = len(ev["devices"])
    idle = {k: v / n_dev for k, v in idle.items()}
    gaps.sort(key=lambda g: -g[1])
    red["breakdown"]["idle_gaps"] = gaps[:top]
    steps = sum(1 for h in host.spans if h[0] == STEP_SPAN and lo <= h[1] < hi)
    phase_s = sum(v for k, v in idle.items() if _is_phase(k))
    by_scope, by_name = scope_ms(ev, lo, hi)
    red.update({
        "idle_by_span": idle,
        "idle_gaps": gaps[:top],
        "steps": steps,
        "host_gap_ms": 1e3 * phase_s / steps if phase_s and steps else None,
        # shares of the idle seconds inside engine.step that an engine
        # phase takes: instant by instant, and by whole gap labels
        "phase_share": _phase_share(idle.items()),
        "phase_share_gaps": _phase_share(gaps),
        "scope_ms": by_scope,
        "by_name": by_name,
    })
    return red


def _is_phase(name: str) -> bool:
    return name.startswith("engine.") and name != STEP_SPAN


def _phase_share(pairs) -> Optional[float]:
    phase = sum(v for k, v in pairs if _is_phase(k))
    step = phase + sum(v for k, v in pairs if k == STEP_SPAN)
    return phase / step if phase and step > 0 else None
