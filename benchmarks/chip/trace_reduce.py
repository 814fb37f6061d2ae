"""From a profiler trace to per-layer numbers and the ``breakdown``.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists; ``reduce`` works on those lists alone, so a small recorded trace
(``tests/data``) checks it on the CPU.

* busy: the union of the intervals in which an operation ran on a device
  (the device plane's ``XLA Ops`` line), inside the window that the
  harness marks with its ``harness.window`` span; idle is the rest.
* programs: device time and calls per role, from the device plane's
  ``XLA Modules`` line. The engine's programs are jitted partials, which
  the trace names ``jit__unknown(<fingerprint>)``, so a name alone does
  not say which is which. The harness's ticks do: in a tick that ran
  ``k`` prefill chunks and a decode, the ``k + 1`` longest modules that
  start inside it are the chunks and then, last, the decode. Each
  fingerprint takes the role most of its calls had (``decode``,
  ``prefill_chunk``); a module no tick claims keeps its own name without
  ``jit_`` and the fingerprint.
* device ops: own label ``<program>: <op>``, or ``<program>/<scope>: <op>``
  where the op carries its name path (``trace_phases.load``), the scope
  being the first of ``SCOPES`` on the path, else ``unscoped``.
* idle gaps: each gap between busy intervals is put down to the harness
  span (``harness.*``, ``engine.step``) that covers most of it.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional

WINDOW_SPAN = "harness.window"
HOST_PREFIXES = ("harness.", "engine.")
#: the model step's ``jax.named_scope`` names that partition its ops
SCOPES = ("mixer", "cache_update", "ffn", "unembed")
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?:/|$)")
_SUFFIX = re.compile(r"\(\d+\)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def scope_of(path: str) -> str:
    """The first of ``SCOPES`` on an op's name path, else ``unscoped``."""
    m = _SCOPE.search(path or "")
    return m.group(1) if m else "unscoped"


def program_name(module: str) -> str:
    name = _SUFFIX.sub("", module)
    return name[4:] if name.startswith("jit_") else name


def load(path: str) -> dict:
    """The device planes' op and module events and the harness's host
    spans, keeping only device events that overlap the window span."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    host = [[e.name, e.start_ns, e.duration_ns]
            for plane in planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(HOST_PREFIXES)]
    win = [h for h in host if h[0] == WINDOW_SPAN]
    lo, hi = ((win[0][1], win[0][1] + win[0][2]) if win
              else (float("-inf"), float("inf")))
    devices = []
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        dev = {"name": plane.name, "ops": [], "modules": []}
        for line in plane.lines:
            key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
            if key:
                dev[key] = [[e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.start_ns < hi and e.end_ns > lo]
        if dev["ops"] or dev["modules"]:
            devices.append(dev)
    return {"devices": devices, "host": host}


def union(intervals: List[List[float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(events, lo, hi):
    for name, start, dur, *_ in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, start, a, b


def roles(modules, ticks, lo) -> Dict[str, str]:
    """Fingerprint -> role, by the harness's ticks. ``ticks`` holds
    (start_s, end_s, n_chunks, decoded) relative to the window's start
    ``lo`` (ns). In each tick the ``n_chunks + decoded`` longest modules
    that start inside it vote: the last for ``decode``, the others for
    ``prefill_chunk``. The engine has one decode program: the fingerprint
    with the most decode votes. A chunk program is one that most of its
    own calls voted for; the engine's small eager ops rarely do."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    calls: Dict[str, int] = {}
    for m in mods:
        calls[m[0]] = calls.get(m[0], 0) + 1
    decode: Dict[str, int] = {}
    chunk: Dict[str, int] = {}
    for t0, t1, n_chunks, decoded in ticks:
        a, b = lo + t0 * 1e9, lo + t1 * 1e9
        inside = mods[bisect.bisect_left(starts, a):
                      bisect.bisect_right(starts, b)]
        n = n_chunks + bool(decoded)
        if not n or len(inside) < n:
            continue
        picked = sorted(sorted(inside, key=lambda m: -m[2])[:n],
                        key=lambda m: m[1])
        for i, m in enumerate(picked):
            votes = decode if decoded and i == n - 1 else chunk
            votes[m[0]] = votes.get(m[0], 0) + 1
    out = {name: "prefill_chunk" for name, v in chunk.items()
           if v > calls[name] / 2}
    if decode:
        out[max(decode, key=decode.get)] = "decode"
    return out


def op_label(name: str) -> str:
    """``%convert.16 = bf16[48,2048]{1,0:T(8,128)} convert(...)`` ->
    ``%convert.16 = bf16[48,2048] convert``."""
    head, _, rest = name.partition(" = ")
    rest = _LAYOUT.sub("", rest)
    if rest.startswith("("):                # a tuple-shaped result
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:]
    else:
        shape, _, rest = rest.partition(" ")
    opcode = rest.strip().split("(", 1)[0]
    return f"{head} = {shape} {opcode}"[:120] if shape else head[:120]


def reduce(ev: dict, ticks=None, top: int = 10) -> Optional[dict]:
    """Per-layer numbers of a loaded trace; None when no device ran.
    ``ticks`` (see ``roles``) names the engine's programs."""
    if not ev["devices"]:
        return None
    win = [h for h in ev["host"] if h[0] == WINDOW_SPAN]
    if win:
        lo, hi = win[0][1], win[0][1] + win[0][2]
    else:
        spans = [e for d in ev["devices"] for e in d["ops"] + d["modules"]]
        lo = min(s for _, s, _ in spans)
        hi = max(s + d for _, s, d in spans)
    window_ns = hi - lo
    spans = [h for h in ev["host"] if h[0] != WINDOW_SPAN]
    busy_ns, programs, ops, gaps = [], {}, {}, []
    role = roles(ev["devices"][0]["modules"], ticks or [], lo)
    for dev in ev["devices"]:
        events = dev["ops"] or dev["modules"]
        busy = union([[a, b] for _, _, a, b in _clip(events, lo, hi)])
        busy_ns.append(sum(b - a for a, b in busy))
        for name, start, a, b in _clip(dev["modules"], lo, hi):
            p = programs.setdefault(role.get(name) or program_name(name),
                                    {"calls": 0, "device_s": 0.0})
            p["device_s"] += (b - a) / len(ev["devices"]) * 1e-9
            if start >= lo:
                p["calls"] += 1 / len(ev["devices"])
        mods = sorted(dev["modules"], key=lambda m: m[1])
        mstart = [m[1] for m in mods]
        labels: Dict[str, str] = {}
        for name, start, dur, *path in dev["ops"]:
            a, b = max(start, lo), min(start + dur, hi)
            if b <= a:
                continue
            i = bisect.bisect_right(mstart, start) - 1
            owner = mods[i][0] if i >= 0 else ""
            if name not in labels:
                labels[name] = op_label(name)
            program = role.get(owner) or program_name(owner)
            if path:
                program += "/" + scope_of(path[0])
            label = program + ": " + labels[name]
            ops[label] = ops.get(label, 0.0) + (b - a) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    spans.sort(key=lambda h: h[1])
    ends = [s + d for _, s, d in spans]
    idle_by_host: Dict[str, float] = {}
    labelled = []
    for length, a, b in gaps:
        label = attribute(spans, ends, a, b)
        idle_by_host[label] = idle_by_host.get(label, 0.0) + length * 1e-9
        labelled.append([label, length * 1e-9])
    busy_s = sum(busy_ns) / len(busy_ns) * 1e-9
    return {
        "busy_s": busy_s,
        "window_s": window_ns * 1e-9,
        "idle_share": 1.0 - busy_s / (window_ns * 1e-9),
        "programs": programs,
        "idle_by_host": idle_by_host,
        "breakdown": {
            "device_ops": sorted(([k, v] for k, v in ops.items()),
                                 key=lambda kv: -kv[1])[:top],
            "idle_gaps": labelled[:top],
        },
    }


def attribute(spans, ends, a: float, b: float) -> str:
    """The host span that overlaps [a, b] the most, else ``host.other``.
    ``spans`` are disjoint and sorted by start; ``ends`` are their ends."""
    best, label = 0.0, "host.other"
    for i in range(bisect.bisect_right(ends, a), len(spans)):
        name, start, dur = spans[i]
        if start >= b:
            break
        ov = min(b, start + dur) - max(a, start)
        if ov > best:
            best, label = ov, name
    return label
