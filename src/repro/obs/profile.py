"""Profiling hooks for jitted callables: compile events + cost analysis.

The serving engine compiles one prefill executable **per distinct prompt
length** and one fused decode tick — today those compiles are silent, so a
trace with many distinct lengths quietly spends most of its wall time in
XLA. ``JitProfiler`` wraps a ``jax.jit`` callable and makes that visible:

* the first call for a distinct argument-shape key AOT-compiles via
  ``fn.lower(*args).compile()`` and records a :class:`CompileEvent` —
  wall-clock compile seconds plus, where ``Compiled.cost_analysis`` works,
  the estimated FLOPs and bytes-accessed of the executable;
* subsequent calls with the same shapes dispatch the cached executable
  (donation declared on the wrapped jit is honored — AOT compiles inherit
  ``donate_argnums``).

Events flow into a recorder (anything with ``on_compile(event)`` — see
``repro.obs.recorder``), which turns them into registry metrics
(``compile_total`` / ``compile_seconds`` / ``compiled_flops`` per callable)
and trace spans. ``roofline_rows(snapshot)`` converts the recorded
FLOPs/bytes gauges into per-callable roofline terms for
``benchmarks/roofline.py --from-obs``.

Overhead note: each profiled call re-derives the shape key with a pytree
flatten (µs-scale on the engine's pytrees). The engine only wraps its
callables when a recorder is *enabled*; the default ``NullRecorder`` path
never sees this module.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax


@dataclasses.dataclass(frozen=True)
class CompileEvent:
    """One XLA compile of a profiled callable."""
    name: str                 # callable name ("prefill", "decode_tick", ...)
    key: str                  # human-readable arg-shape key
    wall_s: float             # lower+compile wall seconds
    flops: Optional[float]    # cost_analysis estimate; None if unavailable
    bytes_accessed: Optional[float]

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def shape_key(args: Tuple[Any, ...]) -> str:
    """Stable key for the arg shapes/dtypes that decide re-compilation,
    plus the devices of the first placed array: an AOT executable is bound
    to the devices it was lowered for, so replicas on different devices
    each compile their own."""
    parts = []
    devices = None
    for leaf in jax.tree_util.tree_leaves(args):
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            parts.append(f"{getattr(leaf, 'dtype', '?')}{list(shape)}")
        else:
            parts.append(repr(leaf))
        if devices is None and isinstance(leaf, jax.Array):
            devices = sorted(d.id for d in leaf.sharding.device_set)
    if devices is not None:
        parts.append(f"devices{devices}")
    return ",".join(parts)


def _cost_analysis(compiled) -> Tuple[Optional[float], Optional[float]]:
    try:
        cost = compiled.cost_analysis() or {}
        flops = cost.get("flops")
        nbytes = cost.get("bytes accessed")
        return (float(flops) if flops is not None else None,
                float(nbytes) if nbytes is not None else None)
    except Exception:       # backends without cost analysis
        return None, None


class JitProfiler:
    """Wrap a jitted callable; AOT-compile per shape key, record compiles."""

    def __init__(self, fn, name: str, recorder):
        # re-wrapping a profiler (engine.adopt_compiled) shares its compiled
        # cache — the adopting engine sees warm executables, not recompiles
        if isinstance(fn, JitProfiler):
            self._compiled = fn._compiled
            fn = fn.fn
        else:
            self._compiled: Dict[str, Any] = {}
        self.fn = fn
        self.name = name
        self.recorder = recorder
        self.events: List[CompileEvent] = []

    def __call__(self, *args):
        key = shape_key(args)
        compiled = self._compiled.get(key)
        if compiled is None:
            t0 = time.perf_counter()
            compiled = self.fn.lower(*args).compile()
            wall = time.perf_counter() - t0
            flops, nbytes = _cost_analysis(compiled)
            event = CompileEvent(name=self.name, key=key, wall_s=wall,
                                 flops=flops, bytes_accessed=nbytes)
            self.events.append(event)
            self._compiled[key] = compiled
            if self.recorder is not None:
                self.recorder.on_compile(event)
        return compiled(*args)

    @property
    def n_compiles(self) -> int:
        return len(self.events)


def maybe_profile(fn, name: str, recorder):
    """Wrap ``fn`` in a JitProfiler when ``recorder`` is enabled; otherwise
    return it untouched (the disabled hot path stays byte-identical)."""
    if recorder is None or not getattr(recorder, "enabled", False):
        return fn
    return JitProfiler(fn, name, recorder)


def roofline_rows(snapshot: dict) -> List[dict]:
    """Per-callable roofline terms from an obs metrics snapshot.

    Reads the ``compiled_flops{fn=...}`` / ``compiled_bytes{fn=...}`` gauges
    the recorder publishes and runs them through
    ``repro.analysis.roofline_terms`` (no collective bytes — these are
    single-executable estimates). Consumed by
    ``benchmarks/roofline.py --from-obs``.
    """
    from repro import analysis
    metrics = snapshot.get("metrics", {})
    flops: Dict[str, float] = {}
    nbytes: Dict[str, float] = {}
    for key, data in metrics.items():
        if key.startswith("compiled_flops{"):
            fn = key.split('fn="', 1)[1].split('"', 1)[0]
            flops[fn] = data.get("value") or 0.0
        elif key.startswith("compiled_bytes{"):
            fn = key.split('fn="', 1)[1].split('"', 1)[0]
            nbytes[fn] = data.get("value") or 0.0
    rows = []
    for fn in sorted(set(flops) | set(nbytes)):
        f, b = flops.get(fn, 0.0), nbytes.get(fn, 0.0)
        rows.append({"fn": fn, "flops": f, "bytes": b,
                     **analysis.roofline_terms(f, b, 0.0)})
    return rows
