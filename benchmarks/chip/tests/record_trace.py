"""Record a small excerpt of a real chip trace for the CPU tests of
``trace_reduce`` (run it on a machine with the chip):

    python benchmarks/chip/tests/record_trace.py --workload <name> \\
        --seed <n> --out chiprun_out/trace

It runs the cell once with ``--trace 1`` and writes, beside the usual
result, ``structure.json`` (every plane and line of the trace with its
event count and first event names) and ``excerpt.json`` (the plain events
of the first ``--excerpt-ms`` of the window, in ``trace_reduce.load``'s
form, with the window span cut to that excerpt).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness as hz        # noqa: E402
import run                  # noqa: E402
import trace_reduce         # noqa: E402


def excerpt(ev: dict, ticks, ms: float) -> dict:
    """The events of the window's first ``ms``, op names cut to their
    label, with the harness's ticks that lie inside."""
    win = [h for h in ev["host"] if h[0] == trace_reduce.WINDOW_SPAN][0]
    lo, hi = win[1], win[1] + ms * 1e6

    def keep(events, label=lambda n: n):
        return [[label(e[0]), e[1], e[2]] for e in events
                if e[1] < hi and e[1] + e[2] > lo]
    return {"devices": [{"name": d["name"],
                         "ops": keep(d["ops"], trace_reduce.op_label),
                         "modules": keep(d["modules"])}
                        for d in ev["devices"]],
            "host": keep([h for h in ev["host"] if h is not win])
            + [[win[0], lo, hi - lo]],
            "ticks": [t for t in ticks if t[1] * 1e3 <= ms]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--excerpt-ms", type=float, default=300.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    def keep_reduce(ticks):
        from jax.profiler import ProfileData
        path = str(sorted(run.TRACE_DIR.rglob("*.xplane.pb"))[-1])
        pd = ProfileData.from_file(path)
        structure = []
        for plane in pd.planes:
            lines = []
            for line in plane.lines:
                evs = list(line.events)
                lines.append({"line": line.name, "events": len(evs),
                              "first": [e.name for e in evs[:15]],
                              "t0": evs[0].start_ns if evs else None})
            structure.append({"plane": plane.name, "lines": lines})
        (out / "structure.json").write_text(json.dumps(structure, indent=1))
        ev = trace_reduce.load(path)
        (out / "excerpt.json").write_text(
            json.dumps(excerpt(ev, ticks, args.excerpt_ms)))
        red = trace_reduce.reduce(ev, ticks)
        (out / "reduction.json").write_text(json.dumps(red, indent=1))
        return red

    run.reduce_trace = keep_reduce
    res = run.run_cell(hz.load_cell(args.workload), args.seed, args.seconds,
                       True)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
