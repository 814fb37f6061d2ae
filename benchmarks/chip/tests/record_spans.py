"""Run one cell traced, as ``run.py --trace 1`` runs it (the engine's
spans and the model step's scopes on), and keep what its reduction read
(run it on a machine with the chip):

    python benchmarks/chip/tests/record_spans.py --workload <name> \\
        --seed <n> --seconds <s> --out <dir>

Beside the usual result line it prints and writes ``phases.json``
(``trace_phases.reduce`` less the breakdown: idle time by engine phase,
``host_gap_ms``, device ms per call by scope and by name),
``op_stats.json`` (the metadata stats of the first device ops, to show
which carries the scope path) and ``excerpt.json`` (the first
``--excerpt-ms`` of the window in ``trace_phases.load``'s form, op names
cut to their label, for the CPU tests).
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness as hz        # noqa: E402
import run                  # noqa: E402
import trace_phases         # noqa: E402
import trace_reduce         # noqa: E402


def excerpt(ev: dict, ticks, ms: float) -> dict:
    """The events of the window's first ``ms``, op names cut to their
    label, with the harness's ticks that lie inside."""
    win = [h for h in ev["host"] if h[0] == trace_reduce.WINDOW_SPAN][0]
    lo, hi = win[1], win[1] + ms * 1e6

    def keep(events, label=lambda n: n):
        return [[label(e[0])] + list(e[1:]) for e in events
                if e[1] < hi and e[1] + e[2] > lo]
    return {"devices": [{"name": d["name"],
                         "ops": keep(d["ops"], trace_reduce.op_label),
                         "modules": keep(d["modules"])}
                        for d in ev["devices"]],
            "host": keep([h for h in ev["host"] if h is not win])
            + [[win[0], lo, hi - lo]],
            "ticks": [t for t in ticks if t[1] * 1e3 <= ms]}


def op_stat_sample(path: str, n: int = 40) -> dict:
    """The metadata stats of the first ``n`` ops of each device plane."""
    with open(path, "rb") as f:
        planes = trace_phases.op_stats(f.read())
    return {plane: dict(list(events.items())[:n])
            for plane, events in planes.items()}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--excerpt-ms", type=float, default=0.0,
                    help="write excerpt.json of this many ms (0: none)")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    def reduce_and_keep(ticks):
        path = str(sorted(run.TRACE_DIR.rglob("*.xplane.pb"))[-1])
        (out / "op_stats.json").write_text(
            json.dumps(op_stat_sample(path), indent=1))
        t0 = hz.clock()
        ev = trace_phases.load(path)
        if args.excerpt_ms:
            (out / "excerpt.json").write_text(
                json.dumps(excerpt(ev, ticks, args.excerpt_ms)))
        red = trace_phases.reduce(ev, ticks)
        phases = {k: v for k, v in (red or {}).items() if k != "breakdown"}
        phases["reduce_s"] = hz.clock() - t0
        (out / "phases.json").write_text(json.dumps(phases, indent=1))
        print(json.dumps({"phases": phases}), flush=True)
        shutil.rmtree(run.TRACE_DIR, ignore_errors=True)
        return red

    run.reduce_trace = reduce_and_keep
    res = run.run_cell(hz.load_cell(args.workload), args.seed, args.seconds,
                       True)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
