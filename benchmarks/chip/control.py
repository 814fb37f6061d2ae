"""Readings for the limit of a cell's check: the program's widest logit
gap on many seeds, and the fp8 control's on the same prompts and tokens,
each judged by the benchmark's own comparison.

    python benchmarks/chip/control.py --workload <name> \\
        --seeds 11,12,13 --seconds 6

For each seed the cell's program serves a short window at the cell's own
load (its longest requests finish in the drain), and the plain reference
runs over a sample as in a benchmark run. Beside the program's gap it
reads the control's: the reference with every weight matmul computed in
fp8 (e4m3, one scale per row and per column), the precision below the
bf16 that the configuration states, and the gap of the token the control
puts first. Both go through ``run.result``, the code that decides a run's
``correct``: the program's tokens must come out correct, the control's in
their place must not. The limit lies between the largest program reading
and the smallest control reading. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness as hz            # noqa: E402
import run                      # noqa: E402


def readings(cell, seed: int, seconds: float) -> dict:
    """One seed: the result lines of the program and of the control in its
    place, and the counts of tokens off the reference's argmax."""
    ctx = run.serve(cell, seed, seconds, False)
    gaps = run.reference_gaps(cell, ctx, seed, control=True)
    if not gaps:
        raise RuntimeError(f"seed {seed}: no request finished; give the "
                           "window more --seconds")
    program = run.result(cell, ctx, run.widest(gaps, "gap"), False)
    control = run.result(cell, ctx, run.widest(gaps, "control_gap"), False)
    return {"seed": seed,
            "program_gap": program["checks"]["logit_gap"]["value"],
            "program_correct": program["correct"],
            "control_gap": control["checks"]["logit_gap"]["value"],
            "control_correct": control["correct"],
            "limit": control["checks"]["logit_gap"]["limit"],
            "tokens": int(sum(len(g["gap"]) for g in gaps)),
            "program_flips": int(sum((g["gap"] > 0).sum() for g in gaps)),
            "control_flips": int(sum((g["control_gap"] > 0).sum()
                                     for g in gaps))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)
    cell = hz.load_cell(args.workload)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(readings(cell, seed, args.seconds))
        run.log(**rows[-1])
    run.log(workload=cell.name,
            lower=max(r["program_gap"] for r in rows),
            upper=min(r["control_gap"] for r in rows),
            limit=cell.mix["check"]["logit_gap"],
            program_correct=all(r["program_correct"] for r in rows),
            control_correct=any(r["control_correct"] for r in rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
