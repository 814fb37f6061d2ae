"""Find a cell's knee: offer its traffic at several rates in one process
and report, per rate, whether the queue grew across the window.

    python benchmarks/chip/sweep.py --workload <name> --seed <n> \\
        --rates 2,4,6,8 --seconds 12

The knee is the highest rate at which the queue does not grow; a cell
then offers 0.8 times it, written into its traffic file as a number.
Each level ramps for the mix's ``ramp_s``, measures ``--seconds``, then
stops offering and lets the engine drain before the next level.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness as hz            # noqa: E402
import run                      # noqa: E402
import traffic as traffic_lib   # noqa: E402


def level(cell, eng, seed, rate, seconds):
    mix = json.loads(json.dumps(cell.mix))
    mix["arrivals"]["rate_per_s"] = rate
    ramp = mix.get("ramp_s", 5.0)
    tr = traffic_lib.Traffic(mix, seed, eng.cfg.vocab, ramp + seconds + 5,
                             window=(ramp, seconds))
    t0 = hz.clock()
    drv = hz.Driver(eng, tr, t0)
    w0, w1 = t0 + ramp, t0 + ramp + seconds
    drv.run(until=lambda now: now >= w0)
    q0 = len(eng.queue)
    drv.run(until=lambda now: now >= w1)
    q1 = len(eng.queue)
    window = [r for r in drv.recs.values() if w0 <= r.due < w1]
    done_in = sum(w0 <= r.done <= w1 for r in drv.recs.values())
    tr.close()
    drv.run(until=lambda now: drv.idle())
    ttft = [r.tokens[0][0] - r.due for r in window if r.tokens]
    wait = [r.admit - r.due for r in window if r.admit == r.admit]
    ctx = run.Ctx(cell=cell, w0=w0, w1=w1, recs=drv.recs.values(),
                  nearest_rank=run.nearest_rank)
    itl = hz.load_module("metrics", "itl_p95_ms").read(ctx)
    return {"rate": rate, "offered": len(window) / seconds,
            "completed_per_s": done_in / seconds, "queue_start": q0,
            "queue_end": q1, "ttft_p50_s": run.nearest_rank(ttft, 50),
            "ttft_p95_s": run.nearest_rank(ttft, 95),
            "queue_wait_p95_s": run.nearest_rank(wait, 95),
            "itl_p95_ms": itl}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
    cell = hz.load_cell(args.workload)
    hz.import_program()
    run.check_device(cell.entry["chips"])
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg = hz.model_config(cell.conf)
    eng = hz.make_engine(cell, hz.init_params(cfg, args.seed), cfg)
    tr = traffic_lib.Traffic(cell.mix, args.seed, cfg.vocab, 1.0)
    hz.warm_up(eng, tr.prompt_lengths(), cfg.vocab)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        run.log(**level(cell, eng, args.seed + i, rate, args.seconds))
    return 0


if __name__ == "__main__":
    sys.exit(main())
