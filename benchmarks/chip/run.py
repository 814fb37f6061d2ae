"""Run one cell of the benchmark once.

    python benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names its configuration
(``configs/<config>.json``) and its traffic mix (``traffic/<mix>.json``);
each metric is read by ``metrics/<metric>.py``. The run builds the model
from the seed on the device, warms up every program the mix can call,
ramps the load up, measures for ``--seconds`` with no compile inside,
waits for the window's requests to finish, and checks a sample of what
was served against the configuration's plain reference
(``reference/<family>.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: each number compared, beside its limit. Earlier lines report
the realised traffic, set-up and the compiles counted in the window. The
run exits non-zero, with no result line, where JAX finds no TPU, fewer
chips than the cell asks for, or a device kind the peaks table lacks.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import List

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness as hz            # noqa: E402
import trace_phases             # noqa: E402
import trace_reduce             # noqa: E402
import traffic as traffic_lib   # noqa: E402
from peaks import peaks_for     # noqa: E402

TRACE_DIR = hz.ROOT / ".bench_trace"


class NoDevice(RuntimeError):
    """JAX found no accelerator the cell can run on."""


def process_start() -> float:
    """This process's start on ``time.perf_counter``'s clock."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - age
    except (OSError, ValueError, IndexError):
        return time.perf_counter()


def log(**fields) -> None:
    print(json.dumps(fields, default=float), flush=True)


class Ctx:
    """What a metric reader may read: the window's records, the engine's
    counters at the window's edges, the trace's reduction, and the counts
    of the configuration."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def window_recs(self):
        return [r for r in self.recs if self.w0 <= r.due < self.w1]

    def window_ticks(self):
        return [t for t in self.ticks if self.w0 <= t.t1 <= self.w1]

    def delta(self, key):
        return self.stats1[key] - self.stats0[key]


def nearest_rank(values, q: float) -> float:
    """The q-th percentile as the sample at rank ceil(q/100 * n)."""
    vals = sorted(values)
    if not vals:
        return math.nan
    return vals[max(0, math.ceil(q / 100 * len(vals)) - 1)]


def check_device(chips: int):
    """The devices and the peaks of their kind; refuses anything but as
    many TPU chips as the cell asks for, of a kind the table holds."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform "
                       f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips; JAX found "
                       f"{len(devices)}")
    try:
        return devices, peaks_for(devices[0].device_kind)
    except KeyError as e:
        raise NoDevice(str(e)) from None


def longest_step(ticks, w0: float, w1: float) -> dict:
    """The window's longest engine step: seconds, when, and what it ran."""
    t = max((t for t in ticks if w0 <= t.t1 <= w1),
            key=lambda t: t.t1 - t.t0, default=None)
    if t is None:
        return {}
    return {"s": t.t1 - t.t0, "at_s": t.t0 - w0, "chunks": len(t.chunks),
            "decoded": t.n_dec}


def serve(cell: hz.Cell, seed: int, seconds: float, trace: bool) -> Ctx:
    """Build, warm up and drive the cell's program, then free it. Returns
    what the metric readers and the check read."""
    t_start = process_start()
    hz.import_program()
    devices, peaks = check_device(cell.entry["chips"])
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    cfg = hz.model_config(cell.conf)
    ramp = cell.mix.get("ramp_s", 5.0)
    traffic = traffic_lib.Traffic(cell.mix, seed, cfg.vocab,
                                  ramp + seconds + hz.DRAIN_S,
                                  window=(ramp, seconds))
    params = hz.init_params(cfg, seed)
    recorder = None
    if trace:
        # the engine's phases as profiler spans; untraced runs, which give
        # every end-to-end metric, run the engine without one
        from repro.obs import EngineRecorder
        recorder = EngineRecorder()
    eng = hz.make_engine(cell, params, cfg, recorder)
    n_warm = hz.warm_up(eng, traffic.prompt_lengths(), cfg.vocab)
    compiles = hz.Compiles()
    gc_pauses = hz.GcPauses()

    t0 = hz.clock()
    drv = hz.Driver(eng, traffic, t0, spans=trace)
    w0, w1 = t0 + ramp, t0 + ramp + seconds
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        drv.run(until=lambda now: now >= w0 - 1.0)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # the harness's spans suffice,
        opts.host_tracer_level = 1      # and they are level 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    drv.run(until=lambda now: now >= w0)
    setup_s = w0 - t_start
    stats0 = hz.stats_snapshot(eng)
    compiles.armed = gc_pauses.armed = True
    t_span = hz.clock()
    with drv.span(trace_reduce.WINDOW_SPAN):
        drv.run(until=lambda now: now >= w1)
    compiles.armed = gc_pauses.armed = False
    gc_pauses.close()
    stats1 = hz.stats_snapshot(eng)
    due = [r for r in drv.recs.values() if w0 <= r.due < w1]
    if trace:
        # stopped before the drain, whose steps no metric reads: their
        # events only lengthened stop_trace and the reduction (on a v5e,
        # chat_burst's 27 s drain then took 130 s with them, 104 s without)
        jax.profiler.stop_trace()
    if cell.open_loop:
        drv.run(until=lambda now: all(r.done == r.done for r in due)
                or now > w1 + hz.DRAIN_S)
    reduction = None
    if trace:
        reduction = reduce_trace([(t.t0 - t_span, t.t1 - t_span,
                                   len(t.chunks), t.n_dec > 0)
                                  for t in drv.ticks if t.t1 >= t_span])
    log(traffic=traffic_lib.describe([r.req for r in due], seconds),
        warmup_prompts=n_warm, setup_s=setup_s,
        window_compiles=compiles.count, drained_s=hz.clock() - w1,
        longest_step=longest_step(drv.ticks, w0, w1),
        longest_gc_s=gc_pauses.longest)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.entry["chips"]])
    ctx = Ctx(cell=cell, seconds=seconds, w0=w0, w1=w1,
              recs=list(drv.recs.values()), ticks=drv.ticks,
              stats0=stats0, stats1=stats1,
              n_slots=eng.n_slots, setup_s=setup_s,
              counts=hz.load_module("counts", cell.conf["family"]),
              model=cell.model, peaks=peaks, trace=reduction,
              nearest_rank=nearest_rank, devices=devices,
              memory_peak_bytes=int(peak))
    # offline: nothing is due in the window's sense; the tokens judge it
    ctx.window_due = due if cell.open_loop else []
    del eng, params, drv
    gc.collect()
    return ctx


def read_metrics(cell: hz.Cell, ctx: Ctx, section: str,
                 sources=None) -> dict:
    """The cell's metrics of ``section`` that a reader finds, optionally
    only those of the given ``sources``."""
    out = {}
    for m in cell.metrics(section):
        if sources is not None and m["source"] not in sources:
            continue
        value = hz.load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def reference_gaps(cell: hz.Cell, ctx: Ctx, seed: int,
                   control: bool = False) -> List[dict]:
    """The plain reference over a sample, drawn from the seed, of the
    requests that finished (see ``harness.pick_sample``)."""
    t_ref = hz.clock()
    sample = hz.pick_sample([r for r in ctx.recs if r.served is not None],
                            seed)
    gaps = hz.reference_gaps(cell, seed, sample, control) if sample else []
    log(reference_s=hz.clock() - t_ref, sample_requests=len(sample),
        sample_tokens=sum(len(r.served) for r in sample),
        sample_longest_prompt=max((len(r.req.tokens) for r in sample),
                                  default=0))
    return gaps


def widest(gaps: List[dict], key: str = "gap") -> float:
    """The widest gap over every compared token; inf where none was."""
    return max((float(g[key].max()) for g in gaps), default=math.inf)


def result(cell: hz.Cell, ctx: Ctx, gap: float, trace: bool) -> dict:
    """The run's result line, with ``correct`` judged from ``gap``, the
    widest logit gap of the served tokens, and the window's failures."""
    failed = (sum(r.done != r.done for r in ctx.window_due)
              + ctx.delta("rejected"))
    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.entry["chips"],
              "memory_peak_bytes": ctx.memory_peak_bytes}
    if ctx.trace:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
    limit = cell.mix["check"]["logit_gap"]
    out = {"correct": bool(gap <= limit and failed == 0),
           "attempted": (len(ctx.window_due) if cell.open_loop
                         else ctx.delta("completed")),
           "failed": failed,
           "metrics": read_metrics(cell, ctx,
                                   "per_layer" if trace else "end_to_end"),
           "device": device}
    if ctx.trace:
        out["breakdown"] = ctx.trace["breakdown"]
    out["checks"] = {"logit_gap": {"value": gap, "limit": limit},
                     "unfinished": {"value": failed, "limit": 0}}
    return out


def run_cell(cell: hz.Cell, seed: int, seconds: float, trace: bool) -> dict:
    ctx = serve(cell, seed, seconds, trace)
    gap = widest(reference_gaps(cell, ctx, seed))
    if not trace:
        # the per-layer readings the host takes, without the profiler on
        log(untraced_per_layer={
            k: v["value"] for k, v in read_metrics(
                cell, ctx, "per_layer",
                ("host_clock", "program_counter")).items()})
    return result(cell, ctx, gap, trace)


def reduce_trace(ticks):
    """Reduce the trace just written (``trace_phases.reduce``: busy time,
    programs, the engine's phases and the model step's scopes), with the
    harness's ticks (start and end relative to the window span, chunks run,
    decoded) naming the engine's programs; then delete it."""
    paths = sorted(TRACE_DIR.rglob("*.xplane.pb"))
    if not paths:
        return None
    red = trace_phases.reduce(trace_phases.load(str(paths[-1])), ticks)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return red


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = hz.load_cell(args.workload)
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except (NoDevice, ImportError, FileNotFoundError, KeyError) as e:
        print(f"run.py: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
