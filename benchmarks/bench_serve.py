"""Continuous-batching serving benchmark -> results/BENCH_serve.json.

    python -m benchmarks.bench_serve --smoke
    python -m benchmarks.bench_serve --arch mistral_nemo_12b --arch mamba2_1p3b

Runs a staggered-arrival trace through repro.serve.engine for each arch and
records requests/s, tokens/s, mean slot occupancy, and the paged-KV-pool
columns (page_size / pages_in_use_peak / prefix_hit_rate — the default
trace shares a common prompt prefix so attn rows prove prefix-page reuse
end to end; ``--compare-monolithic`` appends a monolithic-layout twin of
the first arch for a before/after pair).

Unless ``--no-scaling``, the run also sweeps the multi-replica router
(``repro.serve.router``) over 1/2/4 data-parallel replicas of the first
arch under WEAK scaling (n x the request count at the same arrival rate)
and appends one ``<arch>__replicasN`` row per count carrying the
modeled-concurrency aggregate: ``agg_tokens_per_s = tokens / (router_s +
max_i busy_s[i])`` (replicas are stepped serially in-process, so the
modeled wall is the slowest replica's busy wall plus routing overhead) and
``scaling_efficiency = agg(n) / (n * agg(1))``. records_check gates fresh
entries on the max-replica row reaching >= 0.8x linear.

Unlike BENCH_kernels.json (overwritten single record), BENCH_serve.json keeps a
monotonically APPENDED ``history`` — one entry per run — so the serving-perf
trajectory stays reviewable across PRs. benchmarks/records_check.py (the CI
``records-check`` step) validates the schema, completeness (one row per
requested arch, ``ok`` per row), smoke flags, and history monotonicity.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time
import traceback

RESULTS_PATH = os.path.join(os.path.dirname(__file__),
                            "../results/BENCH_serve.json")
SCHEMA = "bench_serve/v1"
# one attn + one ssd arch, plus the KAN-FFN arch exercising the core.kan
# deploy()/apply() contract (its row carries the requant-free proof) on
# both KAN serving backends — lut vs lut_int8 rows record the int8-MXU
# decode-throughput delta
DEFAULT_ARCHS = ["mistral_nemo_12b", "mamba2_1p3b", "kan_llm",
                 "kan_llm_int8"]


def _decode_tick_requant_free(eng, cfg) -> bool:
    """Trace one fused decode tick over the engine's (deployed) params and
    verify it creates no int8 values — i.e. coefficient quantization ran at
    deploy time, not per tick."""
    import jax.numpy as jnp
    from repro.core import kan
    from repro.serve import engine as engine_lib

    tokens = jnp.zeros((eng.n_slots,), jnp.int32)
    index = jnp.ones((eng.n_slots,), jnp.int32)
    pages = jnp.zeros((eng.n_slots, eng.n_slot_pages), jnp.int32)
    return not kan.trace_requantizes(
        lambda p, c, t, i, g: engine_lib._decode_fn(p, c, t, i, g, cfg=cfg),
        eng.params, eng.cache, tokens, index, pages)


def bench_arch(arch_id: str, *, smoke: bool, slots: int, requests: int,
               prompt_len: int, new_tokens: int, stagger: int,
               seed: int, page_size: int = 0, common_prefix: int = 0,
               label: str = "") -> dict:
    import jax
    from repro.configs import get_arch
    from repro.models import transformer as tfm
    from repro.obs import EngineRecorder
    from repro.serve.engine import Engine, synth_trace

    arch = get_arch(arch_id, smoke=smoke)
    m = arch.model
    params = tfm.init_model(jax.random.PRNGKey(seed), m)
    reqs = synth_trace(
        m.vocab, requests, max_prompt=prompt_len,
        min_prompt=max(2, prompt_len // 2), max_new=new_tokens,
        min_new=max(2, new_tokens // 2), stagger=stagger,
        common_prefix=common_prefix, seed=seed)
    max_len = common_prefix + prompt_len + new_tokens
    # page_size=0 keeps the engine default (one page per slot — the
    # degenerate monolithic layout); an explicit page size exercises the
    # paged pool: chunked prefill + prefix-page sharing on attn archs.
    page_kw = dict(page_size=page_size or None)
    # warm-up run compiles prefill-per-length + the fused tick; the timed
    # run replays the SAME trace on a fresh engine with the warm jit caches,
    # so it measures steady-state throughput, not compile time. Each engine
    # gets its own recorder: the warm-up's captures the compile events (one
    # per distinct prompt length — the row records how many XLA paid for),
    # the timed one captures steady-state TTFT/TPOT latency percentiles.
    rec_warm = EngineRecorder()
    eng = Engine(params, m, n_slots=slots, max_len=max_len,
                 recorder=rec_warm, **page_kw)
    eng.run(reqs)
    rec_timed = EngineRecorder()
    eng2 = Engine(params, m, n_slots=slots, max_len=max_len,
                  recorder=rec_timed, **page_kw).adopt_compiled(eng)
    eng2.run(list(reqs))
    rep = eng2.stats.report()
    lat = rep["ttft_s"], rep["tpot_s"]
    sketch = rep["ttft_sketch"], rep["tpot_sketch"]
    # score the timed run against the default serving SLOs: every latency
    # sample plus each completion as an error-free event, closed into one
    # tick window — the verdict column fresh BENCH rows carry
    from repro.obs import SLOMonitor
    mon = SLOMonitor()
    for v in eng2.stats.ttft_s:
        mon.observe("ttft", v)
    for v in eng2.stats.tpot_s:
        mon.observe("tpot", v)
    for _ in range(rep["completed"]):
        mon.observe_event("errors", True)
    mon.observe("queue_wait", 0.0)
    mon.tick()
    slo_verdicts = mon.verdicts()
    row = {
        "arch": label or arch_id, "family": m.family, "smoke": smoke,
        "ok": True, "replicas": 1,
        "n_slots": slots, "requests": requests,
        "completed": rep["completed"],
        "requests_per_s": rep["requests_per_s"],
        "tokens_per_s": rep["tokens_per_s"],
        "mean_occupancy": rep["mean_occupancy"],
        "slot_reuse": rep["slot_reuse"],
        "ticks": rep["ticks"],
        "evicted_eos": rep["evicted_eos"],
        "evicted_length": rep["evicted_length"],
        # paged KV pool footprint + prefix-cache effectiveness (all zero /
        # one-page-per-slot under the default monolithic-equivalent layout)
        "page_size": rep["page_size"],
        "n_pages": rep["n_pages"],
        "pages_in_use_peak": rep["pages_in_use_peak"],
        "prefill_chunks": rep["prefill_chunks"],
        "prefix_hit_rate": rep["prefix_hit_rate"],
        # steady-state latency percentiles (seconds, warm jit caches)
        "ttft_p50_s": lat[0]["p50"], "ttft_p95_s": lat[0]["p95"],
        "ttft_p99_s": lat[0]["p99"],
        "tpot_p50_s": lat[1]["p50"], "tpot_p95_s": lat[1]["p95"],
        "tpot_p99_s": lat[1]["p99"],
        # mergeable-sketch twins of the numpy percentiles (same samples
        # through obs.sketch.QuantileSketch — alpha-bounded relative
        # error, fleet-mergeable across replicas)
        "ttft_sketch_p50_s": sketch[0]["p50"],
        "ttft_sketch_p95_s": sketch[0]["p95"],
        "ttft_sketch_p99_s": sketch[0]["p99"],
        "tpot_sketch_p50_s": sketch[1]["p50"],
        "tpot_sketch_p95_s": sketch[1]["p95"],
        "tpot_sketch_p99_s": sketch[1]["p99"],
        "sketch_alpha": sketch[0]["alpha"],
        # SLO verdicts over the timed run's samples (obs.slo defaults) and
        # the health-drain count (single engine: structurally zero) — the
        # fleet-health columns records_check gates on fresh rows
        "slo_verdicts": slo_verdicts,
        "drained_for_health": 0,
        # compile cost the warm-up run paid (one prefill per distinct
        # prompt length + the fused tick + the cache write)
        "prefill_compiles": sum(
            1 for e in rec_warm.compile_events
            if e.name.startswith("prefill")),
        "compiles_total": len(rec_warm.compile_events),
        "compile_s": round(sum(e.wall_s for e in rec_warm.compile_events),
                           3),
    }
    if eng2.kan_deployed:
        # the KAN-FFN row proves the two-phase contract: artifacts frozen
        # at engine construction, decode tick free of requantization
        row["kan_deployed"] = True
        row["kan_backend"] = m.kan_backend
        row["requant_free"] = _decode_tick_requant_free(eng2, m)
    return row


def bench_scaling(arch_id: str, *, smoke: bool, slots: int, requests: int,
                  prompt_len: int, new_tokens: int, stagger: int, seed: int,
                  page_size: int = 0,
                  replica_counts=(1, 2, 4)) -> list:
    """Weak-scaling sweep over the multi-replica router: for each n in
    ``replica_counts``, serve an n x ``requests`` trace (same arrival
    stagger, so each replica sees the single-engine load) through a Router
    over n engines pinned round-robin onto ``jax.devices()``. Replica 0
    deploys once; the others share its params and warm jit caches via
    ``adopt_compiled``. The timed fleet replays the warmed trace, so the
    rows record steady-state routing + decode, not compile time.

    Runs WITHOUT recorders, so the timed replays carry no profiling. The
    modeled aggregate (``agg_tokens_per_s``, see RouterStats.aggregate)
    charges the slowest replica's busy wall plus router overhead, since
    in-process replicas step serially rather than concurrently."""
    import jax
    from repro.configs import get_arch
    from repro.models import transformer as tfm
    from repro.serve.engine import make_replicas, synth_trace
    from repro.serve.router import Router

    arch = get_arch(arch_id, smoke=smoke)
    m = arch.model
    params = tfm.init_model(jax.random.PRNGKey(seed), m)
    geometry = dict(n_slots=slots, max_len=prompt_len + new_tokens,
                    page_size=page_size or None)

    rows, warm_src = [], None
    for n in replica_counts:
        # disjoint prompts (common_prefix=0): the sweep measures the
        # load-balancing path, so placement is driven by backlog scoring
        # rather than collapsing onto one replica via prefix affinity
        reqs = synth_trace(
            m.vocab, n * requests, max_prompt=prompt_len,
            min_prompt=max(2, prompt_len // 2), max_new=new_tokens,
            min_new=max(2, new_tokens // 2), stagger=stagger,
            common_prefix=0, seed=seed)
        # weak scaling scales the arrival RATE with the fleet: n requests
        # land per stagger window (occupancy scoring spreads each wave), so
        # every replica sees the single-engine arrival pattern rather than
        # an n x longer trickle that starves the tail of the fleet
        for i, r in enumerate(reqs):
            r.arrival = (i // n) * stagger
        # warm fleet pays any per-device compiles; the shared jit callables
        # then hold one cached executable per device for the timed fleet
        warm = make_replicas(params, m, n, adopt_from=warm_src, **geometry)
        Router(warm).run(list(reqs))
        warm_src = warm_src or warm[0]
        # best-of-3: busy walls are tens of ms at smoke scale, so a single
        # descheduling hiccup on one replica would swing the max-replica
        # efficiency; the best replay is the steady-state measurement
        rep = None
        for _ in range(3):
            timed = Router(make_replicas(params, m, n, adopt_from=warm_src,
                                         **geometry))
            timed.run(list(reqs))
            r = timed.report()
            if rep is None or r["agg_tokens_per_s"] > rep["agg_tokens_per_s"]:
                rep = r
        row = {
            "arch": f"{arch_id}__replicas{n}", "family": m.family,
            "smoke": smoke, "ok": True,
            "replicas": n, "n_slots": slots,
            "requests": n * requests, "completed": rep["completed"],
            "tokens": rep["tokens"],
            "routed": rep["routed"],
            "busy_s": rep["busy_s"], "busy_s_max": rep["busy_s_max"],
            "router_s": rep["router_s"],
            "agg_tokens_per_s": rep["agg_tokens_per_s"],
            "drained_for_health": rep["drained_for_health"],
        }
        base = rows[0] if rows else row
        row["scaling_efficiency"] = round(
            row["agg_tokens_per_s"] * base["replicas"]
            / (n * base["agg_tokens_per_s"]), 3)
        rows.append(row)
    return rows


def load_record(path: str) -> dict:
    """Append-only record loader (shared clobber protection)."""
    from benchmarks._record import load_history_record
    return load_history_record(path, SCHEMA)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None,
                    help="repeatable; default: one attn + one ssd arch")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--stagger", type=int, default=2)
    ap.add_argument("--page-size", type=int, default=4,
                    help="KV page size for the paged pool (0 = engine "
                         "default: one monolithic page per slot)")
    ap.add_argument("--common-prefix", type=int, default=8,
                    help="shared prompt-prefix tokens in the trace; with a "
                         "page size that divides it, attn rows record a "
                         "nonzero prefix_hit_rate (0 = disjoint prompts)")
    ap.add_argument("--compare-monolithic", action="store_true",
                    help="also bench the first arch with the default "
                         "monolithic layout (page_size=0) on the same "
                         "trace, appended as an '<arch>__monolithic' row — "
                         "the before/after pair for the paged-pool change")
    ap.add_argument("--no-scaling", action="store_true",
                    help="skip the multi-replica weak-scaling sweep "
                         "(records_check gates fresh entries on the "
                         "replicas=4 scaling row, so CI must not set this)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    archs = args.arch or DEFAULT_ARCHS

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    # (arch, label, page_size) cells; the optional monolithic twin reruns
    # the first arch on the identical trace with the one-page-per-slot
    # layout so the pair isolates the paging overhead/benefit
    cells = [(a, a, args.page_size) for a in archs]
    if args.compare_monolithic:
        cells.append((archs[0], f"{archs[0]}__monolithic", 0))

    rows, ok = [], True
    for arch_id, label, page_size in cells:
        try:
            row = bench_arch(
                arch_id, smoke=args.smoke, slots=args.slots,
                requests=args.requests, prompt_len=args.prompt_len,
                new_tokens=args.new_tokens, stagger=args.stagger,
                seed=args.seed, page_size=page_size,
                common_prefix=args.common_prefix, label=label)
        except Exception as e:  # recorded, not silently missing
            ok = False
            traceback.print_exc(file=sys.stderr)
            row = {"arch": label, "smoke": args.smoke, "ok": False,
                   "error": f"{type(e).__name__}: {e}"}
        rows.append(row)
        print(json.dumps(row), flush=True)

    scaling_counts = [] if args.no_scaling else [1, 2, 4]
    if scaling_counts:
        try:
            srows = bench_scaling(
                archs[0], smoke=args.smoke, slots=args.slots,
                requests=args.requests, prompt_len=args.prompt_len,
                new_tokens=args.new_tokens, stagger=args.stagger,
                seed=args.seed, page_size=args.page_size,
                replica_counts=tuple(scaling_counts))
        except Exception as e:  # recorded, not silently missing
            ok = False
            traceback.print_exc(file=sys.stderr)
            srows = [{"arch": f"{archs[0]}__replicas", "smoke": args.smoke,
                      "ok": False, "error": f"{type(e).__name__}: {e}"}]
        for row in srows:
            rows.append(row)
            print(json.dumps(row), flush=True)

    record = load_record(RESULTS_PATH)
    record["history"].append({
        "ts": time.time(),
        "ts_iso": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "smoke": args.smoke,
        "ok": ok,
        "archs": list(archs),
        "replica_scaling": scaling_counts,
        "rows": rows,
    })
    os.makedirs(os.path.dirname(RESULTS_PATH), exist_ok=True)
    with open(RESULTS_PATH, "w") as f:
        json.dump(record, f, indent=1)
    print(f"wrote {os.path.normpath(RESULTS_PATH)} "
          f"({len(record['history'])} history entries)", file=sys.stderr)
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
