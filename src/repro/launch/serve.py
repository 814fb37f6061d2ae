"""Serving launcher: thin driver over the continuous-batching engine.

    python -m repro.launch.serve --arch mamba2_1p3b --smoke --requests 8

The engine itself (slot pool, admission queue, prefill-on-admit, fused
multi-slot decode, eviction) lives in ``repro.serve.engine``; this driver
only builds params, synthesizes a staggered-arrival trace, optionally enters
a host mesh (``--mesh-model N`` shards the slot pool via dist.sharding), runs
the engine, and prints the EngineStats report.

``--replicas N`` serves the trace through ``repro.serve.router`` instead:
N data-parallel engines, replica i pinned to device i (round-robin over
``jax.devices()``), share ONE deployed artifact (replica 0's params — KAN
deploy runs once) and ``adopt_compiled`` each other's jits; the router
owns the global queue, scores load/prefix-affinity per dispatch, and
prints the RouterStats aggregate. Mutually exclusive with
``--mesh-model`` (a replica is whole-model by construction).
``--drain-tick T`` schedules a mid-trace drain of ``--drain-replica`` —
its in-flight requests requeue onto the survivors and ``--check`` still
requires full completion (the zero-lost-requests CI gate).

``--check`` is the CI smoke gate: it plants an EOS on request 0 (probed from
a solo run so the request genuinely stops early), then asserts slot reuse
(>1 request served by some slot), at least one EOS eviction, and that every
request completed. Exit status is non-zero on any violation.

Observability: ``--trace-out FILE`` / ``--metrics-out FILE`` run the engine
with a recording ``repro.obs.EngineRecorder`` and write a Chrome
``trace_event`` JSON (open in Perfetto) and an ``obs/v1`` metrics snapshot
(TTFT/TPOT/queue-wait/tick-phase histograms, per-prompt-length compile
events, chip placement gauges for ``cim_tiled``). The default run keeps the
no-op ``NullRecorder`` — zero recording overhead.

Fleet health: ``--metrics-port P`` serves the live registry over HTTP while
the run is in flight (``/metrics`` Prometheus text + ``/metrics.json``
snapshot; ``P=0`` binds an ephemeral port and the driver self-scrapes it at
the end — under ``--check`` the scrape must match ``exposition()`` byte for
byte). ``--snapshot-out FILE`` writes periodic JSON snapshots during the
run. On the router path, ``--drift-replica I --drift-rate R`` attaches a
``hw.health.ChipHealth`` canary probe to every replica with temporal
conductance drift injected into replica I only; the router's HealthMonitor
polls canary deviation + SLO burn every ``--health-poll`` ticks and
auto-drains the degraded replica once deviation crosses
``--health-threshold``. Under ``--check`` the run must then show
``drained_for_health >= 1``, zero lost requests, and a completion-token
multiset identical to a healthy single engine on the same trace — the
closed-loop CI gate.
"""
import argparse
import contextlib
import json

import jax

from repro.configs import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tfm
from repro.serve.engine import Engine, make_replicas, synth_trace
from repro.serve.scheduler import AdmissionQueue, Request


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="max prompt length in the synthetic trace")
    ap.add_argument("--new-tokens", type=int, default=32,
                    help="max per-request generation budget")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--stagger", type=int, default=2,
                    help="ticks between request arrivals")
    ap.add_argument("--page-size", type=int, default=0,
                    help="KV page size in tokens (0 = engine default: one "
                         "page per slot, the degenerate monolithic layout)")
    ap.add_argument("--n-pages", type=int, default=0,
                    help="page-pool capacity incl. the garbage page (0 = "
                         "engine default: every slot's worst case fits)")
    ap.add_argument("--common-prefix", type=int, default=0,
                    help="shared prompt-prefix tokens in the synthetic "
                         "trace (exercises prefix-page sharing on "
                         "pure-attention archs)")
    ap.add_argument("--queue-cap", type=int, default=0,
                    help="bounded admission queue (0 = unbounded)")
    ap.add_argument("--mesh-model", type=int, default=0,
                    help="enter a (data x model) host mesh with this many "
                         "model ways (0 = no mesh)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through the multi-replica router with this "
                         "many data-parallel engines (1 = single engine, "
                         "the historical path; incompatible with "
                         "--mesh-model)")
    ap.add_argument("--drain-tick", type=int, default=0,
                    help="router path only: schedule a drain of "
                         "--drain-replica at this tick (0 = no drain)")
    ap.add_argument("--drain-replica", type=int, default=1,
                    help="replica index --drain-tick evacuates")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kan-backend", default="",
                    help="override ModelConfig.kan_backend for KAN-FFN "
                         "archs (ref|lut|fused|cim; serving deploys the "
                         "chosen backend's frozen artifact once)")
    ap.add_argument("--check", action="store_true",
                    help="CI gate: assert slot reuse + EOS eviction + "
                         "full completion")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome trace_event JSON (Perfetto) of "
                         "the run; enables recording")
    ap.add_argument("--metrics-out", default="",
                    help="write the obs/v1 metrics snapshot JSON; enables "
                         "recording")
    ap.add_argument("--metrics-port", type=int, default=-1,
                    help="serve live /metrics + /metrics.json over HTTP "
                         "during the run (0 = ephemeral port; -1 = off); "
                         "enables recording")
    ap.add_argument("--snapshot-out", default="",
                    help="write periodic JSON metric snapshots to this "
                         "path during the run; enables recording")
    ap.add_argument("--snapshot-every", type=float, default=1.0,
                    help="seconds between periodic snapshots "
                         "(--snapshot-out)")
    ap.add_argument("--drift-replica", type=int, default=-1,
                    help="router path only: inject temporal conductance "
                         "drift into this replica's chip-health canary "
                         "(-1 = no drift / no health monitor)")
    ap.add_argument("--drift-rate", type=float, default=0.05,
                    help="mean drift exponent nu for the degraded replica "
                         "(hw.variation.DriftConfig.rate)")
    ap.add_argument("--health-threshold", type=float, default=0.05,
                    help="canary relative-deviation threshold above which "
                         "the HealthMonitor drains a replica")
    ap.add_argument("--health-poll", type=int, default=2,
                    help="router ticks between HealthMonitor polls")
    args = ap.parse_args(argv)

    if args.replicas > 1 and args.mesh_model:
        raise SystemExit("--replicas and --mesh-model are mutually "
                         "exclusive: a router replica holds the whole "
                         "model on its own device(s)")
    if args.drift_replica >= 0 and not (0 <= args.drift_replica
                                        < args.replicas and
                                        args.replicas > 1):
        raise SystemExit("--drift-replica needs the router path: require "
                         "--replicas > 1 and 0 <= drift-replica < replicas")

    enable_compile_cache()
    arch = get_arch(args.arch, smoke=args.smoke)
    m = arch.model
    if args.kan_backend:
        import dataclasses
        m = dataclasses.replace(m, kan_backend=args.kan_backend)
    key = jax.random.PRNGKey(args.seed)
    params = tfm.init_model(key, m)

    reqs = synth_trace(
        m.vocab, args.requests,
        max_prompt=args.prompt_len, min_prompt=max(2, args.prompt_len // 2),
        max_new=args.new_tokens, min_new=max(2, args.new_tokens // 2),
        stagger=args.stagger, common_prefix=args.common_prefix,
        seed=args.seed)
    max_len = args.common_prefix + args.prompt_len + args.new_tokens
    page_kw = dict(page_size=args.page_size or None,
                   n_pages=args.n_pages or None)

    mesh_ctx = contextlib.nullcontext()
    if args.mesh_model:
        from repro.launch.mesh import make_host_mesh
        mesh_ctx = make_host_mesh(model=args.mesh_model)

    recorder = None
    if (args.trace_out or args.metrics_out or args.snapshot_out
            or args.metrics_port >= 0):
        from repro.obs import EngineRecorder
        recorder = EngineRecorder()

    server = None
    if args.metrics_port >= 0:
        from repro.obs import MetricsHTTPServer
        server = MetricsHTTPServer(recorder, port=args.metrics_port).start()
        print(f"metrics endpoint -> {server.url}")
    writer = None
    if args.snapshot_out:
        from repro.obs import PeriodicSnapshotWriter
        writer = PeriodicSnapshotWriter(
            recorder, args.snapshot_out,
            interval_s=args.snapshot_every).start()

    router = None
    ref_comps = None
    with mesh_ctx:
        queue = AdmissionQueue(args.queue_cap or None)
        if args.replicas > 1:
            from repro.serve.router import Router

            def rec_for(i):
                return recorder.for_replica(i) if recorder else None

            geometry = dict(n_slots=args.slots, max_len=max_len, **page_kw)
            probe_eng = None
            eos_planted = args.check and args.new_tokens >= 3
            if eos_planted:
                # same planted-EOS probe as the single-engine path: identical
                # geometry on replica 0's device, warm caches adopted by it
                probe_eng = Engine(params, m, device=jax.devices()[0],
                                   recorder=rec_for(0), **geometry)
                probe = probe_eng.run([Request(rid="probe",
                                               tokens=reqs[0].tokens,
                                               max_new=2)])
                reqs[0].eos_id = int(probe[0].tokens[1])
            # replica i is pinned to device i (round-robin); replicas 1..N-1
            # share replica 0's DEPLOYED params (KAN deploy is idempotent:
            # one frozen artifact serves the whole fleet) and its jits
            replicas = make_replicas(params, m, args.replicas,
                                     adopt_from=probe_eng,
                                     recorder_for=rec_for, **geometry)
            eng = replicas[0]
            router = Router(replicas, queue=queue, recorder=recorder)
            if args.drain_tick:
                router.schedule_drain(args.drain_replica, args.drain_tick)
            if args.drift_replica >= 0:
                from repro.hw.health import ChipHealth, ProbeGeometry
                from repro.hw.tiles import TileConfig
                from repro.hw.variation import DriftConfig
                from repro.obs.slo import default_serving_slos
                mon = router.enable_health(
                    poll_every=args.health_poll,
                    drift_threshold=args.health_threshold,
                    # lenient latency SLOs: on a CPU smoke the wall-clock
                    # TTFT/TPOT are compile-noise, and this gate is about
                    # the DRIFT loop — a jitter-drained healthy replica
                    # would make the token-multiset check meaningless
                    slos=lambda: default_serving_slos(ttft_s=120.0,
                                                      tpot_s=60.0,
                                                      queue_wait_ticks=1e9))
                for i in range(args.replicas):
                    # every replica carries a canary probe; only the
                    # degraded one drifts (tau=4: deviation crosses the
                    # default threshold within ~a dozen ticks)
                    drifting = (i == args.drift_replica)
                    mon.attach_chip(i, ChipHealth(
                        tile=TileConfig(array_size=64, tile_cols=16),
                        drift=DriftConfig(
                            rate=args.drift_rate if drifting else 0.0,
                            tau=4.0, seed=args.seed),
                        geometry=ProbeGeometry(layer_uids=(0, 1),
                                               tiles_per_layer=2),
                        registry=(recorder.metrics if recorder else None),
                        labels={"replica": str(i)}))
            comps = router.run(reqs)
            if args.check and args.drift_replica >= 0:
                # healthy single-engine reference on the SAME trace (same
                # deployed params, warm caches): greedy decode is
                # deterministic, so the auto-drained fleet must emit the
                # identical completion-token multiset
                ref_eng = Engine(eng.params, m, device=eng.device,
                                 **geometry).adopt_compiled(eng)
                ref_comps = ref_eng.run(list(reqs))
        else:
            eng = Engine(params, m, n_slots=args.slots, max_len=max_len,
                         queue=queue, recorder=recorder, **page_kw)
            eos_planted = args.check and args.new_tokens >= 3
            if eos_planted:
                # plant a genuine early stop: request 0's EOS is its own 2nd
                # token. Probe through an IDENTICAL engine (same mesh, same
                # slot count => same fused-tick shapes): under a mesh the
                # partitioned reduction order depends on the batch shape, so
                # a B=1 generate() probe can argmax-diverge from the pooled
                # decode on a random-init model whose logits are nearly
                # flat. The probe shares the recorder, so its compile events
                # survive adopt_compiled.
                probe_eng = Engine(params, m, n_slots=args.slots,
                                   max_len=max_len, recorder=recorder,
                                   **page_kw)
                probe = probe_eng.run([Request(rid="probe",
                                               tokens=reqs[0].tokens,
                                               max_new=2)])
                reqs[0].eos_id = int(probe[0].tokens[1])
                # the probe compiled the same prefill length + tick: reuse
                eng.adopt_compiled(probe_eng)
            comps = eng.run(reqs)

    if recorder is not None:
        if eng.kan_deployed and m.kan_backend == "cim_tiled":
            # chip placement gauges ride in the same registry as the serving
            # latency metrics: one snapshot for the whole stack
            from repro.core import kan as kanlib
            from repro.hw import chip as chip_lib
            deployed = [x for x in jax.tree_util.tree_leaves(
                eng.params,
                is_leaf=lambda x: isinstance(x, kanlib.DeployedKAN))
                if isinstance(x, kanlib.DeployedKAN)]
            for i, d in enumerate(deployed):
                prefix = "chip" if len(deployed) == 1 else f"chip{i}"
                try:
                    chip_lib.publish_report(chip_lib.chip_report(d),
                                            recorder.metrics, prefix=prefix)
                except (TypeError, ValueError) as e:
                    # stacked (vmapped) artifacts have no flat layer view
                    print(f"note: chip telemetry skipped for artifact {i}: "
                          f"{e}")
        if args.trace_out:
            print(f"trace  -> {recorder.export_trace(args.trace_out)}")
        if args.metrics_out:
            print(f"metrics -> {recorder.export_metrics(args.metrics_out)}")

    if writer is not None:
        print(f"snapshots -> {writer.stop()} ({writer.writes} writes)")
    scrape = live_snap = None
    if server is not None:
        # self-scrape the live endpoint after all telemetry has landed:
        # the text scrape must equal the registry exposition exactly
        import urllib.request
        with urllib.request.urlopen(server.url) as resp:
            scrape = resp.read().decode()
        with urllib.request.urlopen(server.url + ".json") as resp:
            live_snap = json.loads(resp.read().decode())
        print(f"scraped {server.url}: {len(scrape)} bytes "
              f"({server.scrapes} scrapes served)")
        server.stop()

    rep = router.report() if router is not None else eng.stats.report()
    kan_note = (f" kan_backend={m.kan_backend} (deployed once)"
                if eng.kan_deployed else "")
    print(f"arch={m.name} slots={args.slots} requests={args.requests} "
          f"stagger={args.stagger} mesh_model={args.mesh_model or 'none'} "
          f"replicas={args.replicas}{kan_note}")
    print(json.dumps(rep, indent=1))
    for c in comps[:4]:
        print(f"  rid={c.rid} reason={c.reason} slot={c.slot} "
              f"ticks={c.admitted_tick}->{c.finished_tick} "
              f"tokens={list(c.tokens)[:8]}")

    if args.check and scrape is not None:
        if scrape != recorder.metrics.exposition():
            raise SystemExit("metrics check FAILED: live /metrics scrape "
                             "does not match registry exposition")
        if live_snap.get("schema") != "obs/v1":
            raise SystemExit("metrics check FAILED: /metrics.json schema "
                             f"is {live_snap.get('schema')!r}, want obs/v1")
        print("metrics endpoint check OK: scrape matches exposition, "
              "snapshot schema obs/v1")

    if args.check:
        problems = []
        if router is not None:
            per = rep["per_replica"]
            if rep["completed"] != args.requests:
                problems.append(f"lost requests: completed "
                                f"{rep['completed']} != {args.requests} "
                                "submitted")
            if sum(rep["routed"]) != args.requests + rep["requeued"]:
                problems.append(
                    f"dispatch accounting does not add up: routed "
                    f"{rep['routed']} vs {args.requests} requests + "
                    f"{rep['requeued']} requeued")
            if max(r["slot_reuse"] for r in per) <= 1:
                problems.append("no slot reuse on any replica")
            if eos_planted and sum(r["evicted_eos"] for r in per) < 1:
                problems.append("no EOS eviction observed")
            if args.drain_tick and rep["drains"] < 1:
                problems.append("scheduled drain never fired")
            if args.drift_replica >= 0:
                if rep["drained_for_health"] < 1:
                    problems.append("health monitor never drained the "
                                    "degraded replica")
                if not router.draining[args.drift_replica]:
                    problems.append(f"degraded replica "
                                    f"{args.drift_replica} is not draining")
                if ref_comps is not None:
                    fleet_toks = sorted(
                        (c.rid, tuple(int(t) for t in c.tokens))
                        for c in comps)
                    ref_toks = sorted(
                        (c.rid, tuple(int(t) for t in c.tokens))
                        for c in ref_comps)
                    if fleet_toks != ref_toks:
                        problems.append(
                            "auto-drained fleet tokens differ from the "
                            "healthy single-engine reference")
            if problems:
                raise SystemExit("router check FAILED: " + "; ".join(problems))
            print(f"router check OK: zero lost requests "
                  f"({rep['completed']}/{args.requests} completed, "
                  f"{rep['requeued']} requeued), slot reuse, EOS eviction")
            if args.drift_replica >= 0:
                print(f"health check OK: replica {args.drift_replica} "
                      f"auto-drained ({rep['drained_for_health']} health "
                      "drains), tokens identical to healthy reference")
        else:
            if rep["completed"] != args.requests:
                problems.append(f"completed {rep['completed']} != "
                                f"{args.requests} submitted")
            if rep["slot_reuse"] <= 1:
                problems.append(
                    f"no slot reuse: slot_served={rep['slot_served']}")
            if eos_planted and rep["evicted_eos"] < 1:
                problems.append("no EOS eviction observed")
            if rep["evicted_eos"] + rep["evicted_length"] != rep["completed"]:
                problems.append("eviction accounting does not add up")
            if problems:
                raise SystemExit("engine check FAILED: " + "; ".join(problems))
            print("engine check OK: slot reuse, EOS eviction, full "
                  "completion")


if __name__ == "__main__":
    main()
