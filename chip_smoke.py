"""Bring-up check: the serving path end to end on a TPU.

    python chip_smoke.py [--seed N]      # one chip: phases A and B
    python chip_smoke.py --four-chips    # four chips: the multi-chip paths

One chip (the default):

A. mamba2-1.3b at its published width (48 layers, d_model 2048, vocab
   50280, bf16 activations, f32 params; random weights from ``--seed``)
   through ``get_arch`` -> ``init_model`` -> ``Engine`` -> ``Engine.run``:
   4 slots, 6 requests from ``synth_trace`` with 256-512 prompt tokens
   (the SSD chunk is 256, so chunked prefill does real work) and 16 new
   tokens each. Checks: every request completes, every token is in
   [0, vocab), one prefill's logits are finite, and request 0's tokens
   equal a solo engine's (batching invariance).
B. kan_llm, the paper's KAN-FFN LLM (4 layers, d_model 256), served once
   per serving backend (``lut``, ``lut_int8``, ``fused``): each engine
   deploys its KAN artifacts once and serves 6 requests to completion.
   The ``fused`` engine's decode tick must compile to a Mosaic kernel call
   (``tpu_custom_call`` in its HLO), and ``fused`` must agree with ``lut``
   on one deployed KAN layer (tolerance stated at ``FUSED_VS_LUT_TOL``).

Four chips (``--four-chips``) runs only the paths that span chips, each
beside what it is compared with:

a. ``Router`` over four one-chip mamba2-1.3b replicas, replica i pinned to
   ``jax.devices()[i]``: its completion tokens must equal one ``Engine``'s
   on the same trace.
b. An ``Engine`` under a (data=1, model=4) mesh: it serves the trace, and
   its prefill logits are compared with the one-chip logits (tolerance
   stated at ``SHARDED_LOGITS_FACTOR``).

Every phase prints its compile and run seconds and its checks on earlier
lines. The script exits non-zero when JAX finds no TPU and when any check
fails; the last line of stdout is then never printed. On success it is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import sys
import time
from pathlib import Path

SLOTS = 4
N_REQUESTS = 6
NEW_TOKENS = 16
PROMPT_RANGE = (256, 512)
KAN_BACKENDS = ("lut", "lut_int8", "fused")

# fused vs lut on one deployed KAN layer, as a fraction of the sum of the
# absolute terms sum_is |e_is| |c_iso| * scale_o of each output. Both
# contract the same basis values with the same int8 codes. The fused kernel
# multiplies at full f32 precision; XLA's dot in the lut backend runs at the
# TPU's default matmul precision, which rounds the f32 basis values to bf16
# (relative error <= 2^-9; int8 codes are exact in bf16). The f32 sums add
# at most ~(I*S)*2^-24 ≈ 2e-4 more, so 2^-8 bounds the difference.
FUSED_VS_LUT_TOL = 2.0 ** -8

# the sharded prefill may differ from the one-chip prefill only by rounding:
# its max deviation must stay within this factor of the deviation that bf16
# rounding alone causes (the one-chip bf16 logits vs the same model with f32
# activations and full-precision matmuls).
SHARDED_LOGITS_FACTOR = 2.0


def log(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")
    log(check=what, ok=True)


def mamba_trace(vocab: int, seed: int):
    from repro.serve.engine import synth_trace
    return synth_trace(vocab, N_REQUESTS, min_prompt=PROMPT_RANGE[0],
                       max_prompt=PROMPT_RANGE[1], min_new=NEW_TOKENS,
                       max_new=NEW_TOKENS, stagger=2, seed=seed)


def init_params(cfg, seed: int):
    """Random weights from ``seed``, built on the device in one program."""
    import jax
    from repro.models import transformer as tfm
    t0 = time.perf_counter()
    params = jax.jit(functools.partial(tfm.init_model, cfg=cfg))(
        jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    return params, time.perf_counter() - t0


def token_map(comps):
    return {c.rid: tuple(int(t) for t in c.tokens) for c in comps}


def check_completions(comps, reqs, vocab: int, label: str) -> None:
    got = token_map(comps)
    check(sorted(got) == sorted(r.rid for r in reqs),
          f"{label}: all {len(reqs)} requests completed")
    want = {r.rid: r.max_new for r in reqs}
    check(all(len(t) == want[rid] for rid, t in got.items()),
          f"{label}: every request got its max_new tokens")
    check(all(0 <= t < vocab for toks in got.values() for t in toks),
          f"{label}: every token in [0, {vocab})")


def served(label: str, eng, reqs, rec):
    """Run ``reqs`` through ``eng``; log compile vs run seconds and the
    EngineStats report."""
    comps = eng.run(list(reqs))
    compile_s = sum(e.wall_s for e in rec.compile_events)
    log(phase=label, compiles=len(rec.compile_events), compile_s=compile_s,
        run_s=eng.stats.wall_s - compile_s, wall_s=eng.stats.wall_s)
    log(phase=label, engine_stats=eng.stats.report())
    return comps


def prefill_logits(params, cfg, tokens, max_len: int):
    import jax
    import jax.numpy as jnp
    from repro.serve import decode as dec
    fn = jax.jit(lambda p, t: dec.prefill(p, cfg, {"tokens": t},
                                          max_len=max_len,
                                          last_only=True)[0])
    t0 = time.perf_counter()
    out = fn(params, jnp.asarray(tokens, jnp.int32)[None])
    out = jax.block_until_ready(out).astype(jnp.float32)
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_a(seed: int) -> None:
    """mamba2-1.3b at full width through the engine."""
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.obs import EngineRecorder
    from repro.serve.engine import Engine
    from repro.serve.scheduler import Request

    m = get_arch("mamba2_1p3b").model
    params, init_s = init_params(m, seed)
    log(phase="A", model=m.name, n_layers=m.n_layers, d_model=m.d_model,
        vocab=m.vocab, init_s=init_s)
    reqs = mamba_trace(m.vocab, seed)
    max_len = PROMPT_RANGE[1] + NEW_TOKENS
    rec = EngineRecorder()
    eng = Engine(params, m, n_slots=SLOTS, max_len=max_len, recorder=rec)
    comps = served("A", eng, reqs, rec)
    check_completions(comps, reqs, m.vocab, "A")

    solo = Engine(eng.params, m, n_slots=SLOTS,
                  max_len=max_len).adopt_compiled(eng)
    r0 = reqs[0]
    solo_comps = solo.run([Request(rid=r0.rid, tokens=r0.tokens,
                                   max_new=r0.max_new)])
    check(token_map(solo_comps)[r0.rid] == token_map(comps)[r0.rid],
          "A: request 0 tokens equal its solo engine run")

    logits, wall = prefill_logits(eng.params, m, r0.tokens, max_len)
    log(phase="A", prefill_logits_s=wall, prompt_len=len(r0.tokens),
        logits_abs_max=float(jnp.max(jnp.abs(logits))),
        argmax_equals_first_token=int(jnp.argmax(logits[0, -1]))
        == token_map(comps)[r0.rid][0])
    check(bool(jnp.all(jnp.isfinite(logits))), "A: prefill logits finite")


def phase_b(seed: int) -> None:
    """kan_llm served on each KAN backend; fused compiled, fused ~ lut."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.core import kan, quant
    from repro.obs import EngineRecorder
    from repro.serve import engine as engine_lib

    base = get_arch("kan_llm").model
    params, init_s = init_params(base, seed)
    log(phase="B", model=base.name, n_layers=base.n_layers,
        d_model=base.d_model, init_s=init_s)
    reqs = engine_lib.synth_trace(base.vocab, N_REQUESTS, min_prompt=16,
                                  max_prompt=64, min_new=8, max_new=8,
                                  stagger=1, seed=seed)
    engines = {}
    for backend in KAN_BACKENDS:
        m = dataclasses.replace(base, kan_backend=backend)
        rec = EngineRecorder()
        eng = engine_lib.Engine(params, m, n_slots=SLOTS, max_len=64 + 8,
                                recorder=rec)
        check(eng.kan_deployed, f"B/{backend}: KAN artifacts deployed")
        comps = served(f"B/{backend}", eng, reqs, rec)
        check_completions(comps, reqs, m.vocab, f"B/{backend}")
        engines[backend] = eng

    eng = engines["fused"]
    zeros = jnp.zeros((eng.n_slots,), jnp.int32)
    pages = jnp.zeros((eng.n_slots, eng.n_slot_pages), jnp.int32)
    tick = jax.jit(functools.partial(engine_lib._decode_fn, cfg=eng.cfg))
    hlo = tick.lower(eng.params, eng.cache, zeros, zeros,
                     pages).compile().as_text()
    check("tpu_custom_call" in hlo,
          "B/fused: compiled decode tick calls the Pallas kernel")

    # one deployed KAN layer (layer 0's up projection) through both backends
    spec = eng.cfg.kan_spec
    lspec = spec.layer(0)
    art = eng.params["stages"][0]["l0"]["kan"]
    layer = jax.tree.map(lambda a: a[0], art.layers[0])
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (32, lspec.in_dim))
    xb = kan.bound_input(x, lspec.asp)
    y = {b: kan.get_backend(b).run(layer, lspec, spec, xb)
         for b in ("fused", "lut")}
    with jax.default_matmul_precision("highest"):
        e = quant.quantized_basis(xb, layer.hemi, lspec.asp)
        terms = jnp.einsum("bis,iso->bo", jnp.abs(e),
                           jnp.abs(layer.codes.astype(jnp.float32)))
        terms = terms * layer.scale.reshape(-1)
    diff = jnp.abs(y["fused"] - y["lut"])
    ratio = float(jnp.max(diff / jnp.maximum(terms, jnp.finfo(jnp.float32)
                                             .tiny)))
    log(phase="B", fused_vs_lut_max_diff=float(jnp.max(diff)),
        fused_vs_lut_ratio=ratio, tolerance=FUSED_VS_LUT_TOL)
    check(ratio <= FUSED_VS_LUT_TOL,
          f"B: fused within {FUSED_VS_LUT_TOL} of lut (per-output "
          "sum of |terms|)")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_router(seed: int) -> None:
    """(a) Router over four pinned one-chip replicas vs one Engine."""
    import jax
    from repro.configs import get_arch
    from repro.obs import EngineRecorder
    from repro.serve.engine import Engine, make_replicas
    from repro.serve.router import Router

    m = get_arch("mamba2_1p3b").model
    params, init_s = init_params(m, seed)
    reqs = mamba_trace(m.vocab, seed)
    geometry = dict(n_slots=SLOTS, max_len=PROMPT_RANGE[1] + NEW_TOKENS)
    rec = EngineRecorder()
    fleet = make_replicas(params, m, 4, recorder_for=rec.for_replica,
                          **geometry)
    for i, eng in enumerate(fleet):
        placed = {d for leaf in jax.tree.leaves(eng.params)
                  for d in leaf.devices()}
        check(placed == {jax.devices()[i]},
              f"a: replica {i} params on device {i}")
    router = Router(fleet)
    t0 = time.perf_counter()
    comps = router.run(list(reqs))
    wall = time.perf_counter() - t0
    compile_s = sum(e.wall_s for e in rec.compile_events)
    log(phase="a", init_s=init_s, compiles=len(rec.compile_events),
        compile_s=compile_s, run_s=wall - compile_s, wall_s=wall)
    log(phase="a", router=router.report())
    check_completions(comps, reqs, m.vocab, "a")

    one = Engine(fleet[0].params, m, device=fleet[0].device,
                 **geometry).adopt_compiled(fleet[0])
    ref = one.run(list(reqs))
    check(sorted(token_map(comps).items()) == sorted(token_map(ref).items()),
          "a: router completion tokens identical to one Engine")


def phase_mesh(seed: int) -> None:
    """(b) Engine under a (data=1, model=4) mesh vs the one-chip logits."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.launch.mesh import make_host_mesh
    from repro.obs import EngineRecorder
    from repro.serve.engine import Engine

    m = get_arch("mamba2_1p3b").model
    params, init_s = init_params(m, seed)
    reqs = mamba_trace(m.vocab, seed)
    max_len = PROMPT_RANGE[1] + NEW_TOKENS
    prompt = reqs[0].tokens

    one_chip, wall_1 = prefill_logits(params, m, prompt, max_len)
    with jax.default_matmul_precision("highest"):
        ref32, wall_32 = prefill_logits(
            params, dataclasses.replace(m, dtype=jnp.float32), prompt,
            max_len)
    bf16_dev = float(jnp.max(jnp.abs(one_chip - ref32)))

    mesh = make_host_mesh(model=4)
    log(phase="b", mesh=dict(mesh.shape), init_s=init_s)
    with mesh:
        rec = EngineRecorder()
        eng = Engine(params, m, n_slots=SLOTS, max_len=max_len, recorder=rec)
        comps = served("b", eng, reqs, rec)
        sharded, wall_4 = prefill_logits(eng.params, m, prompt, max_len)
    check_completions(comps, reqs, m.vocab, "b")
    dev = float(jnp.max(jnp.abs(sharded - one_chip)))
    log(phase="b", prefill_one_chip_s=wall_1, prefill_f32_s=wall_32,
        prefill_sharded_s=wall_4,
        logits_abs_max=float(jnp.max(jnp.abs(one_chip))),
        sharded_vs_one_chip_max_diff=dev, bf16_vs_f32_max_diff=bf16_dev,
        tolerance=SHARDED_LOGITS_FACTOR * bf16_dev)
    check(bool(jnp.all(jnp.isfinite(sharded))), "b: sharded logits finite")
    check(dev <= SHARDED_LOGITS_FACTOR * bf16_dev,
          f"b: sharded prefill logits within {SHARDED_LOGITS_FACTOR}x the "
          "bf16-vs-f32 deviation of the one-chip logits")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths (needs 4 devices)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's backend is {backend!r}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 1
    log(jax=jax.__version__, device_kind=devices[0].device_kind,
        device_count=len(devices), compile_cache=enable_compile_cache())

    phases = ((phase_router, phase_mesh) if args.four_chips
              else (phase_a, phase_b))
    for phase in phases:
        t0 = time.perf_counter()
        phase(args.seed)
        gc.collect()        # free the phase's device buffers before the next
        log(phase=phase.__name__, done_s=time.perf_counter() - t0,
            peak_hbm_bytes=[(d.memory_stats() or {}).get("peak_bytes_in_use")
                            for d in devices])

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
