"""Training launcher: restart-safe, preemption-aware, mesh-aware.

    python -m repro.launch.train --arch qwen2_72b --steps 200 \
        --ckpt-dir /tmp/ck --host-mesh    # CPU-host execution (examples/tests)

On a real cluster the same entry point runs under the production mesh
(--production-mesh lowers against 256 chips; on this CPU container that
combination is only useful with --dry-run, which delegates to launch.dryrun).

Fault-tolerance behaviour:
  * resumes from the latest complete checkpoint in --ckpt-dir (params,
    optimizer state, data-stream index),
  * SIGTERM/SIGINT trigger a final synchronous checkpoint then exit 0,
  * async checkpoint every --save-every steps,
  * straggler incidents (step > 2.5x rolling median) are logged.
"""
import argparse
import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as ckpt
from repro.configs import get_arch
from repro.data import lm_synth
from repro.dist import fault
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import transformer as tfm
from repro.optim import make_optimizer, warmup_cosine
from repro.train.train_step import TrainConfig, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--host-mesh", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--kan-backend", default="",
                    help="override ModelConfig.kan_backend (the training "
                         "path dispatches through the same core.kan "
                         "registry as serving)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    arch = get_arch(args.arch, smoke=args.smoke)
    m = arch.model
    if args.kan_backend:
        m = dataclasses.replace(m, kan_backend=args.kan_backend)
    mesh = make_host_mesh(args.model_parallel) if args.host_mesh else None

    opt = make_optimizer(arch.optimizer,
                         warmup_cosine(arch.learning_rate, 10, args.steps))
    tcfg = TrainConfig(accum_steps=1, grad_dtype=arch.grad_dtype)
    step_fn = jax.jit(make_train_step(m, opt, tcfg), donate_argnums=(0, 1))

    key = jax.random.PRNGKey(0)
    n_model = args.model_parallel if mesh else 1
    params = tfm.init_model(key, m, n_model=n_model)
    opt_state = opt.init(params)
    dcfg = lm_synth.LMDataConfig(vocab=m.vocab, batch=args.batch,
                                 seq_len=args.seq)
    start = 0

    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        (params, opt_state), extra = ckpt.restore(
            args.ckpt_dir, (params, opt_state))
        start = extra.get("step", 0)
        print(f"resumed from step {start}", flush=True)

    pre = fault.PreemptionHandler()
    mon = fault.StepMonitor()
    pending_save = None

    def run():
        nonlocal params, opt_state, pending_save
        for step in range(start, args.steps):
            mon.start_step(step)
            b = lm_synth.batch_at(dcfg, step)
            batch = {k: jnp.asarray(v) for k, v in b.items()}
            if m.frontend == "audio_stub":
                batch["frames"] = jax.random.normal(
                    jax.random.PRNGKey(step), (args.batch, args.seq,
                                               m.d_model))
            if m.frontend == "vision_stub":
                batch["vision_embeds"] = jax.random.normal(
                    jax.random.PRNGKey(step),
                    (args.batch, m.n_vision_patches, m.d_model))
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            inc = mon.end_step()
            if inc:
                print(f"[straggler] step {inc.step}: {inc.duration:.2f}s vs "
                      f"median {inc.median:.2f}s", flush=True)
            if step % args.log_every == 0:
                print(f"step {step}: loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f}", flush=True)
            if args.ckpt_dir and (step + 1) % args.save_every == 0:
                if pending_save is not None:
                    pending_save.join()
                pending_save = ckpt.save_async(
                    args.ckpt_dir, step + 1, (params, opt_state),
                    extra={"step": step + 1})
            if pre.should_stop:
                print("preemption signal: checkpointing and exiting",
                      flush=True)
                if args.ckpt_dir:
                    ckpt.save(args.ckpt_dir, step + 1, (params, opt_state),
                              extra={"step": step + 1})
                return
        if args.ckpt_dir:
            if pending_save is not None:
                pending_save.join()
            ckpt.save(args.ckpt_dir, args.steps, (params, opt_state),
                      extra={"step": args.steps})

    if mesh is not None:
        with mesh:
            run()
    else:
        run()
    if pending_save is not None:
        pending_save.join()
    print("done", flush=True)


if __name__ == "__main__":
    main()
