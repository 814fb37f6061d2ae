import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Roofline probes (§Roofline of EXPERIMENTS.md).

``compiled.cost_analysis()`` counts while-loop bodies ONCE, so the production
lowering (layer-scan + accum-scan + kv-chunk-scan) undercounts FLOPs. The
probe protocol eliminates every loop whose body carries real compute:

  * depth: lower UNROLLED models at two shallow depths d1 = first + pattern,
    d2 = first + 2*pattern; per-block cost = cost(d2) - cost(d1); full-depth
    cost = cost(d1) + per_block * (L - d1) / pattern  (layers within a stage
    are homogeneous, so the extrapolation is exact up to pattern remainders).
  * grad-accum: probes use accum=1 (same total tokens, no scan).
  * attention: probes use attn_kv_chunk = seq_len (single-iteration scan —
    correct count; memory is irrelevant because nothing is allocated).
  * remat stays ON: recompute FLOPs are real executed FLOPs (the
    MODEL_FLOPS / HLO_FLOPs ratio in the table surfaces exactly this).

Collective bytes use the same two-point extrapolation, with ring-algorithm
per-chip traffic from repro.analysis.collective_traffic.

Memory comes from the PRODUCTION lowering (launch/dryrun.py records it).

Usage: python -m benchmarks.roofline --arch qwen2_72b --shape train_4k
       python -m benchmarks.roofline --all
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict

import jax
import numpy as np

from repro import analysis
from repro import configs as cfglib
from repro.configs import SHAPES, get_arch
from repro.launch import dryrun as dr
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as tfm

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "../results/roofline")


def _probe_arch(arch, depth: int, seq_len: int):
    m = arch.model
    m2 = dataclasses.replace(
        m, n_layers=depth,
        n_enc_layers=min(m.n_enc_layers, depth) if m.n_enc_layers else 0,
        scan_layers=False, attn_kv_chunk=max(seq_len, 1))
    return dataclasses.replace(arch, model=m2, accum_steps=1)


def _lower_cost(arch, shape, mesh) -> Dict[str, float]:
    with mesh:
        fn, args = dr.build_cell(arch, shape, mesh)
        compiled = jax.jit(fn).lower(*args).compile()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    n_dev = int(np.prod(list(dict(mesh.shape).values())))
    coll = analysis.collective_traffic(hlo, n_dev)
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": coll["total"], "coll_by_kind": coll}


def model_flops(arch, shape) -> Dict[str, float]:
    """Analytic MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference), with
    N = active params for MoE."""
    m = arch.model
    params_struct = jax.eval_shape(
        lambda k: tfm.init_model(k, m, n_model=16), jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_flatten_with_path(params_struct)[0]
    total = 0
    expert = 0
    for path, leaf in leaves:
        n = int(np.prod(leaf.shape))
        total += n
        keys = [str(getattr(p, "key", "")) for p in path]
        if "moe" in keys and any(k in ("wi", "wg", "wo") for k in keys):
            expert += n
    active = total - expert
    if m.n_experts:
        active += expert * m.top_k / m.n_experts
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return {"n_params": total, "n_active": active,
            "model_flops": mult * active * tokens}


def probe_cell(arch_name: str, shape_name: str, multi_pod: bool = False,
               save: bool = True) -> Dict[str, Any]:
    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    n_dev = int(np.prod(list(dict(mesh.shape).values())))
    m = arch.model
    plen = len(m.block_pattern)
    nfirst = len(m.first_layers)
    d1, d2 = nfirst + plen, nfirst + 2 * plen
    rec: Dict[str, Any] = {"arch": arch_name, "shape": shape_name,
                           "mesh": mesh_tag, "devices": n_dev,
                           "d1": d1, "d2": d2}
    t0 = time.time()
    try:
        c1 = _lower_cost(_probe_arch(arch, d1, shape.seq_len), shape, mesh)
        c2 = _lower_cost(_probe_arch(arch, d2, shape.seq_len), shape, mesh)
        scale = (m.n_layers - d1) / plen
        est = {k: c1[k] + (c2[k] - c1[k]) * scale
               for k in ("flops", "bytes", "coll")}
        mf = model_flops(arch, shape)
        terms = analysis.roofline_terms(est["flops"], est["bytes"],
                                        est["coll"])
        rec.update({
            "ok": True, "probe_s": round(time.time() - t0, 1),
            "per_device": est,
            "coll_by_kind_d2": c2["coll_by_kind"],
            "model_flops_global": mf["model_flops"],
            "n_params": mf["n_params"], "n_active": mf["n_active"],
            "hlo_flops_global": est["flops"] * n_dev,
            "useful_flops_ratio":
                mf["model_flops"] / max(est["flops"] * n_dev, 1.0),
            **terms,
        })
    except Exception as e:
        rec.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-3000:]})
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        with open(os.path.join(
                RESULTS_DIR,
                f"{arch_name}__{shape_name}__{mesh_tag}.json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()
    cells = ([(a, s) for a, s, ok in cfglib.lm_cells() if ok]
             if args.all else [(args.arch, args.shape)])
    for a, s in cells:
        r = probe_cell(a, s, args.multi_pod)
        if r.get("ok"):
            print(f"{a} x {s}: compute={r['t_compute_s']:.4f}s "
                  f"mem={r['t_memory_s']:.4f}s coll={r['t_collective_s']:.4f}s"
                  f" dom={r['dominant']} useful={r['useful_flops_ratio']:.2f}"
                  f" ({r['probe_s']}s)", flush=True)
        else:
            print(f"{a} x {s}: FAIL {r['error']}", flush=True)


if __name__ == "__main__":
    main()
