"""Quickstart: the paper's full pipeline on one KAN layer in ~30 seconds.

    PYTHONPATH=src python examples/quickstart.py

1. build a KAN layer, deploy it ONCE (``kan.deploy``: int8 codes + scales,
   SH-LUT, bit-slices, SAM row map) and evaluate the frozen artifact on all
   four registered backends through the single ``kan.apply`` entry point
   (float oracle, ASP-KAN-HAQ LUT baseline, fused Pallas kernel, simulated
   RRAM-ACIM crossbar with/without KAN-SAM),
2. show the ASP-KAN-HAQ structure (shared hemi-LUT, PowerGap decode),
3. price the whole thing with the calibrated 22nm cost model.
"""
import dataclasses

import jax
import jax.numpy as jnp

from repro.core import kan, kan_sam
from repro.core.quant import ASPConfig
from repro.hw import cim, cost_model, input_gen

key = jax.random.PRNGKey(0)
asp = ASPConfig(grid_size=8, order=3, n_bits=8)
print(f"ASP-KAN-HAQ: G={asp.grid_size} K={asp.order} n={asp.n_bits} "
      f"=> LD={asp.ld}, {asp.levels_per_interval} levels/knot-interval, "
      f"input range [0, {asp.n_levels - 1}]")

# one KAN layer; train-time params, then a frozen artifact per backend
spec = kan.KANSpec.single(in_dim=64, out_dim=32, asp=asp)
params = kan.init(key, spec)
x = jax.random.uniform(jax.random.fold_in(key, 1), (128, 64),
                       minval=-1, maxval=1)

deployed = {b: kan.deploy(params, spec.with_backend(b))
            for b in ("ref", "lut", "fused")}
hemi = deployed["lut"].layers[0].hemi
print(f"SH-LUT (from the deployed artifact): {hemi.shape[0]}x{hemi.shape[1]} "
      f"entries (vs {asp.n_basis * 2**asp.n_bits} for per-basis "
      "conventional LUTs)")

y_float = kan.train_apply(params, x, spec.with_backend("ref"))
y_ref = kan.apply(deployed["ref"], x)
y_q = kan.apply(deployed["lut"], x)
y_f = kan.apply(deployed["fused"], x)
print(f"float vs deployed-lut err: "
      f"{float(jnp.abs(y_float - y_q).max()):.4f} (8-bit quantization)")
print(f"deployed-ref vs deployed-lut err: "
      f"{float(jnp.abs(y_ref - y_q).max()):.4f} (input quantization only)")
print(f"deployed-lut vs fused Pallas kernel err: "
      f"{float(jnp.abs(y_q - y_f).max()):.2e} "
      f"(same frozen artifact, a few ulp apart — pinned in "
      "tests/test_kan_backends.py)")

# CIM crossbar backend with/without KAN-SAM: same deploy/apply contract
stats = kan_sam.update_stats(kan_sam.init_stats(64, asp), x, asp)
ccfg = cim.CIMConfig(array_size=512)
cim_spec = spec.with_backend("cim", cim=ccfg)
ideal_spec = dataclasses.replace(
    cim_spec, cim=dataclasses.replace(ccfg, gamma0=0.0))
y_ideal = kan.apply(kan.deploy(params, ideal_spec), x)
norm = float(jnp.mean(jnp.abs(y_ideal))) + 1e-9
e_uni = float(jnp.mean(jnp.abs(
    kan.apply(kan.deploy(params, cim_spec), x) - y_ideal))) / norm
dep_sam = kan.deploy(params, dataclasses.replace(cim_spec, use_sam=True),
                     stats=stats)
e_sam = float(jnp.mean(jnp.abs(kan.apply(dep_sam, x) - y_ideal))) / norm
print(f"RRAM-ACIM MAC error: uniform={e_uni:.4f}, KAN-SAM={e_sam:.4f} "
      f"(artifact carries the row map: atten[{dep_sam.layers[0].atten.shape}]"
      f", slices{tuple(dep_sam.layers[0].slices.shape)})")

# cost model
c = cost_model.accelerator_cost(64 * asp.n_basis * 32)
t = input_gen.scheme_table(3)
print(f"cost model: {c.area_mm2:.4f} mm^2, {c.power_w*1e3:.2f} mW; "
      f"TM-DV-IG FOM vs voltage: {t['tmdv'].fom/t['voltage'].fom:.1f}x")
print("OK")
