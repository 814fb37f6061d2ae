"""Fused KAN spline layer Pallas TPU kernel.

The paper's ACIM dataflow (B_i(x) on word lines × ci' in the crossbar) maps
onto the MXU as ``E @ C`` where ``E`` is the expanded basis. The baseline JAX
implementation materializes ``E`` in HBM — a (G+K)× activation blow-up that
makes the layer memory-bound. This kernel fuses the whole chain in VMEM:

    x  ──quantize──► q ──PowerGap──► (seg = q >> LD, loc = q & (L-1))
       ──SH-LUT (select chain over the hemi rows + reflection)──► K+1 taps
       ──local→global routing (compare-select == the paper's DEMUX)──► E_j
       ──MXU──► acc += Σ_j E_j @ dequant(C_j)

``E`` never leaves VMEM; coefficients are stored int8 in HBM (the paper's
8-bit ci') and dequantized in registers, cutting weight traffic 2× vs bf16.

Tiling: grid = (B/bm, O/bo, I/bi), contraction over the I axis innermost with
an f32 VMEM accumulator. Coefficients are laid out slot-major [S, I, O]
(S = G+K), so a C block [S, bi, bo] holds S aligned [bi, bo] planes and the
tile's contraction is S matmuls ``E_j @ C_j`` of the per-slot basis planes
``E_j [bm, bi]``: every value keeps the [bm, bi] layout of the x tile, and
nothing is reshaped in VMEM. bi is a multiple of 128 or the whole (padded) I
and bo a multiple of 128 (ops.py picks them and pads).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import ASPConfig

Array = jax.Array


def _kan_fused_kernel(x_ref, c_ref, scale_ref, hemi_ref, out_ref, acc_ref, *,
                      asp: ASPConfig, n_i_blocks: int):
    """One (bm × bo) output tile; grid dim 2 walks the I contraction."""
    i_blk = pl.program_id(2)

    @pl.when(i_blk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k1 = asp.n_taps                       # K+1
    s = asp.n_basis                       # G+K
    lvl = asp.levels_per_interval         # L = 2^LD
    half = hemi_ref.shape[0]              # ceil(L/2)

    x = x_ref[...].astype(jnp.float32)    # [bm, bi]

    # --- quantize (ASP-KAN-HAQ aligned grid) ---
    q = jnp.floor((x - asp.x_min) / asp.step)
    q = jnp.clip(q, 0, asp.n_levels - 1).astype(jnp.int32)

    # --- PowerGap decode: global segment via shift, local via mask ---
    seg = jax.lax.shift_right_logical(q, asp.ld)
    loc = jax.lax.bitwise_and(q, lvl - 1)

    # --- SH-LUT lookup: select chain over the hemi rows (SMEM scalars).
    # Reflection (loc >= half) reads row L-1-loc with the taps reversed.
    refl = loc >= half
    idx = jnp.where(refl, lvl - 1 - loc, loc)
    rows = [jnp.zeros_like(x) for _ in range(k1)]
    for h in range(half):
        hit = idx == h
        for t in range(k1):
            rows[t] = jnp.where(hit, hemi_ref[h, t], rows[t])
    taps = [jnp.where(refl, rows[k1 - 1 - t], rows[t]) for t in range(k1)]

    # --- local→global routing + MXU contraction, one basis slot at a time:
    # slot j holds tap j - seg when 0 <= j - seg <= K (the TPU form of the
    # paper's PowerGap DEMUX). Slot j's coefficient plane is c_ref[j].
    acc = acc_ref[...]
    for j in range(s):
        e_j = jnp.zeros_like(x)
        for t in range(k1):
            e_j = jnp.where(seg == j - t, taps[t], e_j)
        acc += jax.lax.dot(e_j, c_ref[j].astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(i_blk == n_i_blocks - 1)
    def _finalize():
        out_ref[...] = (acc_ref[...] *
                        scale_ref[...].astype(jnp.float32)
                        ).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("asp", "block_b", "block_i", "block_o", "interpret",
                     "out_dtype"))
def kan_fused(x: Array, c_codes: Array, scale: Array, hemi: Array, *,
              asp: ASPConfig, block_b: int = 128, block_i: int = 128,
              block_o: int = 128, interpret: bool = False,
              out_dtype=jnp.float32) -> Array:
    """Fused KAN spline forward.

    x: [B, I] float (bounded); c_codes: [S, I, O] int8 (slot-major, so each
    basis slot's coefficient plane is one aligned [I, O] tile); scale:
    [1, O] f32; hemi: [half, K+1] f32. B % block_b == 0, I % block_i == 0,
    O % block_o == 0 (ops.py pads). Returns [B, O] out_dtype.
    """
    b, i = x.shape
    o = c_codes.shape[-1]
    s = asp.n_basis
    assert c_codes.shape == (s, i, o), (c_codes.shape, (s, i, o))
    nb, ni, no = b // block_b, i // block_i, o // block_o

    kernel = functools.partial(_kan_fused_kernel, asp=asp, n_i_blocks=ni)
    return pl.pallas_call(
        kernel,
        grid=(nb, no, ni),
        in_specs=[
            pl.BlockSpec((block_b, block_i), lambda bb, oo, ii: (bb, ii)),
            pl.BlockSpec((s, block_i, block_o), lambda bb, oo, ii: (0, ii, oo)),
            pl.BlockSpec((1, block_o), lambda bb, oo, ii: (0, oo)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_b, block_o), lambda bb, oo, ii: (bb, oo)),
        out_shape=jax.ShapeDtypeStruct((b, o), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_b, block_o), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x, c_codes, scale, hemi.astype(jnp.float32))
