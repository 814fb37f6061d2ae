"""Chunked Mamba-2 SSD Pallas TPU kernel.

One program per (batch, head): the chunk loop runs inside the kernel with
the recurrent state held in a VMEM scratch accumulator [P, N] — the
inter-chunk dependency never leaves VMEM, while the intra-chunk quadratic
term uses the MXU ([cl, cl] score and decay matrices per chunk).

    h_t = exp(dt_t a) h_{t-1} + dt_t x_t ⊗ B_t ;   y_t = C_t · h_t + D x_t

All decay exponents are ≤ 0 (a < 0, dt > 0): every exp() is safe.
Oracle: kernels/ref.ssd_ref (sequential scan); also cross-checked against
models/ssd.ssd_chunked (pure-JAX chunked form used by the LM stack).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a: Array, b: Array, contract=((1,), (0,))) -> Array:
    """f32 matmul at full precision (the MXU's bf16 passes would otherwise
    round the decay factors); ``contract`` picks NN / NT / TN forms."""
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _ssd_kernel(x_ref, dt_col_ref, dt_row_ref, a_ref, b_ref, c_ref, d_ref,
                y_ref, state_ref, *, chunk: int, n_chunks: int):
    """Blocks: x [1,1,T,P]; dt as a column [1,1,T,1] and as a row [1,1,1,T];
    b/c [1,T,N]; y [1,1,T,P]; a/d are the whole [H] vectors in SMEM;
    scratch state [P, N] f32.

    The in-chunk cumulative decay is needed both down the sublanes (per
    row i) and across the lanes (per column j). Both come from masked
    reductions of the two dt layouts, so no in-kernel transpose or reshape
    of a vector is needed."""
    state_ref[...] = jnp.zeros_like(state_ref)
    head = pl.program_id(1)
    a = a_ref[head]
    d_skip = d_ref[head]
    cl = chunk
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 1)
    lower = iota_i >= iota_j
    upper = iota_i <= iota_j

    def body(ci, _):
        t0 = pl.multiple_of(ci * cl, cl)
        xc = x_ref[0, 0, pl.ds(t0, cl), :].astype(jnp.float32)      # [cl, P]
        dt_col = dt_col_ref[0, 0, pl.ds(t0, cl), :].astype(jnp.float32)
        dt_row = dt_row_ref[0, 0, :, pl.ds(t0, cl)].astype(jnp.float32)
        da_col = dt_col * a                                      # [cl, 1] <= 0
        da_row = dt_row * a                                      # [1, cl] <= 0
        bc = b_ref[0, pl.ds(t0, cl), :].astype(jnp.float32)          # [cl, N]
        cc = c_ref[0, pl.ds(t0, cl), :].astype(jnp.float32)          # [cl, N]

        # inclusive cumulative decay: cs_i = sum_{k<=i} da_k
        cs_col = jnp.sum(jnp.where(lower, da_row, 0.0), axis=1,
                         keepdims=True)                          # [cl, 1]
        cs_row = jnp.sum(jnp.where(upper, da_col, 0.0), axis=0,
                         keepdims=True)                          # [1, cl]
        seg_end = jnp.sum(da_col, axis=0, keepdims=True)         # [1, 1]
        xdt = xc * dt_col                                        # [cl, P]

        # intra-chunk: L[i,j] = exp(cs_i - cs_j) for i >= j
        l_mat = jnp.where(lower, jnp.exp(cs_col - cs_row), 0.0)
        scores = _mm(cc, bc, ((1,), (1,)))                       # [cl, cl]
        y_diag = _mm(scores * l_mat, xdt)                        # [cl, P]

        # carry-in readout: y_off = (C @ state^T) * exp(cs)
        st = state_ref[...]                                      # [P, N]
        y_off = _mm(cc, st, ((1,), (1,))) * jnp.exp(cs_col)

        # state update: S = exp(seg_end) S + sum_j exp(seg_end - cs_j) xdt_j B_j
        decay_out = jnp.exp(seg_end - cs_col)                    # [cl, 1]
        upd = _mm(xdt * decay_out, bc, ((0,), (0,)))             # [P, N]
        state_ref[...] = jnp.exp(seg_end) * st + upd

        y_ref[0, 0, pl.ds(t0, cl), :] = (y_diag + y_off + d_skip * xc
                                         ).astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, n_chunks, body, ())


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret", "out_dtype"))
def ssd_scan(x: Array, dt: Array, a: Array, b_mat: Array, c_mat: Array,
             d_skip: Array, *, chunk: int = 64, interpret: bool = False,
             out_dtype=jnp.float32) -> Array:
    """x: [B, T, H, P]; dt: [B, T, H]; a/d_skip: [H]; b/c: [B, T, N].
    T % chunk == 0 (ops wrapper pads). Returns y [B, T, H, P]."""
    bsz, t, h, p = x.shape
    n = b_mat.shape[-1]
    n_chunks = t // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    dt_col = jnp.moveaxis(dt, -1, 1)[..., None]  # [B, H, T, 1]
    dt_row = jnp.moveaxis(dt, -1, 1)[:, :, None]  # [B, H, 1, T]
    x3 = jnp.moveaxis(x, 2, 1)                   # [B, H, T, P]
    y = pl.pallas_call(
        kernel,
        grid=(bsz, h),
        in_specs=[
            pl.BlockSpec((1, 1, t, p), lambda b, hh: (b, hh, 0, 0)),
            pl.BlockSpec((1, 1, t, 1), lambda b, hh: (b, hh, 0, 0)),
            pl.BlockSpec((1, 1, 1, t), lambda b, hh: (b, hh, 0, 0)),
            smem,
            pl.BlockSpec((1, t, n), lambda b, hh: (b, 0, 0)),
            pl.BlockSpec((1, t, n), lambda b, hh: (b, 0, 0)),
            smem,
        ],
        out_specs=pl.BlockSpec((1, 1, t, p), lambda b, hh: (b, hh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, t, p), out_dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(x3, dt_col, dt_row, a.astype(jnp.float32), b_mat, c_mat,
      d_skip.astype(jnp.float32))
    return jnp.moveaxis(y, 1, 2)                 # [B, T, H, P]
