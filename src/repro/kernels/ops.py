"""Jitted public wrappers around the Pallas kernels.

Handles: batch-dim flattening, padding to block multiples, int8 coefficient
quantization, interpret-mode selection (CPU → interpret=True, TPU →
compiled, any other backend refused), and the QAT custom-VJP (forward =
quantized kernel, backward = straight-through float path for x, exact
expanded-basis grad for the coefficients).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import quant, splines
from repro.core.quant import ASPConfig
from repro.kernels import cim_mac as _cim
from repro.kernels import kan_fused as _kf
from repro.kernels import ssd_scan as _ssd

Array = jax.Array


def _interpret_default() -> bool:
    """Compiled on a TPU, the Pallas interpreter on the CPU; any other
    backend has no lowering for these kernels and is refused."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas TPU kernels cannot run on backend "
                           f"{backend!r}: only 'tpu' (compiled) and 'cpu' "
                           "(interpret mode) are supported")
    return backend == "cpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# Fused KAN spline (forward kernel + QAT custom VJP)
# ---------------------------------------------------------------------------

# Cap on one int8 coefficient block [S, bi, bo]; Pallas double-buffers it in
# VMEM next to the f32 planes the kernel builds from it.
_C_BLOCK_BYTES = 4 << 20


def _lane_tile(n: int, cap: int) -> int:
    """Tile for a dimension that is a lane dimension of some block: all of n
    (rounded up to 8, legal as the whole padded array dim) when n <= cap,
    else the multiple of 128 <= cap that pads n least (the larger on ties)."""
    if n <= cap:
        return _round_up(n, 8)
    return min(range(cap, 127, -128), key=lambda c: _round_up(n, c))


def _pick_blocks(b: int, i: int, o: int, s: int) -> Tuple[int, int, int]:
    """Tiles legal for the TPU lowering: block_b is a multiple of 8, block_i
    and block_o are multiples of 128 or the whole padded dim. The cap keeps
    the [S, bi, bo] int8 coefficient block within ``_C_BLOCK_BYTES``."""
    block_b = min(128, _round_up(b, 8))
    cap = max(128, min(512, int((_C_BLOCK_BYTES / s) ** 0.5) // 128 * 128))
    return block_b, _lane_tile(i, cap), _lane_tile(o, cap)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def kan_spline_fused(x: Array, coeffs: Array, asp: ASPConfig) -> Array:
    """Quantized fused spline: x [..., I] float, coeffs [I, S, O] float.

    Forward: int8-quantized coefficients through the Pallas kernel.
    Backward: STE — d/dx via the float cardinal path, d/dcoeffs via the exact
    (linear) quantized expanded basis.
    """
    return _fused_fwd_impl(x, coeffs, asp)


def kan_spline_fused_deployed(x: Array, codes: Array, scale: Array,
                              asp: ASPConfig,
                              hemi: Optional[Array] = None) -> Array:
    """Deployed-path fused forward: frozen int8 codes + per-output-channel
    scales (+ the artifact's SH-LUT) go straight into the Pallas kernel —
    no ``quantize_coeffs``/``hemi_for`` in the caller's hot loop. This is
    what ``core.kan``'s "fused" backend runs at serving time.

    x: [..., I] float (bounded); codes: [I, S, O] int8; scale: broadcastable
    to [O]. Returns [..., O] in x.dtype.
    """
    lead = x.shape[:-1]
    i = x.shape[-1]
    o = codes.shape[-1]
    s = asp.n_basis
    xf = x.reshape(-1, i)
    b = xf.shape[0]
    scale_o = scale.reshape(1, o).astype(jnp.float32)
    if hemi is None:
        hemi = quant.hemi_for(asp)

    bb, bi, bo = _pick_blocks(b, i, o, s)
    bp, ip, op = _round_up(b, bb), _round_up(i, bi), _round_up(o, bo)
    xp = jnp.pad(xf.astype(jnp.float32),
                 ((0, bp - b), (0, ip - i)), constant_values=asp.x_min)
    cp = jnp.pad(jnp.transpose(codes, (1, 0, 2)),          # slot-major
                 ((0, 0), (0, ip - i), (0, op - o)))
    sp = jnp.pad(scale_o, ((0, 0), (0, op - o)), constant_values=1.0)

    y = _kf.kan_fused(xp, cp, sp, hemi, asp=asp, block_b=bb, block_i=bi,
                      block_o=bo, interpret=_interpret_default())
    return y[:b, :o].reshape(lead + (o,)).astype(x.dtype)


def _fused_fwd_impl(x: Array, coeffs: Array, asp: ASPConfig) -> Array:
    codes, scale = quant.quantize_coeffs(coeffs, asp, axis=(0, 1))
    return kan_spline_fused_deployed(x, codes, scale, asp)


def _fused_fwd(x, coeffs, asp):
    return _fused_fwd_impl(x, coeffs, asp), (x, coeffs)


def _fused_bwd(asp, res, dy):
    x, coeffs = res
    dyf = dy.astype(jnp.float32)
    hemi = quant.hemi_for(asp)
    eq = quant.quantized_basis(x.astype(jnp.float32), hemi, asp)  # [...,I,S]
    dcoeffs = jnp.einsum("...is,...o->iso", eq, dyf).astype(coeffs.dtype)
    # STE for x: derivative of the float spline path.
    def float_path(xx):
        basis = splines.bspline_basis_uniform(
            xx, asp.x_min, asp.x_max, asp.grid_size, asp.order)
        return jnp.einsum("...is,iso->...o", basis,
                          coeffs.astype(jnp.float32))
    _, vjp = jax.vjp(float_path, x.astype(jnp.float32))
    (dx,) = vjp(dyf)
    return dx.astype(x.dtype), dcoeffs


kan_spline_fused.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# CIM MAC simulator
# ---------------------------------------------------------------------------

def cim_mac(v: Array, w_codes: Array, row_atten: Array, *,
            array_size: int, adc_bits: int = 8,
            in_scale: float = 1.0) -> Array:
    """Padded wrapper for the bit-sliced ACIM MAC kernel.

    v: [..., R] float, w_codes: [R, C] int8, row_atten: [R] float.
    R is padded to a multiple of array_size with atten=0 rows (dead rows).
    """
    lead = v.shape[:-1]
    r = v.shape[-1]
    c = w_codes.shape[-1]
    vf = v.reshape(-1, r)
    b = vf.shape[0]

    rp = _round_up(r, array_size)
    block_b = min(128, _round_up(b, 8))
    block_c = min(128, _round_up(c, 128))
    bp, cp = _round_up(b, block_b), _round_up(c, block_c)

    vp = jnp.pad(vf.astype(jnp.float32), ((0, bp - b), (0, rp - r)))
    wp = jnp.pad(w_codes, ((0, rp - r), (0, cp - c)))
    ap = jnp.pad(row_atten.astype(jnp.float32), (0, rp - r)).reshape(1, rp)

    y = _cim.cim_mac(vp, wp, ap, array_size=array_size, adc_bits=adc_bits,
                     in_scale=in_scale, block_b=block_b, block_c=block_c,
                     interpret=_interpret_default())
    return y[:b, :c].reshape(lead + (c,))


def cim_mac_tiled(v: Array, w_codes: Array, row_atten: Array, *,
                  gain: Optional[Array] = None, array_size: int,
                  adc_bits: int = 8, in_scale: float = 1.0) -> Array:
    """Padded wrapper for the multi-tile ACIM MAC kernel (hw.tiles).

    v: [..., R] float PHYSICAL-order WL values, w_codes: [R, C] int8,
    row_atten: [R] float, gain: optional [R, C] per-cell conductance
    multipliers. R must already be a tile multiple (the chip mapper pads
    rows); batch and columns are padded here. Returns [..., C] int32 —
    the digitally reduced per-tile readout codes (caller scales by LSB).
    """
    lead = v.shape[:-1]
    r = v.shape[-1]
    c = w_codes.shape[-1]
    if r % array_size:
        raise ValueError(f"R={r} not a multiple of array_size={array_size} "
                         "(the chip mapper pads rows to whole tiles)")
    vf = v.reshape(-1, r)
    b = vf.shape[0]

    block_b = min(128, _round_up(b, 8))
    block_c = min(128, _round_up(c, 128))
    bp, cp = _round_up(b, block_b), _round_up(c, block_c)

    vp = jnp.pad(vf.astype(jnp.float32), ((0, bp - b), (0, 0)))
    wp = jnp.pad(w_codes, ((0, 0), (0, cp - c)))
    if gain is None:
        gain = jnp.ones((r, c), dtype=jnp.float32)
    gp = jnp.pad(gain.astype(jnp.float32), ((0, 0), (0, cp - c)))
    ap = row_atten.astype(jnp.float32).reshape(1, r)

    y = _cim.cim_mac_tiled(vp, wp, gp, ap, array_size=array_size,
                           adc_bits=adc_bits, in_scale=in_scale,
                           block_b=block_b, block_c=block_c,
                           interpret=_interpret_default())
    return y[:b, :c].reshape(lead + (c,))


# ---------------------------------------------------------------------------
# Chunked SSD (Mamba-2) kernel
# ---------------------------------------------------------------------------

def ssd(x: Array, dt: Array, a: Array, b_mat: Array, c_mat: Array,
        d_skip: Array, *, chunk: int = 64) -> Array:
    """Padded wrapper for the chunked SSD kernel.

    x: [B, T, H, P]; dt: [B, T, H]; a/d_skip: [H]; b/c: [B, T, N].
    Returns y [B, T, H, P] f32. Pads T to a chunk multiple with dt=0 rows
    (zero step size -> decay 1, zero input: exact no-ops).
    """
    t = x.shape[1]
    pad = (-t) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0)))
    y = _ssd.ssd_scan(x, dt, a, b_mat, c_mat, d_skip, chunk=chunk,
                      interpret=_interpret_default())
    return y[:, :t]
