"""Unified KAN execution API: backend registry + two-phase deploy/apply.

The paper's pipeline is train-with-QAT → quantize → KAN-SAM row-map →
program the crossbar → serve frozen integer artifacts. This module is the
"program" step as an API contract:

* **KANSpec** — one static description of a KAN stack (a single layer, an
  FFN, or the CF-KAN autoencoder), subsuming the legacy
  ``KANLayerConfig``/``KANFFNConfig`` pair.
* **register_backend(name)** — the deployment axis. Six built-ins:
    - ``ref``   : float Cox–de Boor oracle (accuracy ground truth),
    - ``lut``   : ASP-KAN-HAQ quantized expanded-basis matmul on the MXU
                  (the ACIM-faithful dataflow; previously ``baseline``),
    - ``lut_int8``: int8 expanded basis × int8 codes with int32
                  accumulation end to end — no f32 dequant before the
                  contraction (the ROADMAP's int8-MXU backend),
    - ``fused`` : Pallas TPU kernel — quantize → SH-LUT → expand → contract
                  fused in VMEM,
    - ``cim``   : bit-sliced RRAM crossbar simulator with optional KAN-SAM
                  row mapping (previously a private pipeline in cf_kan),
    - ``cim_tiled``: multi-tile ACIM chip simulator (hw.tiles/chip) —
                  per-tile IR drop/ADC/variation, int32 digital partial-sum
                  reduction, empty-row compaction + within-tile KAN-SAM
                  (``spec.cim`` holds a ``hw.chip.ChipConfig``).
* **deploy(params, spec, stats=None) → DeployedKAN** — compile-time artifact
  construction, done ONCE: int8 coefficient codes + per-output-channel
  scales, the SH-LUT, the bit-sliced programming image, and the KAN-SAM row
  order/attenuation. ``DeployedKAN`` is a frozen pytree: it jits, donates,
  scans and shards like any parameter tree.
* **apply(deployed, x) → y** — run-time evaluation against the frozen
  artifact. The hot path contains no ``quantize_coeffs``/``hemi_for`` calls;
  ``trace_requantizes`` below pins that property in tests and CI.
* **train_apply(params, x, spec, qat=...)** — the training twin: same
  backend dispatch, float master weights, fake-quant/STE when ``qat=True``.
  Its QAT forward numerically equals the deployed integer forward
  (pinned in tests/test_kan_backends.py).

Extending: subclass ``KANBackend`` and decorate with
``@register_backend("my-backend")`` — the ``lut_int8`` int8-MXU backend
and the ``cim_tiled`` chip simulator landed exactly this way, without
touching any call site.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

from repro.core import quant, splines
from repro.core.quant import ASPConfig

Array = jax.Array


# ---------------------------------------------------------------------------
# Spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KANLayerShape:
    """Resolved (in, out, asp) view of one layer of a KANSpec."""
    in_dim: int
    out_dim: int
    asp: ASPConfig

    @property
    def n_rows(self) -> int:
        """Crossbar rows of the expanded coefficient matrix (I * (G+K))."""
        return self.in_dim * self.asp.n_basis


@dataclasses.dataclass(frozen=True)
class KANSpec:
    """Static description of a KAN stack: ``dims = (d0, d1, ..., dn)`` is a
    chain of ``n`` KAN layers; ``asp`` is one ASPConfig per layer (a single
    ASPConfig broadcasts). Subsumes the legacy KANLayerConfig (one layer,
    flat params) and KANFFNConfig (two layers named up/down).

    Param-tree convention: a single layer with no ``layer_names`` owns a
    flat ``{"coeffs", "w_base"}`` dict; multi-layer specs nest one such dict
    per layer under ``layer_names`` (default ``l0, l1, ...``).
    """
    dims: Tuple[int, ...]
    asp: Tuple[ASPConfig, ...] = (ASPConfig(),)
    backend: str = "lut"
    base_activation: str = "relu"   # "" disables the b(x) residual branch
    bound_input: bool = True        # tanh-bound inputs into the knot range
    dtype: Any = jnp.float32
    layer_names: Tuple[str, ...] = ()
    # cim/cim_tiled backends only: crossbar config + KAN-SAM mapping toggle
    # (cim takes a hw.cim.CIMConfig, cim_tiled a hw.chip.ChipConfig)
    cim: Any = None
    use_sam: bool = False

    def __post_init__(self):
        dims = tuple(int(d) for d in self.dims)
        if len(dims) < 2:
            raise ValueError(f"KANSpec.dims needs >= 2 entries, got {dims}")
        object.__setattr__(self, "dims", dims)
        asp = self.asp
        if isinstance(asp, ASPConfig):
            asp = (asp,)
        asp = tuple(asp)
        if len(asp) == 1:
            asp = asp * (len(dims) - 1)
        if len(asp) != len(dims) - 1:
            raise ValueError(f"{len(asp)} ASPConfigs for {len(dims)-1} layers")
        object.__setattr__(self, "asp", asp)
        names = tuple(self.layer_names)
        if names and len(names) != len(dims) - 1:
            raise ValueError(f"{len(names)} layer_names for "
                             f"{len(dims)-1} layers")
        object.__setattr__(self, "layer_names", names)

    @property
    def n_layers(self) -> int:
        """Number of KAN layers (``len(dims) - 1``)."""
        return len(self.dims) - 1

    @property
    def names(self) -> Optional[Tuple[str, ...]]:
        """Param-subtree keys; None means flat single-layer params."""
        if self.layer_names:
            return self.layer_names
        if self.n_layers == 1:
            return None
        return tuple(f"l{i}" for i in range(self.n_layers))

    def layer(self, i: int) -> KANLayerShape:
        """Resolved (in, out, asp) shape of layer ``i``."""
        return KANLayerShape(self.dims[i], self.dims[i + 1], self.asp[i])

    def with_backend(self, backend: str, **kw) -> "KANSpec":
        """Copy of the spec targeting another backend (plus overrides)."""
        return dataclasses.replace(self, backend=backend, **kw)

    @classmethod
    def single(cls, in_dim: int, out_dim: int,
               asp: ASPConfig = ASPConfig(), **kw) -> "KANSpec":
        """One KAN layer with flat {"coeffs", "w_base"} params."""
        return cls(dims=(in_dim, out_dim), asp=(asp,), **kw)

    @classmethod
    def ffn(cls, d_model: int, hidden: int, asp: ASPConfig, **kw) -> "KANSpec":
        """Transformer KAN-FFN: d_model -> hidden -> d_model (up/down)."""
        kw.setdefault("layer_names", ("up", "down"))
        return cls(dims=(d_model, hidden, d_model), asp=(asp,), **kw)


def param_count(spec: KANSpec) -> int:
    """Trainable parameter count of the spec (coeffs + base weights)."""
    n = 0
    for i in range(spec.n_layers):
        ls = spec.layer(i)
        n += ls.in_dim * ls.asp.n_basis * ls.out_dim
        if spec.base_activation:
            n += ls.in_dim * ls.out_dim
    return n


def _layer_params(params, spec: KANSpec, i: int) -> Dict[str, Array]:
    names = spec.names
    return params if names is None else params[names[i]]


def _layer_stats(stats, spec: KANSpec, i: int):
    if stats is None:
        return None
    names = spec.names
    if names is None:
        return stats
    return stats.get(names[i]) if isinstance(stats, dict) else stats


# ---------------------------------------------------------------------------
# Shared math primitives (single source of truth; every backend below
# builds on these).
# ---------------------------------------------------------------------------

def bound_input(x: Array, asp: ASPConfig) -> Array:
    """Map pre-activations into the spline's knot range.

    KAN grids are defined on a fixed range; production KAN stacks bound the
    input (efficient-KAN uses LayerNorm, we use tanh scaled to the range so
    the bound is exact rather than statistical).
    """
    half = 0.5 * (asp.x_max - asp.x_min)
    mid = 0.5 * (asp.x_max + asp.x_min)
    return mid + half * jnp.tanh(x.astype(jnp.float32)).astype(x.dtype)


def base_branch(x: Array, w_base: Array, activation: str) -> Array:
    """The b(x) residual branch: ``act(x) @ w_base`` (original KAN form)."""
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[activation]
    return act(x) @ w_base


def spline_ref(x: Array, coeffs: Array, asp: ASPConfig) -> Array:
    """Float Cox–de Boor/cardinal oracle."""
    basis = splines.bspline_basis_uniform(
        x, asp.x_min, asp.x_max, asp.grid_size, asp.order)  # [..., I, G+K]
    return jnp.einsum("...ig,igo->...o", basis, coeffs)


def spline_lut(x: Array, coeffs: Array, asp: ASPConfig,
               hemi: Optional[Array] = None) -> Array:
    """Quantized expanded-basis matmul (the ACIM-faithful MXU dataflow)."""
    if hemi is None:
        hemi = quant.hemi_for(asp, dtype=jnp.float32)
    basis = quant.quantized_basis(x, hemi, asp)  # [..., I, G+K]
    basis = basis.astype(coeffs.dtype)
    lead = basis.shape[:-2]
    ik = basis.shape[-2] * basis.shape[-1]
    e = basis.reshape(lead + (ik,))
    c2 = coeffs.reshape(ik, coeffs.shape[-1])
    return e @ c2


def spline_lut_qat(x: Array, coeffs: Array, asp: ASPConfig,
                   hemi: Optional[Array] = None) -> Array:
    """Quantized forward with float-path straight-through backward."""
    yq = spline_lut(x, coeffs, asp, hemi)
    yf = spline_ref(x, coeffs, asp)
    return yf + jax.lax.stop_gradient(yq - yf)


# ---------------------------------------------------------------------------
# Deployed artifact
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DeployedLayer:
    """Frozen per-layer artifact — what gets programmed into the hardware."""
    codes: Array                    # [I, S, O] int8 coefficient codes
    scale: Array                    # [1, 1, O] f32 per-output-channel scale
    hemi: Array                     # [ceil(L/2), K+1] f32 SH-LUT
    w_base: Optional[Array] = None  # [I, O] residual-branch weights
    atten: Optional[Array] = None   # [R] f32 row attenuation (cim)
    row_order: Optional[Array] = None  # [R] int32 phys-of-logical (KAN-SAM)
    slices: Optional[Array] = None  # [I, S, O, 8] uint8 bit-slices (cim)
    hemi_q: Optional[Array] = None  # [ceil(L/2), K+1] int8 SH-LUT (lut_int8)
    tiles: Optional[Any] = None     # hw.chip.TiledLayer (cim_tiled)

    def tree_flatten(self):
        """Pytree protocol: all artifact arrays are children (traced)."""
        return ((self.codes, self.scale, self.hemi, self.w_base,
                 self.atten, self.row_order, self.slices, self.hemi_q,
                 self.tiles), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Pytree protocol inverse of ``tree_flatten``."""
        del aux
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DeployedKAN:
    """Frozen KAN stack artifact: consumed by ``apply``, produced by
    ``deploy`` exactly once per serving lifetime. A registered pytree, so it
    lives inside larger parameter trees (jit, donate, lax.scan, vmap)."""
    layers: Tuple[DeployedLayer, ...]
    spec: KANSpec

    def tree_flatten(self):
        """Pytree protocol: layers are children, the spec is static aux."""
        return (self.layers, self.spec)

    @classmethod
    def tree_unflatten(cls, spec, layers):
        """Pytree protocol inverse of ``tree_flatten``."""
        return cls(tuple(layers), spec)


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

class KANBackend:
    """One execution substrate for deployed KAN layers.

    Subclass, override ``run`` (and optionally ``deploy_extras`` /
    ``train_run``), and decorate with ``@register_backend(name)``.
    """
    name = "?"

    def deploy_extras(self, codes: Array, scale: Array, lspec: KANLayerShape,
                      spec: KANSpec, stats, *,
                      layer_idx: int = 0) -> Dict[str, Array]:
        """Backend-specific artifact fields (keys of DeployedLayer).
        ``layer_idx`` is a chip-unique layer id (``chip_uid * n_layers +
        layer``, possibly traced — cim_tiled folds it into the per-tile
        process-variation draw so no two physical layers share one)."""
        del codes, scale, lspec, spec, stats, layer_idx
        return {}

    def run(self, layer: DeployedLayer, lspec: KANLayerShape, spec: KANSpec,
            x: Array, rng: Optional[Array] = None) -> Array:
        """Spline forward against the frozen artifact (no requantization)."""
        raise NotImplementedError

    def train_run(self, coeffs: Array, lspec: KANLayerShape, spec: KANSpec,
                  x: Array, qat: bool) -> Array:
        """Training-path spline forward (float master coeffs).

        Default: the quantized LUT path with STE backward under QAT — the
        convention every integer backend trains against.
        """
        if qat:
            return spline_lut_qat(x, coeffs, lspec.asp)
        return spline_lut(x, coeffs, lspec.asp)


_BACKENDS: Dict[str, KANBackend] = {}


def register_backend(name: str):
    """Class/instance decorator: ``@register_backend("mine")``."""
    def deco(obj):
        inst = obj() if isinstance(obj, type) else obj
        inst.name = name
        _BACKENDS[name] = inst
        return obj
    return deco


def get_backend(name: str) -> KANBackend:
    """Registered backend instance by name (KeyError lists known names)."""
    try:
        return _BACKENDS[name]
    except KeyError:
        raise KeyError(f"unknown KAN backend {name!r}; registered backends: "
                       f"{sorted(_BACKENDS)}") from None


def backends() -> Tuple[str, ...]:
    """Sorted names of all registered backends."""
    return tuple(sorted(_BACKENDS))


# ---------------------------------------------------------------------------
# Built-in backends
# ---------------------------------------------------------------------------

@register_backend("ref")
class RefBackend(KANBackend):
    """Float recursive-basis oracle over the dequantized artifact: accuracy
    ground truth (differs from lut/fused by input-quantization error only)."""

    def run(self, layer, lspec, spec, x, rng=None):
        """Dequantize the codes and evaluate the float Cox-de Boor basis."""
        coeffs = quant.dequantize_coeffs(layer.codes, layer.scale)
        return spline_ref(x, coeffs, lspec.asp)

    def train_run(self, coeffs, lspec, spec, x, qat):
        """Pure float forward (the oracle ignores ``qat``)."""
        return spline_ref(x, coeffs, lspec.asp)


@register_backend("lut")
class LutBackend(KANBackend):
    """ASP-KAN-HAQ quantized expanded-basis matmul (the paper-faithful ACIM
    dataflow on the MXU; the serving default). Same basis values as fused;
    the two sum the contraction in a different order, so they agree to a
    few ulp, not bitwise."""

    def run(self, layer, lspec, spec, x, rng=None):
        """f32 expanded-basis matmul over the int8 codes + one scale."""
        basis = quant.quantized_basis(x, layer.hemi, lspec.asp)
        lead = basis.shape[:-2]
        ik = basis.shape[-2] * basis.shape[-1]
        e = basis.reshape(lead + (ik,)).astype(jnp.float32)
        c = layer.codes.astype(jnp.float32).reshape(ik, -1)
        y = e @ c
        return (y * layer.scale.reshape(-1).astype(jnp.float32)
                ).astype(x.dtype)


@register_backend("lut_int8")
class LutInt8Backend(KANBackend):
    """int8-MXU: the expanded-basis contraction stays integer END TO END —
    int8 basis codes (deploy-time-quantized SH-LUT taps, the WL-DAC view)
    × int8 coefficient codes with int32 accumulation; ONE f32 multiply
    after the contraction folds the coefficient scale and the basis LSB.
    Same artifact as ``lut`` plus the int8 SH-LUT; differs from ``lut`` by
    basis-quantization error only (≤ 0.5/127 per tap)."""

    def deploy_extras(self, codes, scale, lspec, spec, stats, *,
                      layer_idx=0):
        """Quantize the SH-LUT once at deploy time (the int8 WL-DAC view)."""
        hemi = quant.hemi_for(lspec.asp)
        return {"hemi_q": quant.quantize_hemi(hemi)}

    def run(self, layer, lspec, spec, x, rng=None):
        """int8 x int8 -> int32 contraction; one f32 rescale at the end."""
        basis = quant.quantized_basis(x, layer.hemi_q, lspec.asp)  # int8
        lead = basis.shape[:-2]
        ik = basis.shape[-2] * basis.shape[-1]
        e = basis.reshape(lead + (ik,))
        c = layer.codes.reshape(ik, -1)
        acc = jax.lax.dot_general(                      # int8 x int8 -> int32
            e, c, (((e.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        y = acc.astype(jnp.float32) * (
            layer.scale.reshape(-1).astype(jnp.float32) * quant.HEMI_LSB)
        return y.astype(x.dtype)


@register_backend("fused")
class FusedBackend(KANBackend):
    """Pallas TPU kernel: quantize → SH-LUT → expand → MXU contract fused in
    VMEM; consumes the artifact's int8 codes + SH-LUT directly."""

    def run(self, layer, lspec, spec, x, rng=None):
        """Deployed-artifact entry of the fused Pallas kernel."""
        from repro.kernels import ops  # lazy: keep core free of kernel deps
        return ops.kan_spline_fused_deployed(x, layer.codes, layer.scale,
                                             lspec.asp, hemi=layer.hemi)

    def train_run(self, coeffs, lspec, spec, x, qat):
        """Fused kernel with the QAT custom-VJP wrapper."""
        from repro.kernels import ops
        # QAT custom-VJP kernel wrapper (forward quantized, STE backward)
        return ops.kan_spline_fused(x, coeffs, lspec.asp)


@register_backend("cim")
class CimBackend(KANBackend):
    """Bit-sliced RRAM crossbar simulator (hw.cim) with optional KAN-SAM.

    Deploy computes the programming image: bit-slices of the codes, the
    per-logical-row IR-drop attenuation (uniform mapping, or the KAN-SAM
    criticality-sorted mapping when ``spec.use_sam`` — Phase-A stats
    required), and the physical row order. Training runs the default
    fake-quant LUT path (analog noise is not differentiable).
    """

    def _cim_cfg(self, spec):
        from repro.hw import cim as cim_lib
        return spec.cim if spec.cim is not None else cim_lib.CIMConfig()

    def deploy_extras(self, codes, scale, lspec, spec, stats, *,
                      layer_idx=0):
        """Bit-slice the codes and freeze the (KAN-SAM) row mapping."""
        from repro.core import kan_sam
        from repro.hw import cim as cim_lib
        ccfg = self._cim_cfg(spec)
        pos_att = cim_lib.row_attenuation(lspec.n_rows, ccfg)
        out: Dict[str, Array] = {"slices": quant.bit_slices(codes)}
        if spec.use_sam:
            if stats is None:
                raise ValueError(
                    "KAN-SAM deploy needs Phase-A BasisStats: pass "
                    "deploy(params, spec, stats=...) with one entry per "
                    "layer name")
            c_w = kan_sam.criticality(stats, codes)
            phys, atten = kan_sam.sam_row_map(c_w, pos_att)
            out["row_order"] = phys
            out["atten"] = atten
        else:
            out["atten"] = pos_att
        return out

    def run(self, layer, lspec, spec, x, rng=None):
        """Analog crossbar forward over the programmed bit-slice image."""
        from repro.hw import cim as cim_lib
        ccfg = self._cim_cfg(spec)
        basis = quant.quantized_basis(x, layer.hemi, lspec.asp)
        lead = basis.shape[:-2]
        v = basis.reshape(lead + (lspec.n_rows,))
        w = layer.codes.reshape(lspec.n_rows, lspec.out_dim)
        y = cim_lib.cim_forward(v, w, ccfg, atten_of_logical=layer.atten,
                                rng=rng)
        return y * layer.scale.reshape(-1)


@register_backend("cim_tiled")
class CimTiledBackend(KANBackend):
    """Multi-tile ACIM chip simulator (hw.tiles / hw.chip).

    Deploy runs the chip mapper: empty-row compaction across tiles,
    within-tile KAN-SAM criticality placement (``spec.use_sam`` + Phase-A
    stats), the per-tile int8 programming images, and the deterministic
    per-``(seed, layer, tile)`` process-variation gains — all frozen into
    the artifact's ``TiledLayer``. Run gathers word lines into physical
    order and reduces per-tile ADC readouts through the int32 digital
    adder tree (Pallas kernel on the deterministic path). Like ``cim``,
    training falls back to the fake-quant LUT path.
    """

    def _chip_cfg(self, spec):
        from repro.hw import chip as chip_lib
        if spec.cim is None:
            return chip_lib.ChipConfig()
        if not isinstance(spec.cim, chip_lib.ChipConfig):
            raise TypeError(
                "the cim_tiled backend takes spec.cim = hw.chip.ChipConfig "
                f"(got {type(spec.cim).__name__}); wrap a TileConfig in "
                "ChipConfig(tile=...)")
        return spec.cim

    def deploy_extras(self, codes, scale, lspec, spec, stats, *,
                      layer_idx=0):
        """Run the chip mapper: tiling, compaction, variation draws."""
        from repro.core import kan_sam
        from repro.hw import chip as chip_lib
        ccfg = self._chip_cfg(spec)
        crit = None
        if spec.use_sam:
            if stats is None:
                raise ValueError(
                    "KAN-SAM deploy needs Phase-A BasisStats: pass "
                    "deploy(params, spec, stats=...) with one entry per "
                    "layer name")
            crit = kan_sam.criticality(stats, codes).reshape(-1)
        tiled = chip_lib.place_layer(codes, crit, ccfg, layer_uid=layer_idx)
        return {"tiles": tiled, "row_order": tiled.phys_of_logical}

    def run(self, layer, lspec, spec, x, rng=None):
        """Multi-tile chip forward + int32 digital partial-sum reduction."""
        from repro.hw import chip as chip_lib
        ccfg = self._chip_cfg(spec)
        basis = quant.quantized_basis(x, layer.hemi, lspec.asp)
        lead = basis.shape[:-2]
        v = basis.reshape(lead + (lspec.n_rows,))
        y = chip_lib.chip_forward(v, layer.tiles, ccfg, lspec.out_dim,
                                  rng=rng)
        return y * layer.scale.reshape(-1)


# ---------------------------------------------------------------------------
# init / deploy / apply / train_apply
# ---------------------------------------------------------------------------

def _init_layer(key: Array, lspec: KANLayerShape, spec: KANSpec
                ) -> Dict[str, Array]:
    """Small-noise spline coefficients + LeCun base weights (original KAN
    init: spline ~ noise, base carries the signal early)."""
    k_c, k_b = jax.random.split(key)
    coeffs = (jax.random.normal(
        k_c, (lspec.in_dim, lspec.asp.n_basis, lspec.out_dim),
        dtype=jnp.float32) * (0.1 / jnp.sqrt(lspec.in_dim)))
    params = {"coeffs": coeffs.astype(spec.dtype)}
    if spec.base_activation:
        w_b = (jax.random.normal(k_b, (lspec.in_dim, lspec.out_dim),
                                 dtype=jnp.float32)
               / jnp.sqrt(lspec.in_dim))
        params["w_base"] = w_b.astype(spec.dtype)
    return params


def init(key: Array, spec: KANSpec):
    """Init the param tree for a spec (flat for a bare single layer)."""
    names = spec.names
    if names is None:
        return _init_layer(key, spec.layer(0), spec)
    ks = jax.random.split(key, spec.n_layers)
    return {name: _init_layer(ks[i], spec.layer(i), spec)
            for i, name in enumerate(names)}


def deploy(params, spec: KANSpec, stats=None, *, chip_uid=0) -> DeployedKAN:
    """Phase 1 — compile-time artifact construction (run ONCE per serving
    lifetime): quantize coefficients to int8 codes + per-output-channel
    scales (``quantize_coeffs(..., axis=(0, 1))``), build the SH-LUT, and
    let the backend attach its extras (cim: bit-slices + KAN-SAM
    row order/attenuation from Phase-A ``stats``).

    ``chip_uid`` distinguishes multiple KAN stacks deployed onto one
    simulated chip (e.g. every KAN-FFN block of a transformer): cim_tiled
    folds ``chip_uid * n_layers + layer`` into its process-variation key,
    so distinct physical layers draw distinct per-cell variation. It may
    be a traced int32 scalar (vmapped stacked-stage deploys pass an iota).

    Idempotent: an already-deployed artifact passes through unchanged.
    """
    if isinstance(params, DeployedKAN):
        return params
    backend = get_backend(spec.backend)
    layers = []
    for i in range(spec.n_layers):
        lp = _layer_params(params, spec, i)
        lspec = spec.layer(i)
        coeffs = lp["coeffs"].astype(jnp.float32)
        codes, scale = quant.quantize_coeffs(coeffs, lspec.asp, axis=(0, 1))
        hemi = quant.hemi_for(lspec.asp)
        extras = backend.deploy_extras(codes, scale, lspec, spec,
                                       _layer_stats(stats, spec, i),
                                       layer_idx=chip_uid * spec.n_layers + i)
        layers.append(DeployedLayer(
            codes=codes, scale=scale.astype(jnp.float32), hemi=hemi,
            w_base=lp.get("w_base"), atten=extras.get("atten"),
            row_order=extras.get("row_order"), slices=extras.get("slices"),
            hemi_q=extras.get("hemi_q"), tiles=extras.get("tiles")))
    return DeployedKAN(tuple(layers), spec)


def apply(deployed: DeployedKAN, x: Array, *,
          rng: Optional[Array] = None) -> Array:
    """Phase 2 — run-time evaluation against the frozen artifact. The ONE
    entry point for every backend; the traced computation performs no
    coefficient quantization and builds no LUTs (see trace_requantizes)."""
    spec = deployed.spec
    backend = get_backend(spec.backend)
    for i, layer in enumerate(deployed.layers):
        lspec = spec.layer(i)
        xb = bound_input(x, lspec.asp) if spec.bound_input else x
        y = backend.run(layer, lspec, spec, xb,
                        rng=None if rng is None else jax.random.fold_in(rng,
                                                                        i))
        if spec.base_activation and layer.w_base is not None:
            y = y + base_branch(xb, layer.w_base, spec.base_activation)
        x = y
    return x


def train_apply(params, x: Array, spec: KANSpec, *, qat: bool = False
                ) -> Array:
    """Training twin of ``apply``: float master weights through the same
    backend dispatch. With ``qat=True``, coefficients are fake-quantized
    (STE) so the forward numerically equals the deployed integer forward."""
    backend = get_backend(spec.backend)
    for i in range(spec.n_layers):
        lp = _layer_params(params, spec, i)
        lspec = spec.layer(i)
        xb = bound_input(x, lspec.asp) if spec.bound_input else x
        coeffs = lp["coeffs"]
        if qat:
            codes, scale = quant.quantize_coeffs(coeffs, lspec.asp,
                                                 axis=(0, 1))
            cq = quant.dequantize_coeffs(codes, scale).astype(coeffs.dtype)
            coeffs = coeffs + jax.lax.stop_gradient(cq - coeffs)
        y = backend.train_run(coeffs, lspec, spec, xb, qat=qat)
        if spec.base_activation and "w_base" in lp:
            y = y + base_branch(xb, lp["w_base"], spec.base_activation)
        x = y
    return x


def apply_any(params_or_deployed, x: Array, spec: KANSpec) -> Array:
    """Call-site dispatch: a DeployedKAN runs the frozen integer path, a raw
    param tree runs the training-path forward (float coeffs). Lets model
    code (transformer FFN, serve.decode) consume either transparently."""
    if isinstance(params_or_deployed, DeployedKAN):
        return apply(params_or_deployed, x)
    return train_apply(params_or_deployed, x, spec)


# ---------------------------------------------------------------------------
# Hot-path guarantee: detect coefficient (re)quantization in a trace.
# ---------------------------------------------------------------------------

def _iter_eqns(jaxpr) -> Iterator:
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for sub in vs:
                if isinstance(sub, jex_core.ClosedJaxpr):
                    yield from _iter_eqns(sub.jaxpr)
                elif isinstance(sub, jex_core.Jaxpr):
                    yield from _iter_eqns(sub)


def trace_requantizes(fn, *args) -> bool:
    """True if tracing ``fn(*args)`` MINTS int8 codes from FLOATING values —
    i.e. the computation re-runs coefficient quantization (the ``round →
    clip → astype(int8)`` chain) instead of consuming frozen codes. Moving
    existing codes around — pad/reshape/slice and their integer fill-value
    casts in the fused kernel wrapper or the CIM simulator — is artifact
    plumbing and does not count. The serving decode tick over a DeployedKAN
    must return False for every backend; the QAT training path returns True
    (its fake-quant step mints codes every call)."""
    closed = jax.make_jaxpr(fn)(*args)
    for eqn in _iter_eqns(closed.jaxpr):
        for var in eqn.outvars:
            if getattr(getattr(var, "aval", None), "dtype", None) != jnp.int8:
                continue
            for v in eqn.invars:
                dt = getattr(getattr(v, "aval", None), "dtype", None)
                if dt is not None and jnp.issubdtype(dt, jnp.inexact):
                    return True
    return False


def contains_deployed(tree) -> bool:
    """True if any subtree of ``tree`` is a frozen DeployedKAN artifact —
    the robust \"is this serving the deployed path\" predicate (identity
    checks against the input tree break on already-deployed params)."""
    return any(isinstance(leaf, DeployedKAN) for leaf in jax.tree.leaves(
        tree, is_leaf=lambda t: isinstance(t, DeployedKAN)))
