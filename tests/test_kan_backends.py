"""Backend registry + two-phase deploy/apply contract (core.kan).

Pins the acceptance matrix of the unified KAN API:
* all four backends run the SAME deployed params through ONE ``kan.apply``;
* ``lut`` vs ``fused`` within a few ulp (same frozen artifact, same basis
  values, different summation order);
* ``ref`` within spline-input-quantization tolerance;
* ``cim`` with an ideal (no IR-drop / no noise / fine DAC+ADC) crossbar
  matches ``lut``;
* ``train_apply`` fake-quant (QAT) forward equals the deployed integer
  forward;
* the serving engine deploys EXACTLY ONCE and its decode tick contains no
  coefficient-quantization ops (jaxpr-level, plus poisoned-function guard).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import kan, quant
from repro.core.quant import ASPConfig
from repro.hw import cim
from repro.models import transformer as tfm
from repro.serve import decode as dec
from repro.serve import engine as engine_lib

BACKENDS = ("ref", "lut", "fused", "cim")

# ideal crossbar: zero IR drop, no readout noise, fine WL-DAC and ADC —
# isolates the *contract* (cim consumes the same artifact) from the error
# model (covered by tests/test_cf_kan.py and tests/test_hw.py)
IDEAL_CIM = cim.CIMConfig(array_size=256, adc_bits=16, gamma0=0.0,
                          sigma_psum=0.0, input_bits=16)


def _setup(b=32, i=16, o=8, g=8, seed=0):
    spec = kan.KANSpec.single(i, o, ASPConfig(grid_size=g))
    key = jax.random.PRNGKey(seed)
    params = kan.init(key, spec)
    x = jax.random.uniform(jax.random.fold_in(key, 1), (b, i),
                           minval=-1, maxval=1)
    return spec, params, x


def _dspec(spec, backend):
    return dataclasses.replace(
        spec, backend=backend, cim=IDEAL_CIM if backend == "cim" else None)


def test_backend_matrix_parity():
    """Same params, same inputs, four backends, one entry point."""
    spec, params, x = _setup()
    outs = {b: kan.apply(kan.deploy(params, _dspec(spec, b)), x)
            for b in BACKENDS}
    for b in BACKENDS:
        assert outs[b].shape == (32, 8)
    # lut vs fused: identical frozen artifact and identical basis values,
    # but the two backends sum the contraction in a different order (lut:
    # one dot over I*S; fused: one dot per basis slot, accumulated), so f32
    # rounding may differ by a few ulp of the output scale
    y_lut = np.asarray(outs["lut"])
    ulp = np.spacing(np.abs(y_lut).max())
    np.testing.assert_allclose(np.asarray(outs["fused"]), y_lut, rtol=0,
                               atol=4 * ulp)
    # ref: float recursive basis over the dequantized codes — differs from
    # lut by input-quantization error only
    np.testing.assert_allclose(outs["ref"], outs["lut"], atol=0.1)
    assert float(jnp.abs(outs["ref"] - outs["lut"]).max()) > 0  # not a no-op
    # cim (ideal, no noise): same codes through the bit-sliced crossbar
    rel = float(jnp.linalg.norm(outs["cim"] - outs["lut"])
                / jnp.linalg.norm(outs["lut"]))
    assert rel < 5e-3, rel


def test_lut_vs_fused_multitile():
    """Shapes crossing the kernel's block boundaries stay allclose."""
    spec, params, x = _setup(b=130, i=50, o=135, g=5, seed=2)
    y_lut = kan.apply(kan.deploy(params, _dspec(spec, "lut")), x)
    y_fused = kan.apply(kan.deploy(params, _dspec(spec, "fused")), x)
    np.testing.assert_allclose(y_lut, y_fused, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("backend", ["ref", "lut", "fused"])
def test_train_apply_qat_equals_deployed_forward(backend):
    """QAT fake-quant forward == deployed integer forward: what you train is
    what you serve."""
    spec, params, x = _setup(seed=4)
    dspec = _dspec(spec, backend)
    y_train = kan.train_apply(params, x, dspec, qat=True)
    y_dep = kan.apply(kan.deploy(params, dspec), x)
    np.testing.assert_allclose(y_train, y_dep, atol=2e-5, rtol=1e-5)


def test_train_apply_backends_grad_finite():
    """Every backend trains through the shared dispatch (cim falls back to
    the fake-quant LUT path: analog noise is not differentiable)."""
    spec, params, x = _setup(b=8)
    for backend in BACKENDS:
        dspec = _dspec(spec, backend)
        loss = lambda p: jnp.sum(kan.train_apply(p, x, dspec, qat=True) ** 2)
        g = jax.grad(loss)(params)
        leaves = jax.tree.leaves(g)
        assert leaves and all(bool(jnp.isfinite(l).all()) for l in leaves)


def test_deploy_artifact_contents_and_idempotence():
    spec, params, x = _setup()
    dep = kan.deploy(params, _dspec(spec, "cim"))
    (layer,) = dep.layers
    r = 16 * spec.asp[0].n_basis
    assert layer.codes.dtype == jnp.int8 and layer.codes.shape == (16, 11, 8)
    assert layer.scale.shape == (1, 1, 8)
    assert layer.hemi.shape[1] == spec.asp[0].n_taps
    assert layer.slices.shape == (16, 11, 8, 8)       # programming image
    assert layer.atten.shape == (r,)
    # idempotent: deploying a deployed artifact is the identity
    assert kan.deploy(dep, dep.spec) is dep
    # it is a pytree: flatten/unflatten round-trips and jit accepts it
    leaves, treedef = jax.tree.flatten(dep)
    dep2 = jax.tree.unflatten(treedef, leaves)
    assert isinstance(dep2, kan.DeployedKAN)
    y = jax.jit(kan.apply)(dep, x)   # jit accepts the artifact pytree
    np.testing.assert_allclose(y, kan.apply(dep2, x), atol=1e-6)


def test_sam_row_map_lives_in_artifact():
    """use_sam freezes the KAN-SAM row order/attenuation at deploy time."""
    from repro.core import kan_sam
    spec, params, x = _setup()
    asp = spec.asp[0]
    stats = kan_sam.update_stats(kan_sam.init_stats(16, asp),
                                 kan.bound_input(x, asp), asp)
    ccfg = cim.CIMConfig(array_size=64, gamma0=0.3)
    base = spec.with_backend("cim", cim=ccfg)
    with pytest.raises(ValueError):        # SAM without Phase-A stats
        kan.deploy(params, dataclasses.replace(base, use_sam=True))
    dep = kan.deploy(params, dataclasses.replace(base, use_sam=True),
                     stats=stats)
    (layer,) = dep.layers
    r = 16 * asp.n_basis
    assert layer.row_order.shape == (r,)
    assert sorted(np.asarray(layer.row_order)) == list(range(r))  # perm
    # SAM mapping is a permutation of the uniform attenuation values
    uni = np.sort(np.asarray(cim.row_attenuation(r, ccfg)))
    np.testing.assert_allclose(np.sort(np.asarray(layer.atten)), uni,
                               atol=1e-6)


def test_registry_errors_and_custom_backend():
    with pytest.raises(KeyError) as ei:
        kan.get_backend("not-a-backend")
    for b in BACKENDS:        # the error lists what IS registered
        assert b in str(ei.value)
    assert set(BACKENDS) <= set(kan.backends())

    @kan.register_backend("test-double-lut")
    class DoubleLut(kan.KANBackend):
        def run(self, layer, lspec, spec, x, rng=None):
            coeffs = quant.dequantize_coeffs(layer.codes, layer.scale)
            return 2.0 * kan.spline_ref(x, coeffs, lspec.asp)

    try:
        spec, params, x = _setup()
        dspec = dataclasses.replace(spec, backend="test-double-lut",
                                    base_activation="")
        params = {"coeffs": params["coeffs"]}
        y2 = kan.apply(kan.deploy(params, dspec), x)
        y1 = kan.apply(kan.deploy(params, _dspec(
            dataclasses.replace(spec, base_activation=""), "ref")), x)
        np.testing.assert_allclose(y2, 2.0 * y1, atol=1e-6)
    finally:
        kan._BACKENDS.pop("test-double-lut")


def test_kanspec_subsumes_layer_and_ffn_and_cfkan_shapes():
    key = jax.random.PRNGKey(0)
    # FFN: d -> hidden -> d with up/down param names
    ffn = kan.KANSpec.ffn(24, 6, ASPConfig(grid_size=5))
    p = kan.init(key, ffn)
    assert set(p) == {"up", "down"}
    x = jax.random.normal(key, (4, 3, 24)) * 0.3
    y = kan.apply(kan.deploy(p, ffn), x)
    assert y.shape == (4, 3, 24)
    yt = kan.train_apply(p, x, ffn)
    assert yt.shape == (4, 3, 24)
    # CF-KAN: per-layer ASPConfigs + enc/dec names
    spec = kan.KANSpec(dims=(40, 8, 40),
                       asp=(ASPConfig(grid_size=7), ASPConfig(grid_size=5)),
                       layer_names=("enc", "dec"))
    p = kan.init(key, spec)
    assert set(p) == {"enc", "dec"}
    assert p["enc"]["coeffs"].shape == (40, 10, 8)
    assert p["dec"]["coeffs"].shape == (8, 8, 40)
    y = kan.apply(kan.deploy(p, spec), jnp.ones((2, 40)) * 0.1)
    assert y.shape == (2, 40)
    # invalid specs are rejected loudly
    with pytest.raises(ValueError):
        kan.KANSpec(dims=(8,))
    with pytest.raises(ValueError):
        kan.KANSpec(dims=(8, 4, 8), layer_names=("only-one",))


# ---------------------------------------------------------------------------
# serving hot-path guarantee
# ---------------------------------------------------------------------------

def test_trace_requantizes_positive_control():
    """The detector must actually fire on the QAT path (which mints int8
    codes every call) — guards the hot-path assertions below against rot —
    and must NOT fire on any deployed backend (moving frozen int8 codes via
    pad/reshape is artifact plumbing, not requantization)."""
    spec, params, x = _setup()
    assert kan.trace_requantizes(
        lambda p, xx: kan.train_apply(p, xx, _dspec(spec, "lut"), qat=True),
        params, x)
    for backend in BACKENDS:
        dep = kan.deploy(params, _dspec(spec, backend))
        assert not kan.trace_requantizes(
            lambda d, xx: kan.apply(d, xx), dep, x), backend


def test_engine_deploys_once_and_decode_tick_is_requant_free(monkeypatch):
    """One engine decode tick for a KAN-FFN arch: deploy happened exactly
    once at engine construction, the tick's jaxpr contains no
    coeff-quantization ops, and quantize_coeffs/hemi_for are never reached
    while serving."""
    m = get_arch("kan_llm", smoke=True).model
    params = tfm.init_model(jax.random.PRNGKey(0), m)
    eng = engine_lib.Engine(params, m, n_slots=2, max_len=16)
    assert eng.kan_deployed

    # every kan subtree was frozen (stacked stage -> vmapped artifact);
    # an engine built from ALREADY-deployed params must report the same
    assert kan.contains_deployed(eng.params)
    eng_pre = engine_lib.Engine(eng.params, m, n_slots=2, max_len=16)
    assert eng_pre.kan_deployed

    tokens = jnp.zeros((2,), jnp.int32)
    index = jnp.ones((2,), jnp.int32)
    pages = jnp.zeros((2, eng.n_slot_pages), jnp.int32)
    assert not kan.trace_requantizes(
        lambda p, c, t, i, g: engine_lib._decode_fn(p, c, t, i, g, cfg=m),
        eng.params, eng.cache, tokens, index, pages)

    # belt and braces: serve a real trace with quantization poisoned
    def boom(*a, **k):
        raise AssertionError("coefficient (re)quantization in the serving "
                             "hot path")
    monkeypatch.setattr(quant, "quantize_coeffs", boom)
    monkeypatch.setattr(quant, "hemi_for", boom)
    reqs = engine_lib.synth_trace(m.vocab, 4, max_prompt=6, min_prompt=3,
                                  max_new=4, min_new=2, stagger=1)
    comps = eng.run(reqs)
    assert len(comps) == 4


def test_kan_engine_matches_solo_deployed_generate():
    """Batching invariance for the KAN family THROUGH the deployed path:
    the engine's pooled decode reproduces solo generation over the same
    frozen artifact token for token."""
    m = get_arch("kan_llm", smoke=True).model
    params = tfm.init_model(jax.random.PRNGKey(1), m)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, m.vocab, size=(s,)).astype(np.int32)
               for s in (4, 6, 3)]
    got = np.asarray(engine_lib.generate_dynamic(params, m, prompts,
                                                 n_new=4))
    dep_params = tfm.deploy_kan(params, m)
    assert tfm.deploy_kan(dep_params, m) is dep_params   # idempotent
    for i, p in enumerate(prompts):
        solo = np.asarray(dec.generate(dep_params, m,
                                       jnp.asarray(p)[None], 4))[0]
        np.testing.assert_array_equal(solo, got[i])
