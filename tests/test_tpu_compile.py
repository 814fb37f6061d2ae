"""The Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test lowers a kernel through its ``kernels.ops`` wrapper
(the block picking and padding the serving path uses) with
``interpret=False`` and compiles it for a described, not attached, v5e
chip. The TPU compiler then refuses what interpret mode accepts: blocks not
aligned to the (8, 128) tiling, relayouts Mosaic cannot lower, kernels
that do not fit VMEM. The compile cache stays off around these compiles
(an entry written without a chip could not be read back).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quant import ASPConfig
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_for(one_chip, monkeypatch):
    """Compile ``fn`` at the given (shape, dtype) args for one v5e chip, with
    the kernels lowered for the chip rather than the interpreter."""
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)

    def compile_(fn, *args):
        structs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                   for s, d in args]
        return jax.jit(fn).lower(*structs).compile()
    return compile_


# kan_llm: d_model 256, hidden 1024 // (G+K+1) = 85; a d_model-5120 model
# (mistral-nemo widths: d_ff 14336 -> hidden 1194). Batches: a decode tick
# of a few slots and a 256-token prefill chunk.
KAN_CASES = [(8, 256, 85), (256, 85, 256), (64, 5120, 1194),
             (64, 1194, 5120)]


@pytest.mark.parametrize("b,i,o", KAN_CASES)
def test_kan_fused_compiles_for_v5e(compiled_for, b, i, o):
    asp = ASPConfig(grid_size=8, order=3)

    def fused(x, codes, scale):
        return ops.kan_spline_fused_deployed(x, codes, scale, asp)

    c = compiled_for(fused, ((b, i), jnp.bfloat16),
                     ((i, asp.n_basis, o), jnp.int8), ((o,), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


def test_ssd_scan_compiles_for_v5e_at_mamba2_width(compiled_for):
    # mamba2-1.3b: d_inner 4096 = 64 heads of 64, state 128, chunk 256
    b, t, h, p, n = 1, 512, 64, 64, 128

    def scan(x, dt, a, bm, cm, d):
        return ops.ssd(x, dt, a, bm, cm, d, chunk=256)

    c = compiled_for(scan, ((b, t, h, p), jnp.bfloat16),
                     ((b, t, h), jnp.float32), ((h,), jnp.float32),
                     ((b, t, n), jnp.bfloat16), ((b, t, n), jnp.bfloat16),
                     ((h,), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


def test_cim_mac_tiled_compiles_for_v5e(compiled_for):
    # kan_llm's up projection on 128-row tiles: 256 * 11 rows -> 2816
    r, c_out = 2816, 85

    def tiled(v, w, atten, gain):
        return ops.cim_mac_tiled(v, w, atten, gain=gain, array_size=128)

    c = compiled_for(tiled, ((8, r), jnp.float32), ((r, c_out), jnp.int8),
                     ((r,), jnp.float32), ((r, c_out), jnp.float32))
    assert "tpu_custom_call" in c.as_text()
