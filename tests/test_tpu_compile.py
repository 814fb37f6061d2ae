"""The Pallas kernels compile for a TPU v5e at real widths, and the
engine's serving programs compile for it without copying the cache.

Nothing runs: each test lowers a kernel through its ``kernels.ops`` wrapper
(the block picking and padding the serving path uses) with
``interpret=False`` and compiles it for a described, not attached, v5e
chip. The TPU compiler then refuses what interpret mode accepts: blocks not
aligned to the (8, 128) tiling, relayouts Mosaic cannot lower, kernels
that do not fit VMEM. The compile cache stays off around these compiles
(an entry written without a chip could not be read back).
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core.quant import ASPConfig
from repro.kernels import ops
from repro.models import transformer as tfm
from repro.serve import decode as dec
from repro.serve import engine as engine_lib


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


def _ops(hlo: str, op: str):
    """Result shapes of every ``op`` in an HLO text, fused ones included."""
    return [tuple(int(d) for d in m.group(1).split(",") if d)
            for m in re.finditer(rf"= \w+\[([\d,]*)\]\{{[^}}]*\}} {op}\(",
                                 hlo)]


# the benchmark's cells: (arch, layers kept, slots, max_len), page 64
SERVING_CELLS = {"mamba2_1p3b": ("mamba2_1p3b", None, 32, 2560),
                 "mistral_nemo_12b_4l": ("mistral_nemo_12b", 4, 32, 3072)}


@pytest.mark.parametrize("cell", sorted(SERVING_CELLS))
def test_serving_programs_update_stacked_cache_in_place(cell, one_chip):
    """The engine's decode tick and each kind of chunk program (first,
    middle, last, whole prompt), built for a v5e at the benchmark's widths:
    no ``copy`` of a stacked cache leaf (the whole SSD state or K/V pool,
    copied per call when the layer scan took the cache as ``xs`` and gave
    it back as ``ys``), and no ``dynamic-slice`` of one layer's whole pool
    in the decode tick. (At SMOKE widths the compiler moves the tiny cache
    into on-chip memory and back, which says nothing of this.)"""
    arch_id, n_layers, slots, max_len = SERVING_CELLS[cell]
    m = get_arch(arch_id).model
    if n_layers:
        m = dataclasses.replace(m, n_layers=n_layers)
    ps = 64
    p = -(-max_len // ps)

    def chip(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params, cache = jax.tree.map(
        lambda a: chip(a.shape, a.dtype), jax.eval_shape(lambda: (
            tfm.init_model(jax.random.PRNGKey(0), m),
            dec.init_paged_cache(m, slots, max_len, page_size=ps,
                                 n_pages=slots * p + 1))))
    programs = {"decode_tick": engine_lib._named_jit(
        functools.partial(engine_lib._decode_fn, cfg=m), "decode_tick",
        donate_argnums=1).lower(params, cache, chip((slots,)),
                                chip((slots,)), chip((slots, p)))}
    c = dec.chunk_tokens_for(m, ps)
    for key in [(c, True, False), (c, False, False), (c // 2, False, True),
                (c, True, True)]:
        length, first, last = key
        programs[key] = engine_lib._named_jit(
            functools.partial(engine_lib._chunk_fn, cfg=m, first=first,
                              last=last), engine_lib._chunk_jit_name(key),
            donate_argnums=1).lower(params, cache, chip((1, length)),
                                    chip(()), chip(()), chip((p,)))
    stacked = {leaf.shape for st, stage in zip(cache, tfm.stages_for(m))
               if stage.repeats > 1 for leaf in jax.tree.leaves(st)}
    assert stacked
    one_layer = {s[1:] for s in stacked} | {(1,) + s[1:] for s in stacked}
    for name, low in programs.items():
        hlo = low.compile().as_text()
        copies = [s for s in _ops(hlo, "copy") if s in stacked]
        assert not copies, (name, copies)
        if name == "decode_tick" and m.family != "ssm":
            slices = [s for s in _ops(hlo, "dynamic-slice") if s in one_layer]
            assert not slices, (name, slices)
