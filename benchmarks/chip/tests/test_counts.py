"""Parameter, FLOP and byte counts from shapes."""

import jax
import pytest

import harness as hz
from smoke import config_cells, smoke_cell

CELLS = config_cells()


@pytest.mark.parametrize("name", CELLS)
def test_param_count_matches_init_model(name):
    from repro.models import transformer as tfm
    cell = smoke_cell(name)
    counts = hz.load_module("counts", cell.conf["family"])
    cfg = hz.model_config(cell.conf)
    shapes = jax.eval_shape(lambda k: tfm.init_model(k, cfg),
                            jax.random.PRNGKey(0))
    assert counts.param_count(cell.model) == tfm.count_params(shapes)


@pytest.mark.parametrize("name", CELLS)
def test_full_size_param_count(name):
    from repro.models import transformer as tfm
    cell = hz.load_cell(name)
    counts = hz.load_module("counts", cell.conf["family"])
    cfg = hz.model_config(cell.conf)
    shapes = jax.eval_shape(lambda k: tfm.init_model(k, cfg),
                            jax.random.PRNGKey(0))
    assert counts.param_count(cell.model) == tfm.count_params(shapes)


def test_mamba_decode_bytes_by_hand():
    counts = hz.load_module("counts", "ssm_mamba2")
    m = {"n_layers": 2, "d_model": 4, "vocab": 10, "ssm_state": 2,
         "ssm_head_dim": 2, "ssm_expand": 2, "ssm_conv_width": 4}
    # per layer: in_proj 4*(8+8+4+4)... di=8, H=4, proj out 2*8+2*2+4 = 24
    per_layer = 4 * 24 + 8 * 4 + 4 * (8 + 4) + 3 * 4 + 8 + 4
    params = 2 * per_layer + 10 * 4 + 4
    state = 4 * 2 * 2 * 4 + 3 * (8 + 4) * 2      # f32 state, bf16 conv ring
    assert counts.param_count(m) == params
    _, nbytes = counts.decode_tick(m, 3, 0)
    assert nbytes == 2 * params + 2 * 3 * 2 * state


def test_dense_decode_bytes_by_hand():
    counts = hz.load_module("counts", "dense_gqa")
    m = {"n_layers": 3, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 2, "d_ff": 16, "vocab": 20, "tie_embeddings": True}
    per_layer = 8 * 2 * (4 + 4 + 2 + 2) + 3 * 8 * 16 + 2 * 8
    params = 3 * per_layer + 20 * 8 + 8
    assert counts.param_count(m) == params
    _, nbytes = counts.decode_tick(m, 2, 100)
    kv = 100 * 3 * 2 * 2 * 2 * 2                 # keys * layers * k,v * Kv * hd * 2 B
    assert nbytes == 2 * params + kv


@pytest.mark.parametrize("name", CELLS)
def test_weights_count_their_least_bytes_whatever_they_are_stored_in(name):
    """A decode tick of no token reads the weights once, at the least bytes
    the family states, and the dtype the program stores them in does not
    change that."""
    cell = hz.load_cell(name)
    counts = hz.load_module("counts", cell.conf["family"])
    m16 = dict(cell.model, param_dtype="bfloat16")
    assert counts.decode_tick(cell.model, 1, 1) == counts.decode_tick(m16, 1, 1)
    assert counts.weight_bytes(cell.model) == counts.weight_bytes(m16)
    _, nbytes = counts.decode_tick(cell.model, 0, 0)
    assert nbytes == counts.weight_bytes(cell.model)


def test_prefill_flops_causal_sum():
    counts = hz.load_module("counts", "dense_gqa")
    m = hz.load_cell("mistral_nemo_12b_4l.sessions").model
    whole = counts.prefill_chunk(m, 0, 128, True)
    split = (counts.prefill_chunk(m, 0, 64, False)
             + counts.prefill_chunk(m, 64, 64, True))
    assert whole == pytest.approx(split)
