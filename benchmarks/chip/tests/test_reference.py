"""The plain references: same weights as the program from the seed, and
the same logits as the program's forward and the engine's tokens."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness as hz
from smoke import config_cells, smoke_cell

CELLS = config_cells()
SEED = 2**31 + 17


def _program(name):
    cell = smoke_cell(name)
    cfg = hz.model_config(cell.conf)
    return cell, cfg, hz.init_params(cfg, SEED)


@pytest.mark.parametrize("name", CELLS)
def test_reference_weights_are_the_programs(name):
    cell, cfg, params = _program(name)
    ref = hz.load_module("reference", cell.conf["family"])
    common = hz.load_module("reference", "_common")
    ks = jax.random.split(common.model_key(SEED), 8)
    layer_keys = jax.random.split(jax.random.fold_in(ks[2], 0),
                                  cell.model["n_layers"])
    stacked = params["stages"][0]["l0"]
    for i, k in enumerate(layer_keys):
        w = ref.init_layer(k, cell.model)
        flat = ref.program_layer(jax.tree.map(lambda x: x[i], stacked))
        compared = 0
        for key, v in w.items():
            if getattr(flat.get(key), "ndim", 0) > 1:
                compared += 1
                # the same draws; the scale multiply may round by one ulp
                np.testing.assert_allclose(
                    np.asarray(v), np.asarray(flat[key]).reshape(v.shape),
                    rtol=3e-7, atol=0)
        assert compared >= 2


@pytest.mark.parametrize("name", CELLS)
def test_reference_logits_match_program_forward(name):
    """The gap that the reference reads for each token of a prompt is the
    one the program's own f32 forward gives."""
    from repro.models import transformer as tfm
    cell, cfg, params = _program(name)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, 40).astype(np.int32)
    logits, _ = jax.jit(lambda p, t: tfm.forward(p, cfg, {"tokens": t}))(
        params, jnp.asarray(prompt)[None])
    logits = np.asarray(logits[0], np.float64)
    ref = hz.load_module("reference", cell.conf["family"])
    # gap of the program's argmax next tokens under the reference
    res = ref.evaluate(cell.model, SEED, [prompt], [20], 512)
    tgt = prompt[20:]
    want = logits[19:39].max(-1) - logits[np.arange(19, 39), tgt]
    np.testing.assert_allclose(res[0]["gap"], want, atol=2e-4)


@pytest.mark.parametrize("name", CELLS)
def test_reference_agrees_with_engine_prefill_then_decode(name):
    from repro.serve.engine import Engine
    from repro.serve.scheduler import Request
    cell, cfg, params = _program(name)
    eng = Engine(params, cfg, n_slots=3, max_len=96, page_size=8)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (16, 40, 56)]
    comps = eng.run([Request(rid=i, tokens=p, max_new=12)
                     for i, p in enumerate(prompts)])
    served = {c.rid: np.asarray(c.tokens) for c in comps}
    ref = hz.load_module("reference", cell.conf["family"])
    seqs = [np.concatenate([p, served[i]]) for i, p in enumerate(prompts)]
    res = ref.evaluate(cell.model, SEED, seqs, [12] * 3, 512)
    assert max(r["gap"].max() for r in res) <= 2e-4
