"""Pieces shared by the plain references: seed to key, float32 helpers,
the fp8 control, and the driver that runs a stack layer by layer and reads
each served token's logit gap.

Nothing here imports the program. Weights are made again from the seed,
by the same random calls the repo's ``init_model`` makes, one layer at a
time, so that only one layer's weights live on the device at once.
"""
from __future__ import annotations

import math
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

FP8_MAX = 448.0          # largest finite float8_e4m3fn
GAP_BLOCK = 128          # positions unembedded per call


def model_key(seed: int):
    """The PRNG key the benchmark builds the weights from."""
    key = jax.random.PRNGKey(seed % 2 ** 32)
    if seed >> 32:
        key = jax.random.fold_in(key, seed >> 32)
    return key


def normal(key, shape, std):
    return jax.random.normal(key, shape, dtype=jnp.float32) * std


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def fp8(a, axis):
    """Round ``a`` to float8_e4m3fn with one scale per slice along the
    other axes (``axis`` is the contracted one)."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, quant: Optional[str] = None):
    """x [..., K] @ w [K, N] in float32 at full precision; with
    ``quant="fp8"`` both operands are first rounded to fp8 (per row of x,
    per column of w): the control that a lower precision must fail."""
    if quant == "fp8":
        x, w = fp8(x, -1), fp8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _gap_block(h, targets, table, scale, eps):
    logits = mm(rmsnorm(h, scale, eps), table.T)
    best = logits.max(axis=-1)
    tgt = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return best - tgt, logits


@jax.jit
def _gaps(h, targets, table, scale, eps):
    return _gap_block(h, targets, table, scale, eps)[0]


@jax.jit
def _gaps_control(h, hc, targets, table, scale, eps):
    gap, logits = _gap_block(h, targets, table, scale, eps)
    lc = mm(rmsnorm(hc, scale, eps), table.T, "fp8")
    pick = jnp.argmax(lc, axis=-1)
    worst = logits.max(axis=-1) - jnp.take_along_axis(
        logits, pick[:, None], axis=-1)[:, 0]
    return gap, worst


def evaluate(init_layer: Callable, apply_layer: Callable, model: dict,
             seed: int, seqs: List[np.ndarray], n_served: List[int],
             bucket: int, control: bool = False) -> List[dict]:
    """Run the reference over each sequence (prompt then served tokens,
    right-padded to ``bucket``) and return, per sequence, ``gap``: for each
    served token, how far its logit lies below the reference's best at
    that position. With ``control``, also ``control_gap``: the same gap
    for the token that the fp8 control puts first."""
    d, vocab, L = model["d_model"], model["vocab"], model["n_layers"]
    eps = model["norm_eps"]
    ks = jax.random.split(model_key(seed), 8)
    table = jax.jit(lambda k: normal(k, (vocab, d), 1.0 / math.sqrt(d)))(
        ks[0])
    if not model["tie_embeddings"]:
        raise NotImplementedError("untied unembedding")
    stage_key = jax.random.fold_in(ks[2], 0)
    layer_keys = (jax.random.split(stage_key, L) if L > 1
                  else stage_key[None])
    toks = [np.pad(np.asarray(s, np.int32), (0, bucket - len(s)))
            for s in seqs]
    embed = jax.jit(lambda t, i: jnp.take(t, i, axis=0))
    hs = [embed(table, jnp.asarray(t)) for t in toks]
    hcs = list(hs) if control else None
    init = jax.jit(lambda k: init_layer(k, model))
    apply = jax.jit(lambda w, x: apply_layer(w, x, model, None))
    apply_c = jax.jit(lambda w, x: apply_layer(w, x, model, "fp8"))
    for lk in layer_keys:
        w = init(lk)
        hs = [apply(w, h) for h in hs]
        if control:
            hcs = [apply_c(w, h) for h in hcs]
        del w
    scale = jnp.ones((d,), jnp.float32)    # final norm scale, ones at init
    out = []
    for i, s in enumerate(seqs):
        n = n_served[i]
        pos = np.arange(len(s) - n - 1, len(s) - 1)
        tgt = np.asarray(s, np.int32)[len(s) - n:]
        gap, cgap = [], []
        for b in range(0, n, GAP_BLOCK):
            p = np.pad(pos[b:b + GAP_BLOCK], (0, GAP_BLOCK - len(pos[b:b + GAP_BLOCK])))
            t = np.pad(tgt[b:b + GAP_BLOCK], (0, GAP_BLOCK - len(tgt[b:b + GAP_BLOCK])))
            m = min(GAP_BLOCK, n - b)
            if control:
                g, c = _gaps_control(hs[i][p], hcs[i][p], jnp.asarray(t),
                                     table, scale, eps)
                cgap.append(np.asarray(c)[:m])
            else:
                g = _gaps(hs[i][p], jnp.asarray(t), table, scale, eps)
            gap.append(np.asarray(g)[:m])
        res = {"gap": np.concatenate(gap)}
        if control:
            res["control_gap"] = np.concatenate(cgap)
        out.append(res)
    return out
