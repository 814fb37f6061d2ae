"""Plain float32 reference of a decoder of GQA attention and gated-MLP
layers (Mistral-NeMo's layer).

Per layer, as the program states it:

    u = rmsnorm(x);  q, k, v = u @ Wq, u @ Wk, u @ Wv   (rotary on q, k)
    o = softmax(q k^T / sqrt(hd) + causal mask) v   (query head h reads
        key/value head h // (H / Kv))
    x += o @ Wo
    u = rmsnorm(x);  x += (silu(u @ Wg) * (u @ Wi)) @ Wo2

Rotary: the two halves of each head rotate by position * theta^(-i/half).
Departures from the published model, shared with the program: tied
embeddings, norm eps 1e-6. Attention is computed in blocks of
``Q_BLOCK`` queries so that the scores of a long prompt fit.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from _common import evaluate as _evaluate, mm, normal, rmsnorm

Q_BLOCK = 512


def init_layer(key, m):
    """One layer's weights, by the random calls of the repo's init."""
    d, hq, kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    ks = jax.random.split(jax.random.split(key, 1)[0], 4)   # block, layer
    ka = jax.random.split(ks[0], 4)                         # attention
    km = jax.random.split(ks[1], 3)                         # mlp
    s = 1.0 / math.sqrt(d)
    return {
        "wq": normal(ka[0], (d, hq, hd), s).reshape(d, hq * hd),
        "wk": normal(ka[1], (d, kv, hd), s).reshape(d, kv * hd),
        "wv": normal(ka[2], (d, kv, hd), s).reshape(d, kv * hd),
        "wo": normal(ka[3], (hq, hd, d), (hq * hd) ** -0.5).reshape(hq * hd, d),
        "wi": normal(km[0], (d, f), s),
        "wo2": normal(km[1], (f, d), 1.0 / math.sqrt(f)),
        "wg": normal(km[2], (d, f), s),
        "mixer_norm": jnp.ones((d,), jnp.float32),
        "ffn_norm": jnp.ones((d,), jnp.float32),
    }


def program_layer(tree):
    """The program's one-layer parameter tree under ``init_layer``'s names
    (the MLP's ``wo`` is ``wo2`` here), for the tests that check both draw
    the same weights."""
    return {**tree["attn"],
            **{("wo2" if k == "wo" else k): v for k, v in tree["mlp"].items()}}


def rope(x, theta):
    """x [T, H, hd]: rotate the two halves by position * theta^(-i/half)."""
    t, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v):
    """Causal GQA. q [T,H,hd], k/v [T,Kv,hd] -> [T,H,hd]."""
    t, hq, hd = q.shape
    g = hq // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    hp = jax.lax.Precision.HIGHEST
    nb = t // Q_BLOCK

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=hp) / math.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[None, :, None] >= jnp.arange(t)[None, None, :],
                      s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision=hp)

    return jax.lax.map(block, jnp.arange(nb)).reshape(t, hq, hd)


def apply_layer(w, x, m, quant):
    """x [T, D] float32 (T a multiple of Q_BLOCK) -> x after one layer."""
    t = x.shape[0]
    hq, kv, hd, eps = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["norm_eps"]
    u = rmsnorm(x, w["mixer_norm"], eps)
    q = rope(mm(u, w["wq"], quant).reshape(t, hq, hd), m["rope_theta"])
    k = rope(mm(u, w["wk"], quant).reshape(t, kv, hd), m["rope_theta"])
    v = mm(u, w["wv"], quant).reshape(t, kv, hd)
    x = x + mm(attention(q, k, v).reshape(t, hq * hd), w["wo"], quant)
    u = rmsnorm(x, w["ffn_norm"], eps)
    h = jax.nn.silu(mm(u, w["wg"], quant)) * mm(u, w["wi"], quant)
    return x + mm(h, w["wo2"], quant)


def evaluate(model, seed, seqs, n_served, bucket, control=False):
    return _evaluate(init_layer, apply_layer, model, seed, seqs, n_served,
                     bucket, control)
