"""Plain float32 reference of a Mamba-2 stack (attention-free SSD layers).

Per layer, as the program states it (Dao & Gu 2024, arXiv:2405.21060):

    u = rmsnorm(x);  [z, xs, B, C, dt] = u @ W_in
    [xs, B, C] = silu(causal depthwise conv_4([xs, B, C]))
    dt = softplus(dt + dt_bias);  A = -exp(a_log)
    h_t = exp(dt_t A) h_{t-1} + dt_t xs_t (x) B_t;  y_t = C_t . h_t + D xs_t
    x += (rmsnorm(y * silu(z)) * g) @ W_out

B and C are one group shared by all heads. Departures from the published
model, shared with the program: no conv bias, norm eps 1e-6. The scan is
written as its exact chunked (dual) form over chunks of ``SCAN_CHUNK``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from _common import evaluate as _evaluate, mm, normal, rmsnorm

SCAN_CHUNK = 128


def _sizes(m):
    d, n, p = m["d_model"], m["ssm_state"], m["ssm_head_dim"]
    di = m["ssm_expand"] * d
    return d, di, n, p, di // p, m["ssm_conv_width"]


def init_layer(key, m):
    """One layer's weights, by the random calls of the repo's init."""
    d, di, n, p, h, k = _sizes(m)
    ks = jax.random.split(jax.random.split(key, 1)[0], 4)   # block, layer
    kb = jax.random.split(ks[0], 4)                         # ssd block
    return {
        "in_proj": normal(kb[0], (d, 2 * di + 2 * n + h), 1.0 / math.sqrt(d)),
        "conv": normal(kb[1], (k, di + 2 * n), 0.2),
        "a_log": jnp.zeros((h,), jnp.float32),
        "dt_bias": jnp.full((h,), math.log(math.e - 1), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm": jnp.ones((di,), jnp.float32),
        "mixer_norm": jnp.ones((d,), jnp.float32),
        "out_proj": normal(kb[2], (di, d), 1.0 / math.sqrt(di)),
    }


def program_layer(tree):
    """The program's one-layer parameter tree under ``init_layer``'s names,
    for the tests that check both draw the same weights."""
    return dict(tree["ssd"])


def ssd_scan(xs, dt, a, b, c):
    """xs [T,H,P], dt [T,H], a [H], b/c [T,N] -> y [T,H,P] (no skip)."""
    t, h, p = xs.shape
    q = SCAN_CHUNK
    pad = (-t) % q
    xs, dt = jnp.pad(xs, ((0, pad), (0, 0), (0, 0))), jnp.pad(dt, ((0, pad), (0, 0)))
    b, c = jnp.pad(b, ((0, pad), (0, 0))), jnp.pad(c, ((0, pad), (0, 0)))
    nc = (t + pad) // q
    xs, dt = xs.reshape(nc, q, h, p), dt.reshape(nc, q, h)
    b, c = b.reshape(nc, q, -1), c.reshape(nc, q, -1)
    hp = jax.lax.Precision.HIGHEST
    la = jnp.cumsum(dt * a, axis=1)                        # [nc,q,H] log decay
    seg = la[:, :, None, :] - la[:, None, :, :]            # [nc,i,j,H]
    causal = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("cin,cjn->cij", c, b, precision=hp)
    xdt = xs * dt[..., None]
    y_in = jnp.einsum("cij,cijh,cjhp->cihp", cb, decay, xdt, precision=hp)
    to_end = jnp.exp(la[:, -1:, :] - la)                   # [nc,q,H]
    chunk_state = jnp.einsum("cjh,cjhp,cjn->chpn", to_end, xdt, b,
                             precision=hp)

    def carry(s, inp):
        st, dec = inp
        return dec[:, None, None] * s + st, s

    _, before = jax.lax.scan(carry, jnp.zeros((h, p, b.shape[-1]), jnp.float32),
                             (chunk_state, jnp.exp(la[:, -1, :])))
    y_out = jnp.einsum("chpn,cin,cih->cihp", before, c, jnp.exp(la),
                       precision=hp)
    return (y_in + y_out).reshape(nc * q, h, p)[:t]


def apply_layer(w, x, m, quant):
    """x [T, D] float32 -> x after one layer."""
    d, di, n, p, h, k = _sizes(m)
    eps = m["norm_eps"]
    t = x.shape[0]
    zx = mm(rmsnorm(x, w["mixer_norm"], eps), w["in_proj"], quant)
    z, xbc, dt = zx[:, :di], zx[:, di:2 * di + 2 * n], zx[:, 2 * di + 2 * n:]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = sum(padded[i:i + t] * w["conv"][i] for i in range(k))
    xbc = jax.nn.silu(conv)
    xs, b, c = xbc[:, :di], xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = -jnp.exp(w["a_log"])
    xs = xs.reshape(t, h, p)
    y = ssd_scan(xs, dt, a, b, c) + w["d_skip"][None, :, None] * xs
    y = rmsnorm(y.reshape(t, di) * jax.nn.silu(z), w["norm"], eps)
    return x + mm(y, w["out_proj"], quant)


def evaluate(model, seed, seqs, n_served, bucket, control=False):
    return _evaluate(init_layer, apply_layer, model, seed, seqs, n_served,
                     bucket, control)
