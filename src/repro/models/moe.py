"""Mixture-of-Experts FFN with expert parallelism (Mixtral, Kimi-K2 style).

Design (see DESIGN.md §4): tokens are sharded over ('pod','data') and
replicated over 'model'; experts are sharded over 'model'. Inside a
shard_map over the full mesh each model-shard:

  1. computes routing for its (replicated) token block — cheap,
  2. builds the capacity-dispatch buffer [E, C, d] (sort-free: one argsort
     over token-slots orders them by expert; intra-expert rank = position -
     expert start offset; slots past capacity C are dropped, their combine
     weight renormalized away — standard GShard token dropping),
  3. slices ITS experts (and its d_ff shard when E < model-axis size:
     weights are stored pre-packed device-major as [n_model, E_loc, d, ff_s]
     so a single leading-dim shard expresses joint expert×ffn sharding),
  4. runs the batched expert FFN [E_loc, C, d] on the MXU,
  5. scatter-adds its partial outputs back to token slots and psums over
     'model' — the same single all-reduce a dense TP FFN needs.

Without a mesh (unit tests / CPU) the identical math runs on one shard.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import current_mesh
from repro.models import layers

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                 # per-expert hidden dim
    n_experts: int
    top_k: int
    n_shared_experts: int = 0  # Kimi-K2: dense shared expert(s) alongside
    capacity_factor: float = 1.25
    activation: str = "silu"   # SwiGLU gating
    router_z_coef: float = 1e-3
    load_balance_coef: float = 1e-2
    dtype: object = jnp.float32


def ep_split(cfg: MoEConfig, n_model: int) -> Tuple[int, int]:
    """(experts per shard, ffn-shard ways). n_model % n_experts == 0 or
    n_experts % n_model == 0 required."""
    if cfg.n_experts % n_model == 0:
        return cfg.n_experts // n_model, 1
    if n_model % cfg.n_experts == 0:
        return 1, n_model // cfg.n_experts
    raise ValueError(f"experts={cfg.n_experts} vs model axis {n_model}")


def init_moe(key: Array, cfg: MoEConfig, n_model: int = 1) -> Dict[str, Array]:
    """Weights pre-packed device-major: [n_model, E_loc, ...ff_s...]."""
    e_loc, fs = ep_split(cfg, n_model)
    ff_s = cfg.d_ff // fs
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    std_in = cfg.d_model ** -0.5
    std_out = cfg.d_ff ** -0.5
    def w(k, shape, std):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * std
                ).astype(cfg.dtype)
    params = {
        "router": w(k1, (cfg.d_model, cfg.n_experts), std_in).astype(
            jnp.float32),
        "wi": w(k2, (n_model, e_loc, cfg.d_model, ff_s), std_in),
        "wg": w(k3, (n_model, e_loc, cfg.d_model, ff_s), std_in),
        "wo": w(k4, (n_model, e_loc, ff_s, cfg.d_model), std_out),
    }
    if cfg.n_shared_experts:
        ks1, ks2, ks3 = jax.random.split(k5, 3)
        dsh = cfg.d_ff * cfg.n_shared_experts
        params["shared"] = {
            "wi": w(ks1, (cfg.d_model, dsh), std_in),
            "wg": w(ks2, (cfg.d_model, dsh), std_in),
            "wo": w(ks3, (dsh, cfg.d_model), std_out),
        }
    return params


def moe_spec(cfg: MoEConfig) -> Dict:
    spec = {
        "router": ("none", "none"),
        "wi": ("experts", "none", "embed", "none"),
        "wg": ("experts", "none", "embed", "none"),
        "wo": ("experts", "none", "none", "embed"),
    }
    if cfg.n_shared_experts:
        spec["shared"] = {"wi": ("embed", "mlp"),
                          "wg": ("embed", "mlp"),
                          "wo": ("mlp", "embed")}
    return spec


def _dispatch(tokens: Array, router_w: Array, cfg: MoEConfig,
              capacity: int):
    """Routing + capacity dispatch. tokens: [T, D].

    Returns (buf [E, C, D], combine_idx [E, C] token ids, combine_w [E, C],
             valid [E, C], aux losses dict).
    """
    t, d = tokens.shape
    e, k = cfg.n_experts, cfg.top_k
    logits = tokens.astype(jnp.float32) @ router_w            # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                    # [T, K]
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # aux losses (Switch/Mixtral style)
    me = probs.mean(axis=0)                                   # [E]
    ce = jnp.zeros((e,)).at[top_e.reshape(-1)].add(1.0) / (t * k)
    lb_loss = cfg.load_balance_coef * e * jnp.sum(me * ce)
    z_loss = cfg.router_z_coef * jnp.mean(
        jax.nn.logsumexp(logits, axis=-1) ** 2)

    # slot ordering: sort (token, k) slots by expert id
    slot_e = top_e.reshape(-1)                                # [T*K]
    slot_tok = jnp.repeat(jnp.arange(t), k)
    slot_w = top_w.reshape(-1)
    order = jnp.argsort(slot_e, stable=True)
    se, st, sw = slot_e[order], slot_tok[order], slot_w[order]
    counts = jnp.zeros((e,), jnp.int32).at[se].add(1)
    starts = jnp.cumsum(counts) - counts                      # [E] excl prefix
    rank = jnp.arange(t * k) - starts[se]                     # intra-expert pos
    keep = rank < capacity
    # scatter into [E, C]
    dst = se * capacity + jnp.where(keep, rank, capacity)     # overflow -> pad
    combine_tok = jnp.full((e * capacity + 1,), t, jnp.int32).at[dst].set(
        jnp.where(keep, st, t))[:-1].reshape(e, capacity)
    combine_w = jnp.zeros((e * capacity + 1,)).at[dst].set(
        jnp.where(keep, sw, 0.0))[:-1].reshape(e, capacity)
    valid = combine_tok < t
    # gather tokens (padded row at index t)
    tok_pad = jnp.concatenate([tokens, jnp.zeros((1, d), tokens.dtype)], 0)
    buf = tok_pad[combine_tok]                                # [E, C, D]
    aux = {"moe_load_balance": lb_loss, "moe_z": z_loss,
           "moe_drop_frac": 1.0 - keep.mean()}
    return buf, combine_tok, combine_w, valid, aux


def _expert_ffn(buf: Array, wi: Array, wg: Array, wo: Array,
                activation: str) -> Array:
    """buf: [E_loc, C, D] x wi/wg [E_loc, D, F] -> wo [E_loc, F, D]."""
    act = layers.ACTIVATIONS[activation]
    h = jnp.einsum("ecd,edf->ecf", buf, wi)
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    return jnp.einsum("ecf,efd->ecd", act(g) * h, wo)


def _moe_local(tokens, router_w, wi, wg, wo, cfg: MoEConfig, capacity: int,
               m_idx, n_model: int):
    """Per-shard computation (tokens replicated over 'model')."""
    t, d = tokens.shape
    e_loc = wi.shape[0]
    buf, ctok, cw, valid, aux = _dispatch(tokens, router_w, cfg, capacity)
    del valid  # combine weights of dropped slots are already zero
    # first global expert owned by this shard: contiguous E_loc experts when
    # E >= n_model, else expert m_idx // (n_model / E) (ffn-sharded fs ways)
    if cfg.n_experts % n_model == 0:
        e0 = m_idx * e_loc
    else:
        e0 = m_idx // (n_model // cfg.n_experts)
    buf_loc = jax.lax.dynamic_slice_in_dim(buf, e0, e_loc, axis=0)
    out_loc = _expert_ffn(buf_loc.astype(wi.dtype), wi, wg, wo,
                          cfg.activation)                     # [E_loc, C, D]
    ctok_loc = jax.lax.dynamic_slice_in_dim(ctok, e0, e_loc, axis=0)
    cw_loc = jax.lax.dynamic_slice_in_dim(cw, e0, e_loc, axis=0)
    y = jnp.zeros((t + 1, d), jnp.float32).at[ctok_loc.reshape(-1)].add(
        (out_loc * cw_loc[..., None]).astype(jnp.float32).reshape(-1, d))
    return y[:t], aux


def apply_moe(params: Dict[str, Array], x: Array, cfg: MoEConfig, *,
              weights_stationary: bool = False
              ) -> Tuple[Array, Dict[str, Array]]:
    """x: [B, S, D] -> (y [B, S, D], aux losses).

    ``weights_stationary=True`` (serving/decode): token counts are tiny, so
    instead of FSDP-gathering expert weights every step (GBs of ICI per
    token), tokens REPLICATE across the data axis and each device computes
    its (expert-slice x d_ff-slice) tile — weights never move; one psum over
    ('data','model') of the [T, D] outputs (~MBs) combines the tiles. This is
    the production "weights stay put, activations move" MoE decode dataflow.
    Requires d_ff % n_data == 0 (expert weights stored sharded on d_ff over
    'data' at rest via the standard FSDP spec)."""
    b, s, d = x.shape
    mesh = current_mesh()
    n_model = dict(mesh.shape).get("model", 1) if mesh else 1

    if weights_stationary and mesh is not None and n_model > 1:
        return _apply_moe_stationary(params, x, cfg, mesh, n_model)

    def run(tokens, router_w, wi, wg, wo, m_idx, t_per_shard):
        capacity = max(1, int(
            t_per_shard * cfg.top_k * cfg.capacity_factor / cfg.n_experts))
        return _moe_local(tokens, router_w, wi, wg, wo, cfg, capacity,
                          m_idx, n_model)

    if mesh is None or n_model == 1:
        tokens = x.reshape(-1, d)
        y, aux = run(tokens, params["router"], params["wi"][0],
                     params["wg"][0], params["wo"][0], 0, tokens.shape[0])
        y = y.reshape(b, s, d).astype(x.dtype)
    else:
        sizes = dict(mesh.shape)
        axes, dp = [], 1
        for a in ("pod", "data"):
            if a in sizes and b % (dp * sizes[a]) == 0:
                axes.append(a)
                dp *= sizes[a]
        # small-batch decode: batch may not shard across all data axes —
        # tokens replicate over the remaining axes, experts stay sharded.
        t_per_shard = (b // dp) * s
        batch_axes = tuple(axes) if axes else None

        def shard_fn(xb, router_w, wi, wg, wo):
            tokens = xb.reshape(-1, d)
            m_idx = jax.lax.axis_index("model")
            y, aux = run(tokens, router_w, wi[0], wg[0], wo[0], m_idx,
                         t_per_shard)
            y = jax.lax.psum(y, "model")
            aux = {k: jax.lax.pmean(v, "model") for k, v in aux.items()}
            return y.reshape(xb.shape[0], s, d).astype(x.dtype), aux

        y, aux = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(batch_axes, None, None), P(None, None),
                      P("model"), P("model"), P("model")),
            out_specs=(P(batch_axes, None, None), P()),
            check_vma=False,
        )(x, params["router"], params["wi"], params["wg"], params["wo"])

    if cfg.n_shared_experts:
        sh = params["shared"]
        act = layers.ACTIVATIONS[cfg.activation]
        h = act(x @ sh["wg"]) * (x @ sh["wi"])
        y = y + (h @ sh["wo"]).astype(y.dtype)
    return y, aux


def _apply_moe_stationary(params, x: Array, cfg: MoEConfig, mesh,
                          n_model: int):
    b, s, d = x.shape
    sizes = dict(mesh.shape)
    data_axes = tuple(a for a in ("pod", "data") if a in sizes)
    n_data = 1
    for a in data_axes:
        n_data *= sizes[a]
    e_loc, fs = ep_split(cfg, n_model)
    ff_s = params["wi"].shape[-1]          # per-model-shard d_ff slice
    if ff_s % n_data != 0:
        raise ValueError(f"d_ff slice {ff_s} not divisible by data={n_data}")
    t_total = b * s
    capacity = max(1, int(
        t_total * cfg.top_k * cfg.capacity_factor / cfg.n_experts))

    def shard_fn(xb, router_w, wi, wg, wo):
        # xb replicated: every device routes ALL tokens (tiny at decode)
        tokens = xb.reshape(-1, d)
        m_idx = jax.lax.axis_index("model")
        buf, ctok, cw, _, aux = _dispatch(tokens, router_w, cfg, capacity)
        if cfg.n_experts % n_model == 0:
            e0 = m_idx * e_loc
        else:
            e0 = m_idx // (n_model // cfg.n_experts)
        buf_loc = jax.lax.dynamic_slice_in_dim(buf, e0, e_loc, axis=0)
        # wi/wg: [1, E_loc, d, ff_s/n_data]; wo: [1, E_loc, ff_s/n_data, d]
        out_loc = _expert_ffn(buf_loc.astype(wi.dtype), wi[0], wg[0], wo[0],
                              cfg.activation)
        ctok_loc = jax.lax.dynamic_slice_in_dim(ctok, e0, e_loc, axis=0)
        cw_loc = jax.lax.dynamic_slice_in_dim(cw, e0, e_loc, axis=0)
        y = jnp.zeros((t_total + 1, d), jnp.float32).at[
            ctok_loc.reshape(-1)].add(
            (out_loc * cw_loc[..., None]).astype(jnp.float32).reshape(-1, d))
        y = jax.lax.psum(y[:t_total], data_axes + ("model",))
        aux = {k: jax.lax.pmean(v, "model") for k, v in aux.items()}
        return y.reshape(b, s, d).astype(x.dtype), aux

    ff_axis = data_axes if len(data_axes) > 1 else (data_axes[0]
                                                    if data_axes else None)
    y, aux = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(None, None, None), P(None, None),
                  P("model", None, None, ff_axis),
                  P("model", None, None, ff_axis),
                  P("model", None, ff_axis, None)),
        out_specs=(P(None, None, None), P()),
        check_vma=False,
    )(x, params["router"], params["wi"], params["wg"], params["wo"])

    if cfg.n_shared_experts:
        sh = params["shared"]
        act = layers.ACTIVATIONS[cfg.activation]
        h = act(x @ sh["wg"]) * (x @ sh["wi"])
        y = y + (h @ sh["wo"]).astype(y.dtype)
    return y, aux
