"""Unified LM: composable decoder / encoder-decoder transformer covering all
assigned architecture families.

Per-layer structure is a (mixer, ffn) pair:
  mixer ∈ attn (full causal GQA) | swa (sliding window) | local (Griffin
          local attn) | bidir (encoder) | rglru | ssd | none
  ffn   ∈ mlp | moe | kan | none

``block_pattern`` cycles over layers (e.g. recurrentgemma = [rglru, rglru,
local]); consecutive repeats of the pattern are *stacked* and executed with
``lax.scan`` over the layer axis (MaxText-style) so the HLO stays O(1) in
depth — essential for 80-96 layer dry-runs — with optional remat for
activation memory. The paper's technique enters as ``ffn="kan"``: the
ASP-KAN-HAQ quantized KAN-FFN replacing the MLP block (DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import kan
from repro.core.quant import ASPConfig
from repro.dist.sharding import shard
from repro.models import attention as attn_lib
from repro.models import layers, moe as moe_lib, rglru as rglru_lib
from repro.models import ssd as ssd_lib

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str = "attn"     # attn|swa|local|bidir|rglru|ssd|none
    ffn: str = "mlp"        # mlp|moe|kan|none
    cross_attn: bool = False


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"   # dense|moe|ssm|hybrid|encdec|vlm|audio
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 256
    vocab: int = 1024
    head_dim: int = 0                    # 0 -> d_model // n_heads
    activation: str = "silu"
    gated_mlp: bool = True
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    norm: str = "rmsnorm"
    tie_embeddings: bool = True
    logits_softcap: float = 0.0
    # layer pattern
    block_pattern: Tuple[LayerSpec, ...] = (LayerSpec(),)
    first_layers: Tuple[LayerSpec, ...] = ()   # override for leading layers
    window: int = 0                      # swa window
    local_window: int = 0                # griffin local-attn window
    # moe
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    # ssm
    ssm_state: int = 128
    ssm_head_dim: int = 64
    ssm_chunk: int = 64
    rnn_width: int = 0                   # rg-lru width (0 -> d_model)
    # enc-dec
    n_enc_layers: int = 0                # >0 => family encdec
    enc_bidirectional: bool = True
    # frontend stubs
    frontend: str = "none"               # none|audio_stub|vision_stub
    n_vision_patches: int = 256
    max_target_len: int = 8192           # learned positions for enc-dec dec
    # KAN-FFN (the paper's technique as a first-class FFN option)
    kan_hidden: int = 0                  # 0 -> d_ff // (G + K + 1)
    kan_grid: int = 8
    kan_order: int = 3
    kan_backend: str = "lut"             # core.kan registry: ref|lut|fused|cim
    # execution
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    scan_layers: bool = True
    attn_kv_chunk: int = 512
    # perf levers (EXPERIMENTS.md §Perf records before/after for each):
    ce_impl: str = "gather"              # "gather" | "onehot" (sharded-safe)
    prescan_cast: bool = False           # cast params to compute dtype once
    kv_shard_mode: str = "head_dim"      # "head_dim" | "replicate" for KV
    moe_serve_stationary: bool = False   # weights-stationary MoE at decode
    # pad q/kv head counts up to multiples of the model axis so attention
    # shards cleanly (zero-init padded heads are exact: wo rows are zero)
    pad_attn_heads: int = 0              # 0 = off; else multiple to pad to
    # Megatron-style sequence parallelism for layer-boundary activations:
    # the saved per-layer residual stream shards its seq dim over 'model',
    # cutting the dominant activation-memory term n_layers/16x at the cost
    # of an all-gather per layer input (see EXPERIMENTS.md §Perf).
    seq_shard_activations: bool = False

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def _pad(self, n: int) -> int:
        m = self.pad_attn_heads
        return n if not m else -(-n // m) * m

    @property
    def padded_heads(self) -> int:
        return self._pad(self.n_heads)

    @property
    def padded_kv_heads(self) -> int:
        return self._pad(self.n_kv_heads)

    @property
    def kan_spec(self) -> kan.KANSpec:
        asp = ASPConfig(grid_size=self.kan_grid, order=self.kan_order)
        hidden = self.kan_hidden or max(
            8, self.d_ff // (self.kan_grid + self.kan_order + 1))
        return kan.KANSpec.ffn(self.d_model, hidden, asp,
                               backend=self.kan_backend,
                               dtype=self.param_dtype)

    @property
    def moe_cfg(self) -> moe_lib.MoEConfig:
        return moe_lib.MoEConfig(
            d_model=self.d_model, d_ff=self.moe_d_ff or self.d_ff,
            n_experts=self.n_experts, top_k=self.top_k,
            n_shared_experts=self.n_shared_experts,
            capacity_factor=self.capacity_factor,
            activation=self.activation, dtype=self.param_dtype)

    @property
    def ssd_cfg(self) -> ssd_lib.SSDConfig:
        return ssd_lib.SSDConfig(
            d_model=self.d_model, d_state=self.ssm_state,
            head_dim=self.ssm_head_dim, chunk=self.ssm_chunk,
            dtype=self.param_dtype)

    @property
    def rglru_cfg(self) -> rglru_lib.RGLRUConfig:
        return rglru_lib.RGLRUConfig(
            d_model=self.d_model, d_rnn=self.rnn_width or self.d_model,
            dtype=self.param_dtype)

    def layer_specs(self, n_layers: Optional[int] = None) -> List[LayerSpec]:
        n = n_layers if n_layers is not None else self.n_layers
        specs = list(self.first_layers)
        i = 0
        while len(specs) < n:
            specs.append(self.block_pattern[i % len(self.block_pattern)])
            i += 1
        return specs[:n]


@dataclasses.dataclass(frozen=True)
class Stage:
    block: Tuple[LayerSpec, ...]
    repeats: int


def compute_stages(specs: Sequence[LayerSpec],
                   pattern_len: int) -> List[Stage]:
    """Group layers into (pattern block × repeats) stages for lax.scan."""
    stages: List[Stage] = []
    i = 0
    n = len(specs)
    while i < n:
        blk = tuple(specs[i:i + pattern_len])
        reps = 1
        while (i + (reps + 1) * len(blk) <= n
               and tuple(specs[i + reps * len(blk):
                               i + (reps + 1) * len(blk)]) == blk):
            reps += 1
        if len(blk) == pattern_len and reps > 1:
            stages.append(Stage(blk, reps))
            i += reps * len(blk)
        else:
            stages.append(Stage((specs[i],), 1))
            i += 1
    return stages


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_attn(key, cfg: ModelConfig, cross: bool = False) -> Dict:
    hd = cfg.resolved_head_dim
    hq, hkv = cfg.padded_heads, cfg.padded_kv_heads
    ks = jax.random.split(key, 4)
    wq = layers.dense_init(ks[0], cfg.d_model, (cfg.n_heads, hd),
                           dtype=cfg.param_dtype)
    wk = layers.dense_init(ks[1], cfg.d_model, (cfg.n_kv_heads, hd),
                           dtype=cfg.param_dtype)
    wv = layers.dense_init(ks[2], cfg.d_model, (cfg.n_kv_heads, hd),
                           dtype=cfg.param_dtype)
    wo = (jax.random.normal(ks[3], (cfg.n_heads, hd, cfg.d_model))
          * (cfg.n_heads * hd) ** -0.5).astype(cfg.param_dtype)
    if hq != cfg.n_heads or hkv != cfg.n_kv_heads:
        # zero-padded heads are mathematically inert (wo rows are zero) but
        # let every attention tensor shard cleanly on the model axis.
        wq = jnp.pad(wq, ((0, 0), (0, hq - cfg.n_heads), (0, 0)))
        wk = jnp.pad(wk, ((0, 0), (0, hkv - cfg.n_kv_heads), (0, 0)))
        wv = jnp.pad(wv, ((0, 0), (0, hkv - cfg.n_kv_heads), (0, 0)))
        wo = jnp.pad(wo, ((0, hq - cfg.n_heads), (0, 0), (0, 0)))
    p = {"wq": wq, "wk": wk, "wv": wv, "wo": wo}
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((hq, hd), cfg.param_dtype)
        p["bk"] = jnp.zeros((hkv, hd), cfg.param_dtype)
        p["bv"] = jnp.zeros((hkv, hd), cfg.param_dtype)
    return p


def _attn_spec(cfg: ModelConfig, cross: bool = False) -> Dict:
    kv_tail = "head_dim" if cfg.kv_shard_mode == "head_dim" else "none"
    s = {"wq": ("embed", "heads", "none"),
         "wk": ("embed", "kv_heads", kv_tail),
         "wv": ("embed", "kv_heads", kv_tail),
         "wo": ("heads", "none", "embed")}
    if cfg.qkv_bias and not cross:
        s["bq"] = ("heads", "none")
        s["bk"] = ("kv_heads", kv_tail)
        s["bv"] = ("kv_heads", kv_tail)
    return s


def _init_mlp(key, cfg: ModelConfig) -> Dict:
    ks = jax.random.split(key, 3)
    p = {"wi": layers.dense_init(ks[0], cfg.d_model, cfg.d_ff,
                                 dtype=cfg.param_dtype),
         "wo": layers.dense_init(ks[1], cfg.d_ff, cfg.d_model,
                                 dtype=cfg.param_dtype)}
    if cfg.gated_mlp:
        p["wg"] = layers.dense_init(ks[2], cfg.d_model, cfg.d_ff,
                                    dtype=cfg.param_dtype)
    return p


def _mlp_spec(cfg: ModelConfig) -> Dict:
    s = {"wi": ("embed", "mlp"), "wo": ("mlp", "embed")}
    if cfg.gated_mlp:
        s["wg"] = ("embed", "mlp")
    return s


def _init_layer(key, spec: LayerSpec, cfg: ModelConfig,
                n_model: int) -> Dict:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {}
    if spec.mixer in ("attn", "swa", "local", "bidir"):
        p["mixer_norm"] = layers.NORM_INIT[cfg.norm](cfg.d_model)
        p["attn"] = _init_attn(ks[0], cfg)
    elif spec.mixer == "rglru":
        p["mixer_norm"] = layers.NORM_INIT[cfg.norm](cfg.d_model)
        p["rglru"] = rglru_lib.init_rglru_block(ks[0], cfg.rglru_cfg)
    elif spec.mixer == "ssd":
        p["mixer_norm"] = layers.NORM_INIT[cfg.norm](cfg.d_model)
        p["ssd"] = ssd_lib.init_ssd_block(ks[0], cfg.ssd_cfg)
    if spec.cross_attn:
        p["cross_norm"] = layers.NORM_INIT[cfg.norm](cfg.d_model)
        p["cross"] = _init_attn(ks[2], cfg, cross=True)
    if spec.ffn == "mlp":
        p["ffn_norm"] = layers.NORM_INIT[cfg.norm](cfg.d_model)
        p["mlp"] = _init_mlp(ks[1], cfg)
    elif spec.ffn == "moe":
        p["ffn_norm"] = layers.NORM_INIT[cfg.norm](cfg.d_model)
        p["moe"] = moe_lib.init_moe(ks[1], cfg.moe_cfg, n_model)
    elif spec.ffn == "kan":
        p["ffn_norm"] = layers.NORM_INIT[cfg.norm](cfg.d_model)
        p["kan"] = kan.init(ks[1], cfg.kan_spec)
    return p


def _layer_spec_tree(spec: LayerSpec, cfg: ModelConfig) -> Dict:
    s: Dict[str, Any] = {}
    nrm = layers.norm_spec(cfg.norm)
    if spec.mixer in ("attn", "swa", "local", "bidir"):
        s["mixer_norm"] = nrm
        s["attn"] = _attn_spec(cfg)
    elif spec.mixer == "rglru":
        s["mixer_norm"] = nrm
        s["rglru"] = rglru_lib.rglru_block_spec(cfg.rglru_cfg)
    elif spec.mixer == "ssd":
        s["mixer_norm"] = nrm
        s["ssd"] = ssd_lib.ssd_block_spec(cfg.ssd_cfg)
    if spec.cross_attn:
        s["cross_norm"] = nrm
        s["cross"] = _attn_spec(cfg, cross=True)
    if spec.ffn == "mlp":
        s["ffn_norm"] = nrm
        s["mlp"] = _mlp_spec(cfg)
    elif spec.ffn == "moe":
        s["ffn_norm"] = nrm
        s["moe"] = moe_lib.moe_spec(cfg.moe_cfg)
    elif spec.ffn == "kan":
        lay = {"coeffs": ("embed", "none", "mlp"), "w_base": ("embed", "mlp")}
        lay2 = {"coeffs": ("mlp", "none", "embed"), "w_base": ("mlp", "embed")}
        s["ffn_norm"] = nrm
        s["kan"] = {"up": lay, "down": lay2}
    return s


def _init_stage(key, stage: Stage, cfg: ModelConfig, n_model: int) -> Dict:
    def init_block(k):
        kk = jax.random.split(k, len(stage.block))
        return {f"l{i}": _init_layer(kk[i], sp, cfg, n_model)
                for i, sp in enumerate(stage.block)}
    if stage.repeats == 1:
        return init_block(key)
    return jax.vmap(init_block)(jax.random.split(key, stage.repeats))


def _stage_spec(stage: Stage, cfg: ModelConfig) -> Dict:
    blk = {f"l{i}": _layer_spec_tree(sp, cfg)
           for i, sp in enumerate(stage.block)}
    if stage.repeats == 1:
        return blk
    # prepend the stacked layer axis
    return jax.tree.map(lambda names: ("layers",) + names, blk,
                        is_leaf=lambda x: isinstance(x, tuple))


def stages_for(cfg: ModelConfig, n_layers: Optional[int] = None,
               encoder: bool = False) -> List[Stage]:
    if encoder:
        specs = [LayerSpec("bidir", "mlp")] * cfg.n_enc_layers
        if not cfg.scan_layers:
            return [Stage((sp,), 1) for sp in specs]
        return compute_stages(specs, 1)
    specs = cfg.layer_specs(n_layers)
    if cfg.family == "encdec":
        specs = [dataclasses.replace(s, cross_attn=True) for s in specs]
    if not cfg.scan_layers:
        return [Stage((sp,), 1) for sp in specs]
    return compute_stages(specs, len(cfg.block_pattern))


def init_model(key, cfg: ModelConfig, n_model: int = 1) -> Dict:
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": layers.init_embedding(ks[0], cfg.vocab, cfg.d_model,
                                       dtype=cfg.param_dtype),
        "final_norm": layers.NORM_INIT[cfg.norm](cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = layers.init_embedding(
            ks[1], cfg.vocab, cfg.d_model, dtype=cfg.param_dtype)
    stages = stages_for(cfg)
    params["stages"] = [
        _init_stage(jax.random.fold_in(ks[2], i), st, cfg, n_model)
        for i, st in enumerate(stages)]
    if cfg.family == "encdec":
        enc_stages = stages_for(cfg, encoder=True)
        params["enc_stages"] = [
            _init_stage(jax.random.fold_in(ks[3], i), st, cfg, n_model)
            for i, st in enumerate(enc_stages)]
        params["enc_final_norm"] = layers.NORM_INIT[cfg.norm](cfg.d_model)
        params["dec_pos"] = (jax.random.normal(
            ks[4], (cfg.max_target_len, cfg.d_model)) * 0.02
            ).astype(cfg.param_dtype)
    return params


def param_spec(cfg: ModelConfig) -> Dict:
    spec: Dict[str, Any] = {
        "embed": ("vocab", "embed"),
        "final_norm": layers.norm_spec(cfg.norm),
    }
    if not cfg.tie_embeddings:
        spec["unembed"] = ("vocab", "embed")
    spec["stages"] = [_stage_spec(st, cfg) for st in stages_for(cfg)]
    if cfg.family == "encdec":
        spec["enc_stages"] = [_stage_spec(st, cfg)
                              for st in stages_for(cfg, encoder=True)]
        spec["enc_final_norm"] = layers.norm_spec(cfg.norm)
        spec["dec_pos"] = ("none", "embed")
    return spec


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _attn_mixer(p, x, cfg: ModelConfig, spec: LayerSpec, positions,
                enc_out=None):
    hd = cfg.resolved_head_dim
    xn = layers.NORM_APPLY[cfg.norm](p["mixer_norm"], x)
    q = jnp.einsum("bsd,dhk->bshk", xn, p["attn"]["wq"].astype(cfg.dtype))
    k = jnp.einsum("bsd,dhk->bshk", xn, p["attn"]["wk"].astype(cfg.dtype))
    v = jnp.einsum("bsd,dhk->bshk", xn, p["attn"]["wv"].astype(cfg.dtype))
    if "bq" in p["attn"]:
        q = q + p["attn"]["bq"].astype(cfg.dtype)
        k = k + p["attn"]["bk"].astype(cfg.dtype)
        v = v + p["attn"]["bv"].astype(cfg.dtype)
    kv_tail = "head_dim" if cfg.kv_shard_mode == "head_dim" else None
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", kv_tail)
    v = shard(v, "batch", "seq", "kv_heads", kv_tail)
    if spec.mixer != "bidir" and cfg.rope_theta:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    if spec.mixer == "swa" and cfg.window:
        o = attn_lib.windowed_attention(q, k, v, window=cfg.window)
    elif spec.mixer == "local" and cfg.local_window:
        o = attn_lib.windowed_attention(q, k, v, window=cfg.local_window)
    else:
        o = attn_lib.chunked_attention(q, k, v,
                                       causal=(spec.mixer != "bidir"),
                                       kv_chunk=cfg.attn_kv_chunk)
    o = shard(o, "batch", "seq", "heads", None)
    return jnp.einsum("bshk,hkd->bsd", o, p["attn"]["wo"].astype(cfg.dtype))


def _cross_mixer(p, x, cfg: ModelConfig, enc_out):
    xn = layers.NORM_APPLY[cfg.norm](p["cross_norm"], x)
    q = jnp.einsum("bsd,dhk->bshk", xn, p["cross"]["wq"].astype(cfg.dtype))
    k = jnp.einsum("bsd,dhk->bshk", enc_out,
                   p["cross"]["wk"].astype(cfg.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out,
                   p["cross"]["wv"].astype(cfg.dtype))
    o = attn_lib.chunked_attention(q, k, v, causal=False,
                                   kv_chunk=cfg.attn_kv_chunk)
    return jnp.einsum("bshk,hkd->bsd", o, p["cross"]["wo"].astype(cfg.dtype))


def _mlp_ffn(p, x, cfg: ModelConfig):
    xn = layers.NORM_APPLY[cfg.norm](p["ffn_norm"], x)
    act = layers.ACTIVATIONS[cfg.activation]
    wi = p["mlp"]["wi"].astype(cfg.dtype)
    wo = p["mlp"]["wo"].astype(cfg.dtype)
    h = xn @ wi
    if cfg.gated_mlp:
        h = act(xn @ p["mlp"]["wg"].astype(cfg.dtype)) * h
    else:
        h = act(h)
    h = shard(h, "batch", "seq", "mlp")
    return h @ wo


def _apply_layer(p, x, spec: LayerSpec, cfg: ModelConfig, positions,
                 enc_out=None):
    aux = {}
    # the scopes group a trace's ops as serving's layers do (serve/decode.py)
    with jax.named_scope("mixer"):
        if spec.mixer in ("attn", "swa", "local", "bidir"):
            x = x + _attn_mixer(p, x, cfg, spec, positions, enc_out)
        elif spec.mixer == "rglru":
            xn = layers.NORM_APPLY[cfg.norm](p["mixer_norm"], x)
            x = x + rglru_lib.apply_rglru_block(p["rglru"], xn,
                                                cfg.rglru_cfg
                                                ).astype(x.dtype)
        elif spec.mixer == "ssd":
            xn = layers.NORM_APPLY[cfg.norm](p["mixer_norm"], x)
            x = x + ssd_lib.apply_ssd_block(p["ssd"], xn, cfg.ssd_cfg
                                            ).astype(x.dtype)
        if spec.cross_attn and enc_out is not None:
            x = x + _cross_mixer(p, x, cfg, enc_out)
    with jax.named_scope("ffn"):
        if spec.ffn == "mlp":
            x = x + _mlp_ffn(p, x, cfg)
        elif spec.ffn == "moe":
            xn = layers.NORM_APPLY[cfg.norm](p["ffn_norm"], x)
            y, aux = moe_lib.apply_moe(p["moe"], xn, cfg.moe_cfg)
            x = x + y
        elif spec.ffn == "kan":
            xn = layers.NORM_APPLY[cfg.norm](p["ffn_norm"], x)
            with jax.named_scope("kan_ffn"):
                x = x + kan.apply_any(p["kan"], xn,
                                      cfg.kan_spec).astype(x.dtype)
    x = shard(x, "batch", "seq_sp" if cfg.seq_shard_activations else "seq",
              None)
    return x, aux


def _apply_block(block_params, x, stage: Stage, cfg: ModelConfig, positions,
                 enc_out=None):
    aux_total = jnp.zeros((), jnp.float32)
    for i, spec in enumerate(stage.block):
        x, aux = _apply_layer(block_params[f"l{i}"], x, spec, cfg,
                              positions, enc_out)
        for k in ("moe_load_balance", "moe_z"):
            if k in aux:
                aux_total = aux_total + aux[k]
    return x, aux_total


def _run_stages(stage_params, stages, x, cfg: ModelConfig, positions,
                enc_out=None):
    aux_total = jnp.zeros((), jnp.float32)
    if cfg.prescan_cast:
        # cast float params to the compute dtype BEFORE the layer scan: FSDP
        # all-gathers then move bf16 (2x less ICI) and happen once per step
        # instead of per microbatch.
        def _cast(p):
            return (p.astype(cfg.dtype)
                    if p.dtype in (jnp.float32, jnp.bfloat16) else p)
        stage_params = jax.tree.map(_cast, stage_params)
    for st_params, stage in zip(stage_params, stages):
        if stage.repeats == 1:
            fn = functools.partial(_apply_block, stage=stage, cfg=cfg,
                                   positions=positions, enc_out=enc_out)
            if cfg.remat:
                fn = jax.checkpoint(fn)
            x, aux = fn(st_params, x)
            aux_total = aux_total + aux
        else:
            def body(carry, lp, stage=stage):
                xx, at = carry
                fn = functools.partial(_apply_block, stage=stage, cfg=cfg,
                                       positions=positions, enc_out=enc_out)
                if cfg.remat:
                    fn = jax.checkpoint(fn)
                xx, aux = fn(lp, xx)
                return (xx, at + aux), None
            (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), st_params)
    return x, aux_total


def embed_inputs(params, cfg: ModelConfig, batch: Dict[str, Array]) -> Array:
    """Token embedding + modality-stub injection."""
    if cfg.frontend == "audio_stub":
        # whisper encoder input: precomputed frame embeddings (conv stub)
        frames = batch["frames"].astype(cfg.dtype)
        pos = layers.sinusoidal_positions(frames.shape[1], cfg.d_model
                                          ).astype(cfg.dtype)
        return frames + pos[None]
    x = layers.embed_lookup(params["embed"], batch["tokens"]
                            ).astype(cfg.dtype)
    if cfg.frontend == "vision_stub" and "vision_embeds" in batch:
        ve = batch["vision_embeds"].astype(cfg.dtype)
        npatch = ve.shape[1]
        x = jnp.concatenate([ve, x[:, npatch:]], axis=1)
    return x


def forward(params, cfg: ModelConfig, batch: Dict[str, Array]
            ) -> Tuple[Array, Array]:
    """Full forward -> (logits [B,S,V], aux loss scalar)."""
    if cfg.family == "encdec":
        return _forward_encdec(params, cfg, batch)
    x = embed_inputs(params, cfg, batch)
    positions = jnp.arange(x.shape[1])
    stages = stages_for(cfg)
    x, aux = _run_stages(params["stages"], stages, x, cfg, positions)
    x = layers.NORM_APPLY[cfg.norm](params["final_norm"], x)
    table = params.get("unembed", params["embed"])
    logits = layers.unembed(x, table.astype(cfg.dtype))
    if cfg.logits_softcap:
        c = cfg.logits_softcap
        logits = c * jnp.tanh(logits / c)
    return logits, aux


def encode(params, cfg: ModelConfig, batch: Dict[str, Array]) -> Array:
    x = embed_inputs(params, cfg, batch)
    positions = jnp.arange(x.shape[1])
    x, _ = _run_stages(params["enc_stages"], stages_for(cfg, encoder=True),
                       x, cfg, positions)
    return layers.NORM_APPLY[cfg.norm](params["enc_final_norm"], x)


def _forward_encdec(params, cfg: ModelConfig, batch):
    enc_out = encode(params, cfg, batch)
    tok = batch["tokens"]
    x = layers.embed_lookup(params["embed"], tok).astype(cfg.dtype)
    x = x + params["dec_pos"][:tok.shape[1]].astype(cfg.dtype)[None]
    positions = jnp.arange(tok.shape[1])
    x, aux = _run_stages(params["stages"], stages_for(cfg), x, cfg,
                         positions, enc_out=enc_out)
    x = layers.NORM_APPLY[cfg.norm](params["final_norm"], x)
    logits = layers.unembed(x, params["embed"].astype(cfg.dtype))
    return logits, aux


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, Array]
            ) -> Tuple[Array, Dict[str, Array]]:
    """Next-token cross entropy (labels provided by the data pipeline).

    ce_impl="gather": straightforward log_softmax + take_along_axis. Under a
    vocab-sharded unembedding this makes XLA move the full f32 logits across
    the model axis (measured 39 GiB/device on qwen2-72b - §Perf).
    ce_impl="onehot": sharded-safe CE - logsumexp and the label logit are
    both vocab-local reductions followed by tiny [B,S] all-reduces.
    """
    logits, aux = forward(params, cfg, batch)
    labels = batch["labels"]
    lf = logits.astype(jnp.float32)
    if cfg.ce_impl == "onehot":
        m = jax.lax.stop_gradient(lf.max(axis=-1, keepdims=True))
        shifted = lf - m
        lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
        onehot = (jax.lax.broadcasted_iota(
            jnp.int32, lf.shape, lf.ndim - 1) == labels[..., None])
        label_logit = jnp.sum(jnp.where(onehot, shifted, 0.0), axis=-1)
        ll = label_logit - lse
    else:
        logp = jax.nn.log_softmax(lf, axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask", jnp.ones_like(ll))
    ce = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    total = ce + aux
    return total, {"ce": ce, "aux": aux}


def count_params(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# serving deployment: freeze KAN-FFN subtrees into integer artifacts
# ---------------------------------------------------------------------------

def deploy_kan(params, cfg: ModelConfig):
    """Two-phase serving contract for KAN-FFN architectures: replace every
    ``p["kan"]`` subtree with a frozen ``kan.DeployedKAN`` artifact (int8
    codes + scales + SH-LUT), built EXACTLY ONCE — the serving hot loop then
    contains no coefficient quantization (core.kan.trace_requantizes pins
    this). Stacked (lax.scan) stages are deployed under vmap so the artifact
    keeps the leading layer axis. Idempotent; returns ``params`` unchanged
    (same object) when the model has no KAN layers or is already deployed.
    """
    if not any(sp.ffn == "kan" for sp in cfg.layer_specs()):
        return params
    spec = cfg.kan_spec
    changed = False
    new_stages = []
    n_blocks = 0  # chip-unique uid per KAN block: the cim_tiled backend
    #               draws per-(layer, tile) process variation from it, so
    #               no two physical FFN blocks share a variation draw
    for st_params, stage in zip(params["stages"], stages_for(cfg)):
        blk = dict(st_params)
        for i, sp in enumerate(stage.block):
            if sp.ffn != "kan":
                continue
            lp = dict(blk[f"l{i}"])
            if isinstance(lp["kan"], kan.DeployedKAN):
                n_blocks += stage.repeats
                continue
            if stage.repeats == 1:
                lp["kan"] = kan.deploy(lp["kan"], spec, chip_uid=n_blocks)
            else:
                uids = n_blocks + jnp.arange(stage.repeats,
                                             dtype=jnp.int32)
                lp["kan"] = jax.vmap(
                    lambda p, u: kan.deploy(p, spec, chip_uid=u))(
                        lp["kan"], uids)
            n_blocks += stage.repeats
            blk[f"l{i}"] = lp
            changed = True
        new_stages.append(blk)
    if not changed:
        return params
    return {**params, "stages": new_stages}
