"""Operations and bytes of a decoder of GQA attention and gated-MLP layers,
from its sizes alone.

``m`` is the ``model`` block of ``configs/<config>.json``. Every weight is
counted at 2 bytes (the bf16 compute dtype), whatever dtype the program
stores it in: that is the least any implementation must read. K and V are
counted at 2 bytes, the dtype of the serving cache.
"""
from __future__ import annotations

KV_BYTES = 2
WEIGHT_BYTES = 2


def layer_params(m) -> int:
    d, hd = m["d_model"], m["head_dim"]
    attn = d * hd * (2 * m["n_heads"] + 2 * m["n_kv_heads"])
    return attn + 3 * d * m["d_ff"] + 2 * d


def weight_bytes(m) -> int:
    """The least bytes of the weights that one pass reads."""
    return WEIGHT_BYTES * param_count(m)


def param_count(m) -> int:
    embed = m["vocab"] * m["d_model"] * (1 if m["tie_embeddings"] else 2)
    return m["n_layers"] * layer_params(m) + embed + m["d_model"]


def _attn_flops(m, keys: int) -> float:
    """QK^T and PV for one query over ``keys`` keys, one layer."""
    return 4 * m["n_heads"] * m["head_dim"] * keys


def unembed_flops(m) -> float:
    return 2 * m["d_model"] * m["vocab"]


def _matmul_flops(m) -> float:
    return 2 * (layer_params(m) - 2 * m["d_model"])


def decode_tick(m, n_tok: int, ctx_sum: int):
    """(FLOPs, least bytes) of one decode tick that produces ``n_tok``
    tokens whose attention spans ``ctx_sum`` keys in all (the new token
    included)."""
    L = m["n_layers"]
    flops = (n_tok * (L * _matmul_flops(m) + unembed_flops(m))
             + L * _attn_flops(m, ctx_sum))
    kv_token = 2 * m["n_kv_heads"] * m["head_dim"] * KV_BYTES * L
    nbytes = weight_bytes(m) + kv_token * ctx_sum
    return flops, nbytes


def prefill_chunk(m, start: int, length: int, last: bool) -> float:
    """FLOPs of one causal prompt chunk of ``length`` tokens at ``start``."""
    L = m["n_layers"]
    keys = length * start + length * (length + 1) // 2
    return (length * L * _matmul_flops(m) + L * _attn_flops(m, keys)
            + (unembed_flops(m) if last else 0.0))
