"""Operations and bytes of a Mamba-2 (SSD) stack, from its sizes alone.

``m`` is the ``model`` block of ``configs/<config>.json``. Every weight is
counted at 2 bytes (the bf16 compute dtype), whatever dtype the program
stores it in: that is the least any implementation must read. The SSD
state is counted at f32, the dtype the serving cache holds it in.
"""
from __future__ import annotations

STATE_BYTES = 4          # SSD recurrent state, f32 in the cache
CONV_BYTES = 2           # conv ring buffer, bf16 in the cache
WEIGHT_BYTES = 2


def _sizes(m):
    d, n, p = m["d_model"], m["ssm_state"], m["ssm_head_dim"]
    di = m["ssm_expand"] * d
    h = di // p
    return d, di, n, p, h, m["ssm_conv_width"]


def layer_params(m) -> int:
    d, di, n, p, h, k = _sizes(m)
    proj = d * (2 * di + 2 * n + h) + di * d
    return proj + k * (di + 2 * n) + 3 * h + di + d


def weight_bytes(m) -> int:
    """The least bytes of the weights that one pass reads."""
    return WEIGHT_BYTES * param_count(m)


def param_count(m) -> int:
    return (m["n_layers"] * layer_params(m) + m["vocab"] * m["d_model"]
            + m["d_model"])


def _token_flops(m) -> float:
    """Per token and layer: projections, conv, and the recurrent SSD
    update (decay, outer product, add) plus its read-out."""
    d, di, n, p, h, k = _sizes(m)
    proj = 2 * (d * (2 * di + 2 * n + h) + di * d)
    return proj + 2 * k * (di + 2 * n) + 5 * h * p * n


def unembed_flops(m) -> float:
    return 2 * m["d_model"] * m["vocab"]


def decode_tick(m, n_tok: int, ctx_sum: int):
    """(FLOPs, least bytes) of one decode tick that produces ``n_tok``
    tokens. ``ctx_sum`` (sum of context lengths) does not matter here."""
    d, di, n, p, h, k = _sizes(m)
    flops = n_tok * (m["n_layers"] * _token_flops(m) + unembed_flops(m))
    state = (h * p * n * STATE_BYTES + (k - 1) * (di + 2 * n) * CONV_BYTES)
    nbytes = (weight_bytes(m)
              + 2 * n_tok * m["n_layers"] * state)
    return flops, nbytes


def prefill_chunk(m, start: int, length: int, last: bool) -> float:
    """FLOPs of one prompt chunk of ``length`` tokens at ``start``."""
    return (length * m["n_layers"] * _token_flops(m)
            + (unembed_flops(m) if last else 0.0))
