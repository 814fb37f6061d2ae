"""Arithmetic that more than one metric reader uses."""
from __future__ import annotations


def program_ms(ctx, program: str):
    """Device milliseconds per call of a compiled program in the traced
    window, or None where the trace holds no call of it."""
    if not ctx.trace:
        return None
    p = ctx.trace["programs"].get(program)
    if not p or p["calls"] < 1:
        return None
    return p["device_s"] / p["calls"] * 1e3


def scope_ms(ctx, program: str, scope: str):
    """Device milliseconds per call of ``program`` in ops under the model
    step's ``scope`` (one of ``trace_reduce.SCOPES``, or ``unscoped``),
    from each op's own time; None where the trace holds no scoped call."""
    if not ctx.trace:
        return None
    return ctx.trace.get("scope_ms", {}).get(program, {}).get(scope)


def named_ms(ctx, program: str, name: str):
    """Device milliseconds per call of ``program`` in ops whose name path
    holds the segment ``name`` (a ``jax.named_scope`` at any depth, such as
    ``kan_ffn`` inside ``ffn``); None where no op of a call does."""
    if not ctx.trace:
        return None
    return ctx.trace.get("by_name", {}).get(program, {}).get(name)


def decode_roofline(ctx):
    """Least time of the window's decode ticks, from their shapes, over
    their device time, in percent: max(FLOPs / peak, least bytes / HBM
    bandwidth) per tick, averaged, over device ms per decode call."""
    ms = program_ms(ctx, "decode")
    ticks = [t for t in ctx.window_ticks() if t.n_dec]
    if ms is None or not ticks:
        return None
    least = 0.0
    for t in ticks:
        flops, nbytes = ctx.counts.decode_tick(ctx.model, t.n_dec, t.ctx_sum)
        least += max(flops / ctx.peaks["bf16_flops"],
                     nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / len(ticks) / (ms * 1e-3)


def window_flops(ctx):
    """Model FLOPs, from shapes, of every prompt chunk and decode token
    run in the window; None where no tick ran."""
    ticks = ctx.window_ticks()
    if not ticks:
        return None
    flops = 0.0
    for t in ticks:
        if t.n_dec:
            flops += ctx.counts.decode_tick(ctx.model, t.n_dec, t.ctx_sum)[0]
        for start, length, last in t.chunks:
            flops += ctx.counts.prefill_chunk(ctx.model, start, length, last)
    return flops


def step_mfu(ctx):
    """The window's model FLOPs over its seconds times the chip's bf16
    peak, in percent."""
    flops = window_flops(ctx)
    if flops is None:
        return None
    return 100.0 * flops / (ctx.seconds * ctx.peaks["bf16_flops"])


def busy_mfu(ctx):
    """The window's model FLOPs over the seconds in which the trace shows
    the device busy, times the chip's bf16 peak, in percent: the share of
    the peak that the device's work reaches, whatever the offered load."""
    flops = window_flops(ctx)
    if flops is None or not ctx.trace or ctx.trace["busy_s"] <= 0:
        return None
    return 100.0 * flops / (ctx.trace["busy_s"] * ctx.peaks["bf16_flops"])
