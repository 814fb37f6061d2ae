"""Metric readers on a hand-made context."""
import pytest

import harness as hz
import run
from harness import Tick

PEAK = 197e12


def _ctx(trace):
    model = hz.load_json("configs", "mamba2_1p3b.json")["model"]
    ticks = [Tick(1.0, 1.04, 32, 32, 32 * 300, []),
             Tick(1.04, 1.1, 33, 32, 32 * 301, [(0, 256, True)]),
             Tick(9.0, 9.1, 32, 32, 0, [])]            # after the window
    return run.Ctx(ticks=ticks, w0=0.5, w1=2.0, seconds=1.5, trace=trace,
                   model=model, peaks={"bf16_flops": PEAK},
                   counts=hz.load_module("counts", "ssm_mamba2"))


def _flops(ctx):
    c, m = ctx.counts, ctx.model
    return (c.decode_tick(m, 32, 32 * 300)[0]
            + c.decode_tick(m, 32, 32 * 301)[0]
            + c.prefill_chunk(m, 0, 256, True))


def test_busy_mfu_is_the_windows_flops_over_busy_device_time():
    mfu = hz.load_module("metrics", "busy_mfu.lat")
    ctx = _ctx({"busy_s": 0.09})
    assert abs(mfu.read(ctx) - 100 * _flops(ctx) / (0.09 * PEAK)) < 1e-9
    assert mfu.read(_ctx(None)) is None


def test_step_mfu_is_the_windows_flops_over_its_seconds():
    mfu = hz.load_module("metrics", "step_mfu")
    ctx = _ctx(None)
    assert abs(mfu.read(ctx) - 100 * _flops(ctx) / (1.5 * PEAK)) < 1e-9


def test_every_metric_of_the_benchmark_has_a_reader():
    bench = hz.load_cell("mamba2_1p3b.chat_burst").bench
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(hz.load_module("metrics", m["name"]).read), m


@pytest.mark.parametrize("name", [
    "host_gap_ms", "decode_mixer_ms", "decode_ffn_ms",
    "decode_cache_update_ms", "decode_unscoped_ms"])
def test_phase_and_scope_metrics_read_nothing_without_a_trace(name):
    assert hz.load_module("metrics", name).read(_ctx(None)) is None
