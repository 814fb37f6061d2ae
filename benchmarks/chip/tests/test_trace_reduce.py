"""The trace reduction on small traces."""
import json
from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def _trace():
    """Ops 0-2 ms, 1-3 ms (overlapping), 5-6 ms and 8-9 ms inside a 0-10 ms
    window; three engine steps, the second running a chunk then a
    decode; the host sleeps and reads back in between."""
    dev = {"name": "/device:TPU:0",
           "ops": [["fusion.1", 0, 2 * MS], ["fusion.2", 1 * MS, 2 * MS],
                   ["copy.3", 5 * MS, 1 * MS], ["fusion.1", 8 * MS, 1 * MS],
                   ["fusion.1", 11 * MS, 1 * MS]],
           "modules": [["jit__unknown(7)", 0, 3 * MS],
                       ["jit__unknown(3)", 5 * MS, 0.6 * MS],
                       ["jit_broadcast_in_dim(5)", 5.6 * MS, 0.01 * MS],
                       ["jit__unknown(7)", 5.61 * MS, 0.39 * MS],
                       ["jit__unknown(7)", 8 * MS, 1 * MS]]}
    host = [["harness.window", 0, 10 * MS],
            ["engine.step", 0, 3.2 * MS], ["harness.sleep", 3.2 * MS, 1.6 * MS],
            ["engine.step", 4.8 * MS, 1.4 * MS],
            ["harness.readback", 6.2 * MS, 1.5 * MS],
            ["engine.step", 7.7 * MS, 1.5 * MS]]
    ticks = [(0.0, 3.2e-3, 0, True), (4.8e-3, 6.2e-3, 1, True),
             (7.7e-3, 9.2e-3, 0, True)]
    return {"devices": [dev], "host": host}, ticks


def test_busy_is_the_union_of_op_intervals():
    red = tr.reduce(*_trace())
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.005)        # 0-3, 5-6, 8-9
    assert red["idle_share"] == pytest.approx(0.5)


def test_device_time_per_program_by_role():
    progs = tr.reduce(*_trace())["programs"]
    assert progs["decode"]["calls"] == 3
    assert progs["decode"]["device_s"] == pytest.approx(0.00439)
    assert progs["prefill_chunk"]["calls"] == 1
    assert progs["prefill_chunk"]["device_s"] == pytest.approx(0.0006)
    assert progs["broadcast_in_dim"]["calls"] == 1
    assert tr.program_name("jit__decode_fn(12)") == "_decode_fn"


def test_without_ticks_programs_keep_their_names():
    progs = tr.reduce(_trace()[0])["programs"]
    assert progs["_unknown"]["calls"] == 4


def test_op_labels_drop_layouts():
    assert tr.op_label("%convert.16 = bf16[48,2048]{1,0:T(8,128)(2,1)} "
                       "convert(f32[48,2048]{1,0} %p)") == \
        "%convert.16 = bf16[48,2048] convert"
    assert tr.op_label("%while = (s32[]{:T(128)}, f32[4]{0}) while((s32[]) "
                       "%t), condition=%c") == "%while = (s32[], f32[4]) while"


def test_idle_gaps_go_to_the_host_span_covering_them():
    red = tr.reduce(*_trace())
    gaps = red["breakdown"]["idle_gaps"]
    # 3-5 ms: sleep covers 3.2-4.8; 6-8 ms: readback 6.2-7.7; 9-10: step
    assert sorted(g[0] for g in gaps[:2]) == ["harness.readback",
                                              "harness.sleep"]
    assert [g[1] for g in gaps] == pytest.approx([0.002, 0.002, 0.001])
    assert red["idle_by_host"]["engine.step"] == pytest.approx(0.001)
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["decode: fusion.1"] == pytest.approx(0.003)   # not 11-12 ms


def test_no_device_no_reduction():
    assert tr.reduce({"devices": [], "host": []}) is None


def test_recorded_chip_trace():
    """130 ms of a traced `mamba2_1p3b.chat_burst` window on a v5e
    (tests/record_trace.py), three decode ticks: the numbers the reduction
    gives, against the same numbers worked out here another way."""
    ev = json.loads((DATA / "trace_excerpt.json").read_text())
    red = tr.reduce(ev, ev["ticks"])
    _, lo, span = [h for h in ev["host"] if h[0] == tr.WINDOW_SPAN][0]
    hi = lo + span
    covered, end = 0.0, lo
    for _, s, d in sorted(ev["devices"][0]["ops"], key=lambda e: e[1]):
        a, b = max(s, end), min(s + d, hi)
        if b > a:
            covered += b - a
            end = b
    assert red["window_s"] == pytest.approx(0.130)
    assert red["busy_s"] == pytest.approx(covered * 1e-9)
    # every tick decoded; one more decode starts before the excerpt ends
    assert all(t[3] for t in ev["ticks"])
    mods = [m for m in ev["devices"][0]["modules"]
            if m[1] >= lo and m[2] > 1e6]
    assert len(mods) == len(ev["ticks"]) + 1
    assert red["programs"]["decode"]["calls"] == len(mods)
    assert red["programs"]["decode"]["device_s"] == pytest.approx(
        sum(min(m[1] + m[2], hi) - m[1] for m in mods) * 1e-9)
    assert {g[0] for g in red["breakdown"]["idle_gaps"]} <= {
        "engine.step", "harness.readback", "harness.generate",
        "harness.submit", "harness.sleep", "host.other"}
