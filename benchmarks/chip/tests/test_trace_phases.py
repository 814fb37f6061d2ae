"""The phase and scope reduction on small traces."""
import json
from pathlib import Path

import pytest

import trace_phases as tp

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def _trace():
    """One engine step (0-10 ms) of a recorded engine: a chunk, then a
    decode whose loop holds four scoped ops; the harness reads back after
    it. The device idles 0-0.7, 2.9-5.6 and 9.4-20 ms."""
    host = [["harness.window", 0, 20 * MS],
            ["engine.step", 0, 10 * MS],
            ["engine.admit", 0, 0.5 * MS],
            ["engine.prefill", 0.5 * MS, 4 * MS],
            ["engine.prefill_chunk", 0.6 * MS, 2.4 * MS],
            ["engine.readback", 3 * MS, 1.4 * MS],
            ["engine.inputs", 4.5 * MS, 1 * MS],
            ["engine.decode", 5.5 * MS, 0.5 * MS],
            ["engine.readback", 6 * MS, 3.5 * MS],
            ["engine.host", 9.5 * MS, 0.4 * MS],
            ["harness.readback", 10 * MS, 1 * MS]]
    w = "jit(decode_tick)/while/body/closed_call/"
    dev = {"name": "/device:TPU:0",
           "modules": [["jit_prefill_chunk4_first(1)", 0.7 * MS, 2.2 * MS],
                       ["jit_decode_tick(2)", 5.6 * MS, 3.8 * MS]],
           "ops": [["%fusion.1", 0.7 * MS, 2.2 * MS,
                    "jit(prefill_chunk4_first)/mixer/dot_general"],
                   ["%while", 5.6 * MS, 3.4 * MS, "jit(decode_tick)/while"],
                   ["%fusion.2", 5.7 * MS, 0.8 * MS, w + "mixer/mul"],
                   ["%fusion.3", 6.5 * MS, 0.5 * MS,
                    w + "cache_update/jit(_where)/select_n"],
                   ["%fusion.4", 7 * MS, 1.5 * MS, w + "ffn/dot_general"],
                   ["%fusion.5", 8.5 * MS, 0.5 * MS,
                    w + "mixer/paged_attention/reduce"],
                   ["%fusion.6", 9 * MS, 0.4 * MS,
                    "jit(decode_tick)/unembed/argmax"]]}
    return {"devices": [dev], "host": host}


def test_a_gap_goes_to_the_innermost_span_most_of_it_lies_under():
    spans = [["engine.step", 0, 10 * MS], ["engine.inputs", 1 * MS, 1 * MS],
             ["engine.readback", 2 * MS, 3 * MS]]
    assert tp.attribute(spans, 1.5 * MS, 4.5 * MS) == "engine.readback"
    assert tp.attribute(spans, 0.8 * MS, 2.4 * MS) == "engine.inputs"
    # most of it lies under no child: the parent
    assert tp.attribute(spans, 5 * MS, 9 * MS) == "engine.step"
    assert tp.attribute(spans, 9 * MS, 12 * MS) == "host.other"


def test_idle_is_split_by_innermost_span():
    red = tp.reduce(_trace())
    idle = {k: v * 1e3 for k, v in red["idle_by_span"].items()}
    want = {"engine.admit": 0.5, "engine.prefill": 0.2,
            "engine.prefill_chunk": 0.2, "engine.readback": 1.5,
            "engine.inputs": 1.0, "engine.decode": 0.1, "engine.host": 0.4,
            "engine.step": 0.1, "harness.readback": 1.0, "host.other": 9.0}
    assert idle == pytest.approx(want)
    assert red["steps"] == 1
    assert red["host_gap_ms"] == pytest.approx(3.9)
    assert red["phase_share"] == pytest.approx(3.9 / 4.0)
    assert [g[0] for g in red["idle_gaps"]] == [
        "host.other", "engine.readback", "engine.admit"]
    assert red["phase_share_gaps"] == pytest.approx(1.0)


def test_scopes_take_each_ops_own_time():
    sc = tp.reduce(_trace())["scope_ms"]
    dec = sc["decode"]
    assert dec["calls"] == 1 and dec["device_ms"] == pytest.approx(3.8)
    assert {k: dec[k] for k in ("mixer", "cache_update", "ffn", "unembed",
                                "unscoped")} == pytest.approx(
        {"mixer": 1.3, "cache_update": 0.5, "ffn": 1.5, "unembed": 0.4,
         "unscoped": 0.1})          # the loop's own time, less its body
    assert sum(v for k, v in dec.items()
               if k not in ("calls", "device_ms")) == pytest.approx(3.8)
    assert sc["prefill_chunk"]["mixer"] == pytest.approx(2.2)


@pytest.mark.parametrize("path,scope", [
    ("jit(decode_tick)/while/body/closed_call/mixer/jit(silu)/mul", "mixer"),
    ("jit(decode_tick)/while/body/cache_update/dynamic_update_slice",
     "cache_update"),
    ("jit(x)/ffn/kan_ffn/dot_general", "ffn"),
    ("jit(decode_tick)/unembed/argmax", "unembed"),
    ("jit(decode_tick)/while", "unscoped"),
    ("jit(decode_tick)/mixers/add", "unscoped"),
    ("", "unscoped")])
def test_scope_of_an_op_path(path, scope):
    assert tp.scope_of(path) == scope


def test_op_path_reads_the_stat_that_carries_one():
    assert tp.op_path({"hlo_category": "x", "tf_op": "jit(f)/mixer/add",
                       "long_name": "%add = f32[2] add(...)"}) == \
        "jit(f)/mixer/add"
    assert tp.op_path({"source": "jit(f)/ffn/dot"}) == "jit(f)/ffn/dot"
    assert tp.op_path({"hlo_category": "x"}) == ""


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n >> 7 else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message of (field number, int | str | bytes) pairs."""
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return out


def test_op_stats_reads_event_metadata_of_device_planes():
    op = "%fusion.3 = f32[2] fusion(...)"
    meta = _msg((1, 5), (2, op),
                (5, _msg((1, 11), (7, 12))),        # tf_op by reference
                (5, _msg((1, 13), (5, "loop fusion"))),
                (5, _msg((1, 14), (3, 99))))        # a number: left out
    stat = [_msg((1, k), (2, _msg((1, k), (2, name)))) for k, name in
            ((11, "tf_op"), (12, "jit(decode_tick)/mixer/add"),
             (13, "hlo_category"), (14, "flops"))]
    device = _msg((1, 7), (2, "/device:TPU:0"), (3, _msg((2, "XLA Ops"))),
                  (4, _msg((1, 5), (2, meta))), *[(5, s) for s in stat])
    host = _msg((1, 1), (2, "/host:CPU"), (4, _msg((1, 5), (2, meta))))
    space = _msg((1, device), (1, host), (4, "worker"))
    assert tp.op_stats(space) == {"/device:TPU:0": {op: {
        "tf_op": "jit(decode_tick)/mixer/add", "hlo_category": "loop fusion"}}}


def test_a_program_without_engine_spans_or_scopes_reads_none():
    """The parent program's trace (only the harness's spans, no op paths):
    every new number is None or empty."""
    ev = json.loads((DATA / "trace_excerpt.json").read_text())
    for dev in ev["devices"]:
        dev["ops"] = [op + [""] for op in dev["ops"]]
    red = tp.reduce(ev)
    assert red["host_gap_ms"] is None and red["phase_share"] is None
    assert red["scope_ms"] == {}
    assert set(red["idle_by_span"]) <= {"engine.step", "harness.sleep",
                                        "harness.readback",
                                        "harness.generate", "host.other"}


def test_recorded_excerpt_with_engine_spans_and_scopes():
    """150 ms of a traced ``mistral_nemo_12b_4l.offline`` run on a TPU v5e
    with the engine's spans and each op's scope path: two engine steps,
    each a 64-token chunk and a 32-slot decode."""
    ev = json.loads((DATA / "trace_excerpt_spans.json").read_text())
    red = tp.reduce(ev)
    assert red["steps"] == 2
    assert 3.0 < red["host_gap_ms"] < 6.0
    assert red["phase_share"] > 0.9 and red["phase_share_gaps"] > 0.9
    dec = red["scope_ms"]["decode"]
    assert dec["calls"] == 2
    assert {"mixer", "cache_update", "ffn", "unembed"} <= set(dec)
    parts = sum(v for k, v in dec.items() if k not in ("calls", "device_ms"))
    assert parts == pytest.approx(dec["device_ms"], rel=0.01)


def test_run_reduces_a_traced_run_with_phases_and_scopes(tmp_path,
                                                         monkeypatch):
    """``run.reduce_trace`` on the recorded excerpt: the trace's programs and
    busy time as before, and beside them ``host_gap_ms``, the scope
    partition of a decode call and its device ms by name; idle gaps named
    by engine phase and op labels by scope; the trace is deleted."""
    import run
    ev = json.loads((DATA / "trace_excerpt_spans.json").read_text())
    trace_dir = tmp_path / "trace"
    (trace_dir / "plugins").mkdir(parents=True)
    (trace_dir / "plugins" / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(run, "TRACE_DIR", trace_dir)
    monkeypatch.setattr(tp, "load", lambda path: ev)
    red = run.reduce_trace(ev["ticks"])
    assert not trace_dir.exists()
    assert red["programs"]["decode"]["calls"] >= 2
    assert 3.0 < red["host_gap_ms"] < 6.0
    dec = red["scope_ms"]["decode"]
    assert set(dec) == {"calls", "device_ms", *tp.SCOPES, "unscoped"}
    parts = sum(dec[k] for k in (*tp.SCOPES, "unscoped"))
    assert parts == pytest.approx(dec["device_ms"], rel=0.01)
    names = red["by_name"]["decode"]
    for scope in tp.SCOPES:
        assert names.get(scope, 0.0) == pytest.approx(dec[scope])
    assert 0 < names["paged_attention"] < names["mixer"]
    gaps = {g[0] for g in red["breakdown"]["idle_gaps"]}
    assert gaps & {"engine.readback", "engine.inputs", "engine.prefill_chunk"}
    labels = [k for k, _ in red["breakdown"]["device_ops"]]
    assert all(k.split(":")[0].split("/")[-1] in (*tp.SCOPES, "unscoped")
               for k in labels), labels
    assert {"decode/unscoped", "decode/unembed"} <= {
        k.split(":")[0] for k in labels}, labels


def test_a_metric_reads_a_nested_scope_by_name(tmp_path):
    """A metric that a later PR adds as a file of its own reads a scope
    outside ``SCOPES`` (``kan_ffn`` inside ``ffn``) through ``by_name``."""
    import importlib.util
    import harness as hz
    import run
    ev = _trace()
    ops = ev["devices"][0]["ops"]
    ops[4][3] = ops[4][3].replace("ffn/", "ffn/kan_ffn/")
    ctx = run.Ctx(trace=tp.reduce(ev))
    hz.load_module("metrics", "decode_ffn_ms")          # puts _shared on the path
    path = tmp_path / "decode_kan_ffn_ms.py"
    path.write_text("from _shared import named_ms\n\n\n"
                    "def read(ctx):\n"
                    "    return named_ms(ctx, 'decode', 'kan_ffn')\n")
    spec = importlib.util.spec_from_file_location("decode_kan_ffn_ms", path)
    metric = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(metric)
    assert metric.read(ctx) == pytest.approx(1.5)
    assert hz.load_module("metrics", "decode_ffn_ms").read(ctx) == \
        pytest.approx(1.5)
    assert tp.reduce(_trace())["by_name"]["decode"].get("kan_ffn") is None
    assert metric.read(run.Ctx(trace=None)) is None
