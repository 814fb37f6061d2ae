"""The harness: refusals, a whole run at SMOKE size on the CPU, the
control and a planted fault."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import control
import harness as hz
import peaks
import run
from smoke import smoke_cell

RUN = str(hz.BENCH / "run.py")
CELLS = [w["name"] for w in json.loads(
    (hz.ROOT / "BENCHMARK.json").read_text())["workloads"]]
ARGS = ["--workload", "mamba2_1p3b.chat_burst", "--seed", "2147483999",
        "--seconds", "2", "--trace", "0"]


@pytest.fixture(autouse=True)
def short_drain(monkeypatch):
    monkeypatch.setattr(hz, "DRAIN_S", 30.0)


@pytest.fixture
def cpu_as_chip(monkeypatch):
    """Let a run in this process take the CPU for the chip, with the v5e
    peaks (the command line still refuses it)."""
    import jax

    def check_device(chips):
        return jax.devices(), peaks.peaks_for("TPU v5 lite")
    monkeypatch.setattr(run, "check_device", check_device)


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, RUN.replace(str(hz.ROOT), str(cwd))]
                          + ARGS, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def _no_result(proc):
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    return proc.returncode != 0 and not last.startswith("{")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_refuses_a_cpu_backend():
    proc = _cli(hz.ROOT)
    assert _no_result(proc), proc.stdout[-500:]
    assert "no TPU" in proc.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(hz.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(hz.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _cli(tmp_path)
    assert _no_result(proc), proc.stdout[-500:]


@pytest.mark.parametrize("name", CELLS)
def test_smoke_run_is_correct_and_compiles_nothing_in_the_window(
        name, capsys, cpu_as_chip):
    out = run.run_cell(smoke_cell(name), 2**31 + 3, 2.0, False)
    logged = [json.loads(line) for line in capsys.readouterr().out.splitlines()
              if line.startswith("{")]
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["checks"]["logit_gap"]["value"] <= 1e-4
    assert {m["name"] for m in smoke_cell(name).metrics("end_to_end")} == \
        set(out["metrics"])
    assert next(d for d in logged if "window_compiles" in d)[
        "window_compiles"] == 0


def test_a_token_altered_where_it_is_produced_fails(monkeypatch, cpu_as_chip):
    from repro.serve import engine as engine_lib
    good = engine_lib._decode_fn

    def altered(params, cache, tokens, index, pages, *, cfg):
        nxt, cache = good(params, cache, tokens, index, pages, cfg=cfg)
        return (nxt + 1) % cfg.vocab, cache

    monkeypatch.setattr(engine_lib, "_decode_fn", altered)
    out = run.run_cell(smoke_cell("mamba2_1p3b.chat_burst"), 5, 2.0, False)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_fp8_control_in_the_programs_place_is_not_correct(name, cpu_as_chip):
    """The control at SMOKE widths in bf16, judged by ``run.result`` with
    the cell's committed limit: the program's tokens come out correct, the
    fp8 control's in their place do not. mamba2 runs at its published
    depth of 48 layers, through which fp8's error grows past the limit."""
    import jax.numpy as jnp
    cell = smoke_cell(name)
    over = {"dtype": jnp.bfloat16}
    if cell.conf["name"] == "mamba2_1p3b":
        over["n_layers"] = 48
    cell.conf["model"].update(over, dtype="bfloat16")
    cell.conf["overrides"] = over
    r = control.readings(cell, 22, 2.0)
    assert r["program_correct"], r
    assert not r["control_correct"], r
    assert r["control_gap"] > r["limit"] > r["program_gap"]
