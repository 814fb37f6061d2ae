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
import smoke
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
        name, capsys, cpu_as_chip, monkeypatch):
    make_engine, recorders = hz.make_engine, []

    def spy(cell, params, cfg, recorder=None):
        recorders.append(recorder)
        return make_engine(cell, params, cfg, recorder)
    monkeypatch.setattr(hz, "make_engine", spy)
    out = run.run_cell(smoke_cell(name), 2**31 + 3, 2.0, False)
    assert recorders == [None]      # untraced: the engine as users run it
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


@pytest.mark.parametrize("key,path", [
    ("smoke_model", "configs/mistral_nemo_12b_4l.json"),
    ("smoke", "traffic/offline.json")])
def test_a_file_without_its_smoke_block_is_an_error_naming_it(
        key, path, monkeypatch):
    cell = hz.load_cell("mistral_nemo_12b_4l.offline")
    part = cell.conf if key == "smoke_model" else cell.mix
    del part[key]
    monkeypatch.setattr(hz, "load_cell", lambda name: cell)
    with pytest.raises(KeyError, match=path):
        smoke.smoke_cell(cell.name)


def test_a_new_configuration_and_cell_join_as_files_and_entries(tmp_path):
    """A copy of the benchmark gains a configuration (a renamed copy of
    mistral_nemo_12b_4l's file) and a cell on the offline mix, as new
    files and new BENCHMARK.json entries alone: the copy's own reference
    and count tests take it as a case, and pass."""
    root = tmp_path / "checkout"
    bench = root / "benchmarks" / "chip"
    shutil.copytree(hz.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    conf = json.loads((bench / "configs/mistral_nemo_12b_4l.json").read_text())
    conf["name"] = "nemo_copy"
    (bench / "configs/nemo_copy.json").write_text(json.dumps(conf))
    spec = json.loads((hz.ROOT / "BENCHMARK.json").read_text())
    old = next(c for c in spec["configs"]
               if c["name"] == "mistral_nemo_12b_4l")
    spec["configs"].append(dict(old, name="nemo_copy",
                                file="benchmarks/chip/configs/nemo_copy.json"))
    spec["workloads"].append({"name": "nemo_copy.offline",
                              "config": "nemo_copy", "traffic": "offline",
                              "chips": 1, "why": "a copy"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(hz.ROOT / "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-k", "nemo_copy",
         str(bench / "tests/test_reference.py"),
         str(bench / "tests/test_counts.py")],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert "6 passed" in proc.stdout, proc.stdout[-1000:]
    assert not (hz.BENCH / "configs/nemo_copy.json").exists()
