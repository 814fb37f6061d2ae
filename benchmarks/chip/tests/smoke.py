"""Cells at the program's SMOKE sizes, small enough for the CPU."""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness as hz  # noqa: E402

SMOKE_MODELS = {
    "mamba2_1p3b": {"n_layers": 3, "d_model": 64, "vocab": 512,
                    "ssm_state": 16, "ssm_head_dim": 16, "ssm_chunk": 16,
                    "dtype": "float32"},
    "mistral_nemo_12b_4l": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                            "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                            "vocab": 512, "dtype": "float32"},
}

SMOKE_LENGTHS = {
    "chat_burst": {"prompt": {"median": 24, "min": 8, "max": 64, "round": 8},
                   "max_new": {"median": 6, "min": 3, "max": 12},
                   "engine": {"n_slots": 4, "max_len": 80}},
    "sessions": {"system_prompt": 32,
                 "user": {"median": 12, "min": 8, "max": 24, "round": 8},
                 "max_new": {"median": 8, "min": 8, "max": 16, "round": 8},
                 "engine": {"n_slots": 4, "max_len": 200}},
    "long_prompt": {"prompt": {"min": 32, "max": 128, "round": 8},
                    "max_new": {"min": 3, "max": 8},
                    "engine": {"n_slots": 4, "max_len": 144}},
    "offline": {"prompt": {"median": 24, "min": 8, "max": 48, "round": 8},
                "max_new": {"median": 8, "min": 4, "max": 16},
                "engine": {"n_slots": 4, "max_len": 72}},
}


def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and k in out else v
    return out


def smoke_cell(name: str, page_size: int = 8) -> hz.Cell:
    """The named cell of BENCHMARK.json at SMOKE sizes."""
    cell = hz.load_cell(name)
    conf = copy.deepcopy(cell.conf)
    conf["smoke"] = True
    conf["overrides"] = {}
    conf["model"].update(SMOKE_MODELS[conf["name"]])
    conf["engine"] = {"page_size": page_size}
    mix = _merge(cell.mix, SMOKE_LENGTHS[cell.entry["traffic"]])
    if "rate_per_s" in mix["arrivals"]:
        mix["arrivals"]["rate_per_s"] = 1.0
    return hz.Cell(cell.name, cell.entry, conf, mix, cell.bench)


def smoke_json(cell: hz.Cell) -> str:
    return json.dumps({"conf": cell.conf, "mix": cell.mix})
