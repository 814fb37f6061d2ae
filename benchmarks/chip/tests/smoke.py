"""Cells at the program's SMOKE sizes, small enough for the CPU: each
configuration file states its own under ``smoke_model`` and each mix file
under ``smoke``, so a new cell brings its sizes with it. The run on the
chip reads neither."""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness as hz  # noqa: E402


def config_cells() -> list:
    """One cell per configuration of BENCHMARK.json: the first workload
    that names it."""
    bench = json.loads((hz.ROOT / "BENCHMARK.json").read_text())
    first = {}
    for w in bench["workloads"]:
        first.setdefault(w["config"], w["name"])
    return list(first.values())


def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and k in out else v
    return out


def _smoke_block(data: dict, key: str, *path: str) -> dict:
    if key not in data:
        raise KeyError(f"{hz.BENCH.joinpath(*path)} has no {key!r} block: "
                       "the sizes of its cells on the CPU")
    return data[key]


def smoke_cell(name: str, page_size: int = 8) -> hz.Cell:
    """The named cell of BENCHMARK.json at SMOKE sizes."""
    cell = hz.load_cell(name)
    conf = copy.deepcopy(cell.conf)
    conf["smoke"] = True
    conf["overrides"] = {}
    conf["model"].update(_smoke_block(
        conf, "smoke_model", "configs", cell.entry["config"] + ".json"))
    conf["engine"] = {"page_size": page_size}
    mix = _merge(cell.mix, _smoke_block(
        cell.mix, "smoke", "traffic", cell.entry["traffic"] + ".json"))
    if "rate_per_s" in mix["arrivals"]:
        mix["arrivals"]["rate_per_s"] = 1.0
    return hz.Cell(cell.name, cell.entry, conf, mix, cell.bench)


def smoke_json(cell: hz.Cell) -> str:
    return json.dumps({"conf": cell.conf, "mix": cell.mix})
