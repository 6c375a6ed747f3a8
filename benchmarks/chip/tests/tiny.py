"""Cells of the benchmark cut to a tiny size, for rehearsals on the CPU: the
model to its reference's ``TINY`` sizes, the traffic to a few short rows.

The cells are BENCHMARK.json's and those of ``data/unlisted_cells.json``:
cells whose files are here but which are not yet measured on the chip (none,
when its lists are empty)."""
from __future__ import annotations

import copy
import json
import time
from pathlib import Path

from harness import core

UNLISTED = Path(__file__).resolve().parent / "data" / "unlisted_cells.json"


def bench() -> dict:
    """BENCHMARK.json with the unlisted cells' entries added."""
    b = json.loads((core.CHECKOUT / "BENCHMARK.json").read_text())
    extra = json.loads(UNLISTED.read_text())
    for key in ("configs", "workloads", "per_layer"):
        b[key] += extra[key]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + extra["also_reported_by"].get(m["name"], [])
    return b


def tiny_cell(name: str, bench_file=None, **traffic) -> core.Cell:
    cell = (core.find_cell(name, bench_file) if bench_file
            else core.cell_from(bench(), core.CHECKOUT, name))
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(cell.reference().TINY)
    cell.traffic = dict(cell.traffic, **traffic)
    small = {"batch": 2 * cell.chips, "seq": 32, "prompt": 16, "gen": 4, "requests": 2}
    for k, v in small.items():
        if k in cell.traffic:
            cell.traffic[k] = v
    if "ckpt_every" in cell.traffic and cell.traffic["ckpt_every"]:
        cell.traffic["ckpt_every"] = 2
    return cell


def cells() -> list:
    return [w["name"] for w in bench()["workloads"]]


def rehearse(cell: core.Cell, seed: int = 2**31 + 7, seconds: float = 0.5,
             trace: bool = False, driver=None) -> dict:
    return core.execute(cell, seed, seconds, trace, time.perf_counter(),
                        allow_cpu=True, driver=driver)
