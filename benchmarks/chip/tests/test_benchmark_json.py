"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
has its file."""
import json
import re

import pytest

from harness import core
from tiny import bench

BENCH = json.loads((core.CHECKOUT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_shape_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1].startswith("benchmarks/chip/")
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
    assert len((core.CHECKOUT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("with_unlisted", [False, True])
def test_entries(with_unlisted):
    BENCH = bench() if with_unlisted else globals()["BENCH"]
    confs = {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmarks/chip/")
        assert json.loads((core.CHECKOUT / c["file"]).read_text())["name"] == c["name"]
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and _line(w["why"])
        assert w["config"] in confs and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert (core.HERE / "traffic" / f"{w['traffic']}.json").exists()
    assert {w["config"] for w in BENCH["workloads"]} == confs
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and _line(m["layer"])
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert (core.HERE / "metrics" / f"{m['name']}.py").exists()
        for w in m.get("workloads", []):
            cell = core.cell_from(BENCH, core.CHECKOUT, w)
            assert m["moves"] in {x["name"] for x in cell.end_to_end}
        layers.setdefault(m["layer"], m["layer"])
    for w in BENCH["workloads"]:
        cell = core.cell_from(BENCH, core.CHECKOUT, w["name"])
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert (core.HERE / "drivers" / f"{cell.traffic['driver']}.py").exists()
    names = [x["name"] for x in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
