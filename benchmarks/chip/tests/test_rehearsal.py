"""Every cell of BENCHMARK.json, run end to end on the CPU at a tiny size
(the four-chip cell on four virtual devices), past the harness's look for a
chip: each reports what BENCHMARK.json says it reports, and is correct."""
import pytest

from harness import device
from tiny import cells, rehearse, tiny_cell


@pytest.fixture
def cpu_peaks(monkeypatch):
    # a made-up peak, so that a CPU run can read step_mfu; never a device number
    monkeypatch.setitem(device.PEAKS, "cpu", dict(device.PEAKS["TPU v5 lite"]))


@pytest.mark.parametrize("name", cells())
def test_cell_runs_and_is_correct(name):
    cell = tiny_cell(name)
    line = rehearse(cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["count"] == cell.chips
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("name", cells())
def test_traced_run_reports_per_layer_metrics(name, cpu_peaks):
    cell = tiny_cell(name)
    line = rehearse(cell, trace=True)
    assert line["correct"], line["checks"]
    got = set(line["metrics"])
    # the CPU trace has no device plane: the device-trace metrics stay out
    want = {m["name"] for m in cell.per_layer if m["source"] != "device_trace"}
    assert want <= got <= {m["name"] for m in cell.per_layer}
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
