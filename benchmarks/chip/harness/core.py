"""One run of one cell: find its files by name, drive it, print the line.

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
traffic mix. The configuration's ``file`` holds its sizes and names two
modules of its architecture: its plain reference, ``configs/<reference>.py``
(the mathematics, ``train_flops`` and the ``TINY`` sizes of the CPU
rehearsals), and its layout in the program, ``drivers/layouts/<layout>.py``.
The traffic mix is ``traffic/<traffic>.json`` and names the driver
(``drivers/<driver>.py``) that runs it with those parameters. Each per-layer
metric is read by ``metrics/<name>.py``. A later architecture, cell, mix or
metric is a new file and a new entry, with no edit to a file that is here.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

HERE = Path(__file__).resolve().parents[1]      # the benchmark's directory
CHECKOUT = HERE.parents[1]                      # the repo's root


def load_module(path: Path, name: Optional[str] = None):
    spec = importlib.util.spec_from_file_location(name or f"bench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list   # BENCHMARK.json entries this cell reports
    per_layer: list
    root: Path

    def driver(self):
        drivers = str(self.root / "drivers")
        if drivers not in sys.path:
            sys.path.insert(0, drivers)
        return load_module(self.root / "drivers" / f"{self.traffic['driver']}.py",
                           f"bench_driver_{self.traffic['driver']}")

    def reference(self):
        return load_module(self.root / "configs" / f"{self.config['reference']}.py",
                           f"bench_ref_{self.config['reference']}")

    def layout(self):
        return load_module(self.root / "drivers" / "layouts" / f"{self.config['layout']}.py",
                           f"bench_layout_{self.config['layout']}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, bench_file: Path = CHECKOUT / "BENCHMARK.json") -> Cell:
    return cell_from(json.loads(bench_file.read_text()), bench_file.parent, name)


def cell_from(bench: dict, root: Path, name: str) -> Cell:
    """Cell ``name`` of ``bench`` (BENCHMARK.json's content), whose paths
    are relative to ``root``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    w = cells[name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf_entry["file"]).read_text())
    bench_dir = (root / conf_entry["file"]).parents[1]
    traffic = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=w["chips"], config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=bench_dir)


class Run:
    """What a driver records: host spans, facts, end-to-end numbers and
    checks. Metric readers read it after the window."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_start: float, workdir: Optional[str] = None):
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.t_start = t_start
        self.workdir = workdir
        self.spans: list = []        # (name, t0, t1), host clock, in window
        self.facts: dict = {}
        self.e2e: dict = {}
        self.checks: list = []
        self.attempted = 0
        self.failed = 0
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.summary = None          # trace.Summary of a traced run
        self.devices: list = []
        self.memory_peak_bytes = 0
        self._in_window = False

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:" + name):
            yield
        if self._in_window:
            self.spans.append((name, t0, time.perf_counter()))

    def span_total(self, name: str) -> list:
        return [t1 - t0 for n, t0, t1 in self.spans if n == name]

    @contextlib.contextmanager
    def window(self):
        """The measured window: ends set-up; traced when ``--trace 1``."""
        import jax
        from harness import trace as tr
        tdir = tempfile.mkdtemp(prefix="trace_", dir=self.workdir) if self.trace else None
        if tdir:
            jax.profiler.start_trace(tdir)
        self.setup_s = time.perf_counter() - self.t_start
        self._in_window = True
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            yield
        self.window_s = time.perf_counter() - t0
        self._in_window = False
        if tdir:
            jax.profiler.stop_trace()
            self.summary = tr.reduce(tr.find_xplane(tdir))

    def read_memory(self):
        from harness.device import memory_peak_bytes
        self.memory_peak_bytes = memory_peak_bytes(self.devices)


def result_line(run: Run) -> dict:
    from harness.device import describe
    from harness.trace import breakdown
    cell = run.cell
    metrics = {}
    if not run.trace:
        for m in cell.end_to_end:
            v = run.setup_s if m["name"] == "setup_s" else run.e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            reader = load_module(cell.root / "metrics" / f"{m['name']}.py",
                                 "bench_metric_" + m["name"].replace(".", "_"))
            v = reader.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(describe(run.devices), memory_peak_bytes=run.memory_peak_bytes)
    out = {"correct": all(c.ok for c in run.checks) and bool(run.checks),
           "attempted": run.attempted, "failed": run.failed,
           "metrics": metrics, "device": device}
    if run.trace and run.summary is not None:
        device["busy_s"] = run.summary.busy_mean_s
        device["window_s"] = run.summary.window_s
        out["breakdown"] = breakdown(run.summary)
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in run.checks}
    return out


def execute(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
            allow_cpu: bool = False, driver=None) -> dict:
    """Run ``cell`` once and return its result line (not yet printed)."""
    from harness.device import require_chips
    devs = require_chips(cell.chips, allow_cpu=allow_cpu)
    with tempfile.TemporaryDirectory(prefix="bench_") as work:
        run = Run(cell, seed, seconds, trace, t_start, work)
        run.devices = devs
        (driver or cell.driver()).run(run)
    print(f"timing: setup {run.setup_s:.3f} s, window {run.window_s:.3f} s, "
          f"total {time.perf_counter() - t_start:.3f} s", file=sys.stderr)
    return result_line(run)


def print_result(line: dict) -> None:
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(line), flush=True)


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT / ".jax_cache")
