"""A later PR adds a configuration, a traffic mix and a per-layer metric as
new files and new BENCHMARK.json entries; the harness finds them by name and
runs them with no edit to a file that is already there."""
import hashlib
import json
import shutil

from harness import core
from tiny import rehearse, tiny_cell


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file() and "__pycache__" not in p.parts}


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(core.HERE, bench_dir, ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(bench_dir)

    conf = json.loads((bench_dir / "configs" / "smollm-135m-train.json").read_text())
    conf["name"] = "extra-train"
    (bench_dir / "configs" / "extra-train.json").write_text(json.dumps(conf))
    (bench_dir / "traffic" / "train-plain.json").write_text(json.dumps(
        {"driver": "train", "batch": 8, "seq": 2048, "check_steps": 3,
         "sync": "xla", "ckpt_every": 0}))
    (bench_dir / "metrics" / "steps_done.py").write_text(
        "def read(run):\n    return run.facts.get('steps')\n")

    bench = json.loads((core.CHECKOUT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "extra-train", "source": conf["source"],
                             "file": "benchmarks/chip/configs/extra-train.json",
                             "reduced": [], "why": "discovery test"})
    bench["workloads"].append({"name": "extra.train-plain", "config": "extra-train",
                               "traffic": "train-plain", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("extra.train-plain")
    bench["per_layer"].append({"name": "steps_done", "unit": "steps", "better": "higher",
                               "source": "program_counter", "layer": "step",
                               "moves": "train_tokens_per_s",
                               "workloads": ["extra.train-plain"]})
    bench_file = tmp_path / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench))

    cell = tiny_cell("extra.train-plain", bench_file)
    assert cell.root == bench_dir
    line = rehearse(cell)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    traced = rehearse(cell, trace=True)
    assert traced["metrics"]["steps_done"]["value"] == traced["attempted"] > 0
    assert "ckpt_leaves_differ" not in line["checks"]

    after = _digests(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before
