"""A later PR adds a configuration, a traffic mix, a per-layer metric or a
whole architecture as new files and new BENCHMARK.json entries; the harness
finds them by name and runs them with no edit to a file that is already
there."""
import hashlib
import json
import shutil
from types import SimpleNamespace

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


ARCH2 = core.HERE / "tests" / "data" / "arch2"


def test_second_architecture_is_new_files_only(tmp_path):
    # a configuration, its reference (mathematics, FLOP count, tiny sizes)
    # and its layout in the program: leaves the Llama layout has not (an
    # untied head, query and key norms) and a plain MLP, whose count differs
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(core.HERE, bench_dir, ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(bench_dir)
    shutil.copy(ARCH2 / "qkn-test.json", bench_dir / "configs" / "qkn-test.json")
    shutil.copy(ARCH2 / "qkn_ref.py", bench_dir / "configs" / "qkn_ref.py")
    shutil.copy(ARCH2 / "qkn.py", bench_dir / "drivers" / "layouts" / "qkn.py")

    bench = json.loads((core.CHECKOUT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "qkn-test", "source": "test",
                             "file": "benchmarks/chip/configs/qkn-test.json",
                             "reduced": [], "why": "second architecture"})
    cells = {"qkn.train-ckpt": "train-ckpt", "qkn.serve-cold": "serve-cold"}
    for name, traffic in cells.items():
        bench["workloads"].append({"name": name, "config": "qkn-test", "traffic": traffic,
                                   "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("train_tokens_per_s", "ckpt_commit_s"):
            m["workloads"].append("qkn.train-ckpt")
        elif m["name"] == "ready_s":
            m["workloads"].append("qkn.serve-cold")
    for m in bench["per_layer"]:
        if m["name"] == "step_mfu":
            m["workloads"].append("qkn.train-ckpt")
    bench_file = tmp_path / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench))

    for name in cells:
        cell = tiny_cell(name, bench_file)
        assert cell.reference().__file__.startswith(str(bench_dir / "configs"))
        # a few requests: at the tiny width the untied head's logits lie
        # close, and a served token that rounding put on a near tie reads a
        # gap of about 2e-3, too much for one request's 8 tokens alone
        line = rehearse(cell, seconds=3)
        assert line["correct"], (name, line["checks"])
        assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}

    cell = core.find_cell("qkn.train-ckpt", bench_file)
    m, ref = cell.config["model"], cell.reference()
    llama = core.find_cell("smollm-135m.train-ckpt").reference()
    assert ref.train_flops(m, 8, 2048) < llama.train_flops(m, 8, 2048)
    reader = core.load_module(bench_dir / "metrics" / "step_mfu.py", "bench_metric_step_mfu")
    run = SimpleNamespace(cell=cell, window_s=1.0,
                          devices=[SimpleNamespace(device_kind="TPU v5 lite")],
                          facts={"steps": 1, "model": m, "batch": 8, "seq": 2048})
    assert reader.read(run) == 100 * ref.train_flops(m, 8, 2048) / 197e12

    after = _digests(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before
