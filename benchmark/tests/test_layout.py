"""Everything is found by name: each cell resolves to its files, each metric
to a reader, every name keeps to the manifest's characters, and a new config,
traffic mix and metric are picked up as new files with no edit to an old one."""

import json
import os
import re
import shutil


import run as bench_run
from conftest import BENCH, bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_cells_resolve_by_name(manifest):
    for cell in manifest["workloads"]:
        files = bench_run.resolve(manifest, cell["name"])
        assert os.path.isfile(os.path.join(
            BENCH, "families", f"{files['conf']['family']}.py"))
        assert os.path.isfile(os.path.join(
            BENCH, "drivers", f"{files['traffic']['kind']}.py"))
        for key in ("source", "reduced", "assumed", "deployment"):
            assert key in files["conf"], (cell["name"], key)
        assert files["limits"], f"{cell['name']} has no limits file"
        assert bench_run.cell_metrics(manifest, cell["name"], "per_layer")
        e2e = {m["name"] for m in
               bench_run.cell_metrics(manifest, cell["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2


def test_metrics_resolve_to_readers(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        how = bench_run.load_json("metrics", f"{m['name']}.json")
        assert hasattr(bench_run.load_module("readers", how["reader"]), "read")
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells


def test_names_and_units(manifest):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    for dirpath, _, files in os.walk(BENCH):
        if any(p in dirpath for p in ("__pycache__", ".pytest_cache", os.sep + "out")):
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.-]+$", f), os.path.join(dirpath, f)


def test_new_files_are_picked_up(tmp_path, manifest):
    """A config, a traffic mix, a metric and a cell added as NEW files and
    entries run end to end; no file that was there is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__", ".pytest_cache"))
    b = root / "benchmark"
    conf = json.load(open(b / "configs" / "mistral-7b-v0.3-l2.json"))
    conf["rehearse"]["num_hidden_layers"] = 1
    (b / "configs" / "dummy-cfg.json").write_text(json.dumps(conf))
    traffic = json.load(open(b / "traffic" / "train_b4_s4096.json"))
    traffic["rehearse"]["seq"] = 16
    (b / "traffic" / "dummy_mix.json").write_text(json.dumps(traffic))
    (b / "metrics" / "dummy_step_mean.json").write_text(json.dumps(
        {"reader": "timings", "args": {"series": "step_ms", "stat": "mean"}}))
    (b / "limits" / "dummy_cell.json").write_text(
        (b / "limits" / "mistral7b_train.json").read_text())
    m = json.loads(json.dumps(manifest))
    m["configs"].append({"name": "dummy-cfg", "source": conf["source"],
                         "file": "benchmark/configs/dummy-cfg.json",
                         "reduced": ["num_hidden_layers"], "why": "test"})
    m["workloads"].append({"name": "dummy_cell", "config": "dummy-cfg",
                           "traffic": "dummy_mix", "chips": 1, "why": "test"})
    for e in m["end_to_end"]:
        if e["name"] == "train_tok_s":
            e["workloads"].append("dummy_cell")
    m["per_layer"].append({"name": "dummy_step_mean", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "entry", "moves": "train_tok_s",
                           "workloads": ["dummy_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    r = bench("--workload", "dummy_cell", "--seed", "5", "--seconds", "1",
              "--trace", "1", "--rehearse", cwd=str(root),
              script=str(b / "run.py"))
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert "dummy_step_mean" in line["rehearsal_metrics"]
    # metrics without a `workloads` key reach the new cell too
    assert "backend_compile_s" in line["rehearsal_metrics"]
