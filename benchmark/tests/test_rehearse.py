"""Each driver end to end under ``--rehearse`` on the CPU: a tiny config,
interpret-mode kernels, the last line one JSON object with the contract's
keys, and nothing printed under a device metric's name."""

import json
import os

import pytest

from conftest import ROOT, bench

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def last_line(r):
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_every_cell_rehearses(manifest, tmp_path, trace):
    for cell in manifest["workloads"]:
        r = bench("--workload", cell["name"], "--seed", str(2**31 + 12345),
                  "--seconds", "2", "--trace", str(trace), "--rehearse",
                  "--out", str(tmp_path))
        line = last_line(r)
        assert KEYS <= set(line), cell["name"]
        assert list(line)[-1] == "compared"
        assert line["correct"] is True, (cell["name"], line["compared"])
        assert line["failed"] == 0 and line["attempted"] > 0
        assert line["device"]["platform"] == "cpu"
        # a CPU run prints nothing under a device metric's name
        assert line["metrics"] == {} and "busy_s" not in line["device"]
        names = set(line["rehearsal_metrics"])
        assert "setup_s" in names if trace == 0 else "setup_s" not in names
        for c in line["compared"].values():
            assert c["value"] is not None and c["limit"] is not None
        assert "correct: True" in r.stderr.splitlines()[-1]


def test_expert_parallel_cell_rehearses_on_four_devices(ep4_checkout, tmp_path):
    r = bench("--workload", "mixtral8x7b_train_ep4", "--seed", "17",
              "--seconds", "1", "--trace", "1", "--rehearse",
              "--out", str(tmp_path), cwd=str(ep4_checkout),
              script=str(ep4_checkout / "benchmark" / "run.py"))
    line = last_line(r)
    assert line["correct"] is True, line["compared"]
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                              "memory_peak_bytes": None}
    assert "step_ms_p50.train" in line["rehearsal_metrics"]
    assert "collective_exposed_ms_per_step.train" not in line["rehearsal_metrics"]


def test_train_rate_is_whole_groups_over_fence_to_fence(tmp_path):
    r = bench("--workload", "mistral7b_train", "--seed", "9", "--seconds", "2",
              "--trace", "0", "--rehearse", "--out", str(tmp_path))
    line = last_line(r)
    g = json.load(open(os.path.join(
        tmp_path, "mistral7b_train", "seed9_trace0", "groups.json")))
    steps = len(g["group_s"]) * g["group_steps"]
    assert line["attempted"] == steps
    assert abs(sum(g["group_s"]) - g["window_s"]) < 1e-9 * steps + 1e-6
    assert g["window_s"] >= 2.0            # closed on a fence past --seconds
    rate = steps * g["tokens_per_step"] / g["window_s"]
    assert line["rehearsal_metrics"]["train_tok_s"]["value"] == pytest.approx(rate)


def test_no_accelerator_no_result():
    r = bench("--workload", "mistral7b_train", "--seed", "1", "--seconds", "1",
              "--trace", "0")
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_bare_directory_fails(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program, no run."""
    import shutil

    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    r = bench("--workload", "mistral7b_train", "--seed", "1", "--seconds", "1",
              "--trace", "0", "--rehearse", cwd=str(tmp_path),
              script=str(tmp_path / "benchmark" / "run.py"),
              env={"PYTHONPATH": ""})
    assert r.returncode != 0 and r.stdout.strip() == ""
