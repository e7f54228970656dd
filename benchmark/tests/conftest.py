"""Run by hand: ``python -m pytest benchmark/tests -q`` (tier-1 collects
``tests/`` only). Every test here runs on the CPU."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


def bench(*argv, cwd=ROOT, script=os.path.join(BENCH, "run.py"), env=None):
    """One run of the command in a process of its own, held to the CPU."""
    e = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    e.pop("XLA_FLAGS", None)
    e.update(env or {})
    return subprocess.run([sys.executable, script, *argv], cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=900)


@pytest.fixture(scope="session")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture
def ep4_checkout(tmp_path, manifest):
    """A checkout in which the four-chip expert-parallel cell (built and
    rehearsed in PR 24, not yet proved on the chip: PERF.md Open questions
    row 1) is in the manifest: the benchmark's files copied, and the entries
    of ``ep4_cell.json`` added, as the PR that proves it will add them."""
    import shutil

    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        "out", "__pycache__", ".pytest_cache"))
    with open(os.path.join(HERE, "ep4_cell.json")) as f:
        ep4 = json.load(f)
    m = json.loads(json.dumps(manifest))
    name = ep4["workload"]["name"]
    m["configs"].append(ep4["config"])
    m["workloads"].append(ep4["workload"])
    for e in m["end_to_end"]:
        if e["name"] in ep4["end_to_end_workloads"]:
            e["workloads"].append(name)
    for e in m["per_layer"]:
        if e["name"] in ep4["per_layer_workloads"]:
            e["workloads"].append(name)
    m["per_layer"] += ep4["per_layer_new"]
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root
