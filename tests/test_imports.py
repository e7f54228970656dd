"""Every thunder_tpu module must import: orphaned or broken modules (e.g. a
stale package directory whose sources were deleted but whose bytecode
lingers) fail here instead of lurking until a user hits them."""

import importlib
import os
import pkgutil

import thunder_tpu


def _all_module_names():
    names = ["thunder_tpu"]
    for info in pkgutil.walk_packages(thunder_tpu.__path__, prefix="thunder_tpu."):
        if info.name.endswith(".__main__"):
            continue  # importing a __main__ runs its CLI
        names.append(info.name)
    return names


def test_every_module_imports():
    failures = []
    for name in _all_module_names():
        try:
            importlib.import_module(name)
        except Exception as e:  # noqa: BLE001 - collecting all failures
            failures.append(f"{name}: {type(e).__name__}: {e}")
    assert not failures, "unimportable modules:\n  " + "\n  ".join(failures)


def test_no_orphaned_bytecode():
    """A __pycache__ entry whose source module is gone means a deleted module
    still shadows the repo's history — delete the stale bytecode."""
    pkg_root = os.path.dirname(thunder_tpu.__file__)
    orphans = []
    for dirpath, dirnames, filenames in os.walk(pkg_root):
        if os.path.basename(dirpath) != "__pycache__":
            continue
        src_dir = os.path.dirname(dirpath)
        for fn in filenames:
            if not fn.endswith(".pyc"):
                continue
            mod = fn.split(".")[0]
            if not os.path.exists(os.path.join(src_dir, mod + ".py")):
                orphans.append(os.path.join(dirpath, fn))
    assert not orphans, f"bytecode without source: {orphans}"


def test_observe_package_exports():
    """The observe subsystem's public surface stays importable from the
    package root (the API the docs teach)."""
    from thunder_tpu import observe

    for attr in ("enable", "disable", "is_enabled", "snapshot", "explain",
                 "export_jsonl", "export_chrome_trace", "export_prometheus",
                 "span", "inc", "set_gauge", "event"):
        assert callable(getattr(observe, attr)), attr


def test_only_the_benchmark_reads_bench_environment_variables():
    """One yardstick: no Python file of the library or at the repo root
    reads a ``BENCH_*`` / ``SERVE_*`` environment variable — those were the
    pre-benchmark timing scripts' 36 knobs. ``benchmark/`` keeps its own."""
    import re

    repo = os.path.dirname(os.path.dirname(thunder_tpu.__file__))
    reads = re.compile(r"environ[^\n]*[\"'](?:BENCH|SERVE)_[A-Z0-9_]*[\"']"
                       r"|getenv\([\"'](?:BENCH|SERVE)_")
    paths = [os.path.join(repo, f) for f in os.listdir(repo) if f.endswith(".py")]
    for top in ("thunder_tpu", "examples"):
        for dirpath, _, files in os.walk(os.path.join(repo, top)):
            paths += [os.path.join(dirpath, f) for f in files if f.endswith(".py")]
    assert len(paths) > 50
    found = []
    for path in paths:
        with open(path) as f:
            found += [f"{os.path.relpath(path, repo)}: {m.group(0)}"
                      for m in reads.finditer(f.read())]
    assert not found, found
