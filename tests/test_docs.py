"""The onboarding tutorial's code blocks run verbatim, top to bottom
(VERDICT r4 #9: a runnable zero-to-thunder_tpu path, reference parity with
the reference's notebooks/zero_to_thunder.ipynb — but executed in CI)."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC = os.path.join(REPO, "docs", "zero_to_thunder_tpu.md")
KERNELS_DOC = os.path.join(REPO, "KERNELS.md")


def test_tutorial_blocks_execute():
    with open(DOC) as f:
        text = f.read()
    blocks = re.findall(r"```python\n(.*?)```", text, re.DOTALL)
    assert len(blocks) >= 8, "tutorial lost its code blocks"
    ns: dict = {}
    src = "\n\n".join(blocks)
    exec(compile(src, DOC, "exec"), ns)  # noqa: S102 - the doc IS the test
    # the tutorial's own asserts ran; spot-check its final state
    assert ns["rep"]["total_in_bytes"] > 0


def test_runtime_metric_names_documented():
    """Every ``runtime.*`` metric name the code emits must appear in the
    docs' metrics reference table — the names are the ops contract
    (dashboards and alerts key on them), and silent drift breaks dashboards
    without breaking any test. Same spirit as tests/test_imports.py: the
    contract is enforced, not remembered."""
    import glob

    import thunder_tpu

    pkg_root = os.path.dirname(thunder_tpu.__file__)
    sources = glob.glob(os.path.join(pkg_root, "**", "*.py"), recursive=True)
    assert sources
    names: set = set()
    for path in sources:
        with open(path) as f:
            src = f.read()
        names |= set(re.findall(r"[\"'](runtime\.[a-z0-9_]+)[\"']", src))
    # the sentinel/retry/quarantine/supervisor metric families must all be
    # present (a refactor that stops emitting them should fail loudly here)
    for required in ("runtime.nonfinite_steps", "runtime.skipped_steps",
                     "runtime.rewinds", "runtime.bisections",
                     "runtime.grad_norm", "runtime.loss_ewma",
                     "runtime.retries", "runtime.fallbacks",
                     "runtime.quarantined_kernels"):
        assert required in names, f"code no longer emits {required}"
    with open(DOC) as f:
        doc = f.read()
    missing = [n for n in sorted(names) if f"`{n}`" not in doc]
    assert not missing, (
        "runtime metrics emitted by the code but missing from the docs "
        f"metrics table (docs/zero_to_thunder_tpu.md): {missing}")


def test_serving_metric_names_documented():
    """Every ``serving.*`` metric name the code emits must appear in the
    docs' serving metrics table — same contract pattern as the runtime
    metrics table above: the names are what dashboards and SLO alerts key
    on, so a new serving metric can't ship undocumented."""
    import glob

    import thunder_tpu

    pkg_root = os.path.dirname(thunder_tpu.__file__)
    sources = glob.glob(os.path.join(pkg_root, "**", "*.py"), recursive=True)
    names: set = set()
    for path in sources:
        with open(path) as f:
            names |= set(re.findall(r"[\"'](serving\.[a-z0-9_]+)[\"']", f.read()))
    # the scheduler's core metric families AND the SLO/supervision family
    # (engine restarts, shedding, deadline health) must all be present (a
    # refactor that stops emitting them should fail loudly here)
    for required in ("serving.queue_depth", "serving.active_requests",
                     "serving.kv_pages_free", "serving.ttft_ms",
                     "serving.decode_ms", "serving.preempted_requests",
                     "serving.engine_restarts", "serving.shed_requests",
                     "serving.deadline_misses", "serving.drain_ms",
                     "serving.slo_attainment",
                     # the shared-prefix serving family (ISSUE 14)
                     "serving.prefix_hit_rate", "serving.cached_pages",
                     "serving.cow_copies", "serving.cache_evictions",
                     # the fleet-router family (ISSUE 20)
                     "serving.router_decisions",
                     "serving.router_affinity_hits",
                     "serving.router_migrated_requests",
                     "serving.router_rebalanced_requests",
                     "serving.router_rejections"):
        assert required in names, f"code no longer emits {required}"
    with open(DOC) as f:
        doc = f.read()
    missing = [n for n in sorted(names) if f"`{n}`" not in doc]
    assert not missing, (
        "serving metrics emitted by the code but missing from the docs "
        f"serving metrics table (docs/zero_to_thunder_tpu.md): {missing}")


def test_serving_event_kinds_documented():
    """The serving event vocabulary is an ops contract three ways: every
    kind the code emits must be registered in ``serving.EVENT_KINDS`` and
    documented in the docs' serving-events table, and every registered or
    documented kind must still be emitted — a stale vocabulary teaches
    postmortem triage scripts to match events that never fire (same
    two-direction pattern as the block-planner decision kinds)."""
    import glob

    import thunder_tpu
    from thunder_tpu.serving import EVENT_KINDS

    assert EVENT_KINDS, "serving lost its event vocabulary"
    pkg_root = os.path.dirname(thunder_tpu.__file__)
    sources = glob.glob(os.path.join(pkg_root, "**", "*.py"), recursive=True)
    emitted: set = set()
    for path in sources:
        with open(path) as f:
            emitted |= set(re.findall(
                r"event\(\s*[\"'](serving_[a-z_]+)[\"']", f.read()))
    unregistered = sorted(emitted - EVENT_KINDS)
    assert not unregistered, (
        f"code emits serving event kinds missing from EVENT_KINDS "
        f"(thunder_tpu/serving/events.py): {unregistered}")
    dead = sorted(EVENT_KINDS - emitted)
    assert not dead, (
        f"EVENT_KINDS registers kinds no code emits any more: {dead}")
    with open(DOC) as f:
        doc = f.read()
    table_kinds = set(re.findall(r"^\| `(serving_[a-z_]+)` \|", doc, re.M))
    assert table_kinds, "docs lost the serving event-vocabulary table"
    undocumented = sorted(EVENT_KINDS - table_kinds)
    assert not undocumented, (
        "serving event kinds registered in EVENT_KINDS but missing from the "
        f"docs serving-events table (docs/zero_to_thunder_tpu.md): "
        f"{undocumented}")
    stale = sorted(table_kinds - EVENT_KINDS)
    assert not stale, (
        "docs serving-events table documents kinds the code no longer "
        f"registers: {stale}")


def test_health_states_documented():
    """The health-state vocabulary is the routing contract: a router keys
    its traffic decisions on these names (and the ``serving.health_state``
    gauge on their codes), so the docs table and
    ``serving.HEALTH_STATES`` must agree in BOTH directions — same
    discipline as the block-planner decision kinds."""
    from thunder_tpu.serving import HEALTH_STATES
    from thunder_tpu.serving.health import HEALTH_STATE_CODE

    assert HEALTH_STATES, "serving lost its health-state vocabulary"
    # the gauge codes are table positions — reordering silently rewires
    # every dashboard threshold, so the mapping is pinned here too
    assert HEALTH_STATE_CODE == {s: i for i, s in enumerate(HEALTH_STATES)}
    with open(DOC) as f:
        doc = f.read()
    table_states = set(re.findall(r"^\| `([A-Z]+)` \|", doc, re.M))
    assert table_states, "docs lost the serving health-states table"
    undocumented = sorted(set(HEALTH_STATES) - table_states)
    assert not undocumented, (
        "health states in serving.HEALTH_STATES but missing from the docs "
        f"health-states table (docs/zero_to_thunder_tpu.md): {undocumented}")
    stale = sorted(table_states - set(HEALTH_STATES))
    assert not stale, (
        "docs health-states table documents states the code no longer "
        f"defines: {stale}")


def test_census_metric_names_documented():
    """Every ``compile.*`` / ``hlo.*`` metric name the code emits must
    appear in the docs' census metrics table, and every name the table
    documents must still be emitted — the census gauges are what dashboards
    and the ROADMAP-3 overlap work key on (same both-direction pattern as
    the serving event vocabulary)."""
    import glob

    import thunder_tpu

    pkg_root = os.path.dirname(thunder_tpu.__file__)
    sources = glob.glob(os.path.join(pkg_root, "**", "*.py"), recursive=True)
    names: set = set()
    for path in sources:
        with open(path) as f:
            names |= set(re.findall(
                r"[\"']((?:compile|hlo)\.[a-z0-9_]+)[\"']", f.read()))
    # the census family must all be present (a refactor that stops
    # emitting them should fail loudly here)
    for required in ("compile.count", "compile.census_runs",
                     "compile.census_errors", "compile.pessimizations",
                     "compile.pallas_launches", "compile.fusion_regions",
                     "hlo.collective_instructions", "hlo.async_fraction",
                     "hlo.recv_bytes_per_device", "hlo.peak_hbm_bytes"):
        assert required in names, f"code no longer emits {required}"
    with open(DOC) as f:
        doc = f.read()
    missing = [n for n in sorted(names) if f"`{n}`" not in doc]
    assert not missing, (
        "compile/hlo census metrics emitted by the code but missing from "
        f"the docs metrics table (docs/zero_to_thunder_tpu.md): {missing}")
    # reverse direction: table rows documenting names nothing emits
    table_names = set(re.findall(r"^\| `((?:compile|hlo)\.[a-z0-9_]+)` \|",
                                 doc, re.M))
    assert table_names, "docs lost the census metrics table"
    stale = sorted(table_names - names)
    assert not stale, (
        f"docs census metrics table documents names the code no longer "
        f"emits: {stale}")


def test_profile_calib_metric_names_documented():
    """Every ``profile.*`` / ``calib.*`` metric name the measured-time
    observatory emits must appear in the docs' measured-time metrics table,
    and every name the table documents must still be emitted — same
    both-direction contract as the census metrics (calibration dashboards
    key on these names to watch model-vs-measured drift)."""
    import glob

    import thunder_tpu

    pkg_root = os.path.dirname(thunder_tpu.__file__)
    sources = glob.glob(os.path.join(pkg_root, "**", "*.py"), recursive=True)
    names: set = set()
    for path in sources:
        with open(path) as f:
            names |= set(re.findall(
                r"[\"']((?:profile|calib)\.[a-z0-9_]+)[\"']", f.read()))
    # the observatory's core families must all be present (a refactor that
    # stops emitting them should fail loudly here)
    for required in ("profile.regions_measured", "profile.ledger_records",
                     "profile.measured_coverage", "profile.residual_p50_pct",
                     "profile.verdict_flips", "calib.constants_fitted",
                     "calib.active_constants", "calib.budget_violations"):
        assert required in names, f"code no longer emits {required}"
    with open(DOC) as f:
        doc = f.read()
    missing = [n for n in sorted(names) if f"`{n}`" not in doc]
    assert not missing, (
        "profile/calib metrics emitted by the code but missing from the "
        f"docs measured-time metrics table (docs/zero_to_thunder_tpu.md): "
        f"{missing}")
    table_names = set(re.findall(r"^\| `((?:profile|calib)\.[a-z0-9_]+)` \|",
                                 doc, re.M))
    assert table_names, "docs lost the measured-time metrics table"
    stale = sorted(table_names - names)
    assert not stale, (
        f"docs measured-time metrics table documents names the code no "
        f"longer emits: {stale}")


def test_pessimization_kinds_documented():
    """The pessimization-sentinel vocabulary is an ops contract both ways:
    every kind in ``census.PESSIMIZATION_KINDS`` must be documented in
    NORTHSTAR.md's pessimization table, and every table row must name a
    registered kind (stale docs teach triage scripts to match findings
    that never fire)."""
    from thunder_tpu.observe.census import PESSIMIZATION_KINDS

    assert PESSIMIZATION_KINDS, "census lost its pessimization vocabulary"
    northstar_doc = os.path.join(REPO, "NORTHSTAR.md")
    with open(northstar_doc) as f:
        doc = f.read()
    missing = [k for k in sorted(PESSIMIZATION_KINDS) if f"`{k}`" not in doc]
    assert not missing, (
        "pessimization kinds the sentinel can emit but missing from the "
        f"NORTHSTAR.md table: {missing}")
    table_kinds = set(re.findall(r"^\| `([a-z][a-z-]*)` \|", doc, re.M))
    assert table_kinds, "NORTHSTAR.md lost its pessimization-kinds table"
    stale = sorted(table_kinds - set(PESSIMIZATION_KINDS))
    assert not stale, (
        "NORTHSTAR.md pessimization table documents kinds the sentinel "
        f"no longer registers: {stale}")


def test_block_planner_decision_kinds_documented():
    """Every verdict kind the block planner can emit must appear in the
    KERNELS.md "Reading planner decisions" table — the decision log is an
    ops surface (dashboards / triage scripts key on the kinds), and a new
    kind landing in code without its documented meaning fails tier-1 here
    rather than drifting silently. BOTH directions are enforced: a kind in
    KERNELS.md's table that the code no longer registers fails too (stale
    docs teach triage scripts to match verdicts that never fire). The
    in-source direction (the planner records only registered kinds) is
    asserted in tests/test_block_planner.py."""
    from thunder_tpu.core.fusion_passes import BLOCK_DECISION_KINDS

    assert BLOCK_DECISION_KINDS, "planner lost its decision vocabulary"
    with open(KERNELS_DOC) as f:
        doc = f.read()
    missing = [k for k in sorted(BLOCK_DECISION_KINDS) if f"`{k}`" not in doc]
    assert not missing, (
        "block-planner decision kinds emitted by the code but missing from "
        f"the KERNELS.md planner-decisions table: {missing}")
    # reverse direction: parse the planner-decisions table rows (| `kind` |)
    table_kinds = set(re.findall(r"^\| `([a-z][a-z-]*)` \|", doc, re.M))
    assert table_kinds, "KERNELS.md lost its planner-decisions table"
    stale = sorted(table_kinds - set(BLOCK_DECISION_KINDS))
    assert not stale, (
        "KERNELS.md planner-decisions table documents kinds the planner "
        f"no longer registers: {stale}")


# a repo-relative Python path as a document writes it: under one of our
# directories, or a bare file name at the top level. The reference's own
# ``thunder/...`` paths are not ours and are skipped by the look-behind, as
# is a quoted file name a user would choose (``execution_file="prog.py"``).
_PY_PATH = re.compile(
    r"(?<![\w/.\"-])((?:thunder_tpu|benchmark|tests|examples)/[\w/.-]*?\.py"
    r"|[A-Za-z_]\w*\.py)\b")


@pytest.mark.parametrize("doc", ["README.md", "KERNELS.md",
                                 "docs/zero_to_thunder_tpu.md"])
def test_named_python_paths_exist(doc):
    """Every repo-relative ``*.py`` path a document names exists in the
    tree: a script that goes takes its prose with it. A bare file name
    (``pallasex.py``) may live anywhere under our directories."""
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    named = set(_PY_PATH.findall(text))
    assert named, f"{doc} names no Python file?"
    basenames = set()
    for top in ("thunder_tpu", "benchmark", "tests", "examples"):
        for _, _, files in os.walk(os.path.join(REPO, top)):
            basenames.update(f for f in files if f.endswith(".py"))
    basenames.update(f for f in os.listdir(REPO) if f.endswith(".py"))
    missing = sorted(
        p for p in named
        if not (os.path.exists(os.path.join(REPO, p)) if "/" in p
                else p in basenames))
    assert not missing, f"{doc} names Python files that are gone: {missing}"
