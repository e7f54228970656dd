"""Test configuration: force an 8-device virtual CPU platform so distributed
transforms/collectives are testable without TPU hardware (strictly better
than the reference, which cannot test collectives without GPUs — SURVEY §4)."""

import os

# must run before jax backend initialization
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

# The suite is a CPU rehearsal by definition — 8 virtual devices, Pallas
# kernels in interpret mode — so the platform is asked for in code (it must
# hold even where JAX_PLATFORMS is unset and a chip is attached), with exact
# matmuls for numerical comparisons.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

# Suite wall-time is dominated by XLA compiles of near-identical tiny
# programs; the persistent executable cache dedups them within one run and
# removes them entirely on warm reruns. Placed by the one helper every entry
# point shares: JAX_COMPILATION_CACHE_DIR wins when set, else exactly
# .pytest_xla_cache (the path is part of the cache key — it must not move).
import thunder_tpu  # noqa: E402

thunder_tpu.enable_compilation_cache(os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, ".pytest_xla_cache")))

import pytest  # noqa: E402


def pytest_configure(config):
    # register the tier-boundary marker so `-m 'not slow'` selection never
    # silently no-ops because of a typo'd/unknown marker
    config.addinivalue_line("markers", "slow: excluded from the tier-1 budget "
                            "(run explicitly or in the full suite)")
    # chaos = deterministic fault-injection / recovery tests (runtime.faults
    # schedules are seeded, so these stay IN tier-1 — the marker exists for
    # selection, `-m chaos`, not exclusion)
    config.addinivalue_line("markers", "chaos: deterministic fault-injection "
                            "and recovery tests (tier-1; select with -m chaos)")


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    # machine-readable summary for the tier-1 driver: counting progress dots
    # breaks when a test prints mid-line; this line is grep-able and exact.
    # (Emitted even when the run is interrupted part-way.)
    passed = len(terminalreporter.stats.get("passed", []))
    failed = len(terminalreporter.stats.get("failed", []))
    errors = len(terminalreporter.stats.get("error", []))
    terminalreporter.write_line(f"PASSED={passed} FAILED={failed} ERRORS={errors}")


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs


@pytest.fixture(scope="session")
def fsdp_smoke_step():
    """ONE tiny fsdp zero-2 smoke compile (llama tiny, 2 layers, 8-device
    CPU mesh — the NORTHSTAR smoke config) shared by test_northstar's
    evidence-pipeline smoke and test_census's census/budget gates: the
    compile plus its memoized AOT executable are the expensive parts, and
    both files read the same entry. Returns (jstep, entry)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    import numpy as np

    import thunder_tpu as tt
    from thunder_tpu.core.devices import MeshSpec
    from thunder_tpu.distributed import fsdp
    from thunder_tpu.models import llama
    from thunder_tpu.optim import AdamW

    cfg = llama.CONFIGS["tiny"]
    opt = AdamW(lr=1e-4)

    def train_step(params, opt_state, tokens, targets):
        loss, grads = tt.value_and_grad(
            lambda p: llama.loss_fn(p, tokens, targets, cfg))(params)
        new_p, new_s = opt.update(params, grads, opt_state)
        return loss, new_p, new_s

    params = llama.init_params(cfg, seed=0, scale_layers=2)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab_size, (8, 16)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)
    jstep = fsdp(train_step, MeshSpec.make(fsdp=8), zero=2)
    entry = jstep.compile(params, opt.init(params), tokens, targets)
    return jstep, entry


@pytest.fixture(scope="session")
def fsdp_overlap_step():
    """The SAME tiny fsdp zero-2 smoke config compiled WITH the
    overlap-scheduling pass (``comm_reorder=True``): decomposed forward
    gathers, bucketed sub-threshold collectives, cost-aware schedule.
    Shared by test_overlap's schedule/determinism tests and test_census's
    overlap budget gate. Returns (jstep, entry)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    import numpy as np

    import thunder_tpu as tt
    from thunder_tpu.core.devices import MeshSpec
    from thunder_tpu.distributed import fsdp
    from thunder_tpu.models import llama
    from thunder_tpu.optim import AdamW

    cfg = llama.CONFIGS["tiny"]
    opt = AdamW(lr=1e-4)

    def train_step(params, opt_state, tokens, targets):
        loss, grads = tt.value_and_grad(
            lambda p: llama.loss_fn(p, tokens, targets, cfg))(params)
        new_p, new_s = opt.update(params, grads, opt_state)
        return loss, new_p, new_s

    params = llama.init_params(cfg, seed=0, scale_layers=2)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab_size, (8, 16)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)
    jstep = fsdp(train_step, MeshSpec.make(fsdp=8), zero=2, comm_reorder=True)
    entry = jstep.compile(params, opt.init(params), tokens, targets)
    return jstep, entry


@pytest.fixture
def heads_a_copy(monkeypatch):
    """The per-op kernel's walk at ``nh`` KV heads a copy: the VMEM it plans
    against holds exactly that many heads' staging and blocks (the heads are
    sized in bytes too: shrink the bytes, not an option)."""
    from thunder_tpu.core import cost_model
    from thunder_tpu.executors import pallasex as px

    real = cost_model.decode_pages_per_block

    def at(nh):
        def sized(ps, hd, item, npg, kv_heads=1, head_bytes=0):
            ppb, _ = real(ps, hd, item, npg)
            return real(ps, hd, item, npg, kv_heads=kv_heads,
                        head_bytes=head_bytes,
                        vmem_left=nh * (4 * ppb * ps * hd * item + head_bytes))
        monkeypatch.setattr(px, "decode_pages_per_block", sized)
    return at
