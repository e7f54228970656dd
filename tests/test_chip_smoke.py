"""CPU rehearsal of ``chip_smoke.py`` (tier-1): the smoke's own phase
functions at a tiny preset with Pallas kernels in interpret mode, so the
planned == executed / empty-quarantine / no-fallback checks run on every PR;
the device gate; a broken kernel being LOUD instead of served through its
decomposition; and the compile cache staying where the environment put it.
The chip run itself is the builder's (``chiprun -- python3 chip_smoke.py``)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from thunder_tpu.runtime import faults, quarantine  # noqa: E402
from thunder_tpu.runtime.faults import KernelExecutionError  # noqa: E402

# float32 tiny-gqa: the bounds are summation-order noise, not bf16 rounding
TINY = chip_smoke.Preset(
    model="tiny-gqa", n_layers=1, batch=2, seq=32, steps=1,
    max_slots=2, page_size=8, max_context=32, prefill_chunk=16,
    prompt_lens=(5, 13, 22),                # rungs {8, 16}
    new_tokens=2, parity_prompt=6, parity_tokens=2,
    loss_atol=1e-4, logits_atol=2e-3, logits_rms=5e-4, require_flash=True)


@pytest.fixture(autouse=True)
def _clean_runtime(monkeypatch):
    monkeypatch.setenv("THUNDER_TPU_PALLAS_INTERPRET", "1")
    faults.clear()
    quarantine.reset()
    yield
    faults.clear()
    quarantine.reset()
    from thunder_tpu import observe

    observe.disable()


@pytest.fixture(scope="module")
def meter():
    return chip_smoke.CompileMeter()


def test_phases_pass_at_tiny_preset(meter):
    """train + serve, then fsdp=2 and mesh=2 on the virtual CPU devices:
    every phase's own checks hold (reference loss/logits, completion,
    quiescence, planned == executed, nothing quarantined), and the claim
    tables carry the kernels each program is supposed to run."""
    dev = chip_smoke.device_info()
    report = chip_smoke.run(TINY, dev, meter, multi_chip=2)
    train = report["train"]["claims"]
    assert train["planned"] == train["executed"]
    for cid in (*chip_smoke.FLASH_CLAIMS, "pallas.fused_adamw"):
        assert train["executed"].get(cid), (cid, train)
    assert report["train"]["losses"][-1] < report["train"]["losses"][0]
    decode = report["serve"]["claims"]["decode"]
    assert decode["executed"].get("pallas.decode_layer") == TINY.n_layers
    # under a mesh the kernels run inside their partitioning plans: the
    # attention sub-block (pool sharded by kv-head) and the MLP sub-block
    mesh_decode = report["serve_mesh"]["claims"]["decode"]
    assert mesh_decode["executed"].get("pallas.attn_subblock") == TINY.n_layers
    assert mesh_decode["executed"].get("pallas.mlp_subblock") == TINY.n_layers
    assert report["serve_mesh"]["spread"]["kv_pool"]["sharded_1_over_n"] > 0
    assert report["fsdp"]["spread"]["params"]["sharded_1_over_n"] > 0
    json.dumps(report, default=str)     # what main() writes must serialize


def test_main_refuses_to_run_off_tpu(capsys):
    """Platform is not ``tpu``: non-zero exit before any work, and no
    result line on stdout."""
    assert chip_smoke.device_info()["platform"] == "cpu"
    assert chip_smoke.main([]) == chip_smoke.EXIT_NO_ACCELERATOR != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no accelerator" in captured.err


def test_kernel_that_breaks_is_loud_not_served(monkeypatch, meter):
    """The seed's failure mode, replayed: the decode megakernel raises an
    AttributeError while being traced (``pl.store`` did not exist in the
    installed Pallas). The serve phase must FAIL with that error chained —
    not quarantine the claim, recompile on the decomposition and report
    every request complete."""
    from thunder_tpu.executors import pallasex

    def gone(*a, **k):
        raise AttributeError(
            "module 'jax.experimental.pallas' has no attribute 'store'")

    monkeypatch.setattr(pallasex, "_decode_qkv_phase", gone)
    with pytest.raises(KernelExecutionError) as exc:
        chip_smoke.serve_phase(TINY, meter, chip_smoke.device_info())
    assert exc.value.claim_id == "pallas.decode_layer"
    assert isinstance(exc.value.__cause__, AttributeError)
    assert len(quarantine.get_quarantine()) == 0


def test_claim_table_fails_when_a_required_kernel_did_not_claim():
    """The other half of planned == executed: a program that ran WITHOUT a
    kernel the phase requires (the executor vanished) fails the table."""
    import numpy as np

    import thunder_tpu as tt
    from thunder_tpu import ops

    jf = tt.jit(lambda a, w: ops.rms_norm(a, w), executors=["xla"])
    jf(np.ones((8, 128), np.float32), np.ones(128, np.float32))
    with pytest.raises(RuntimeError, match="did not claim"):
        chip_smoke.claim_table(jf, "probe", required=("pallas.rms_norm",))


def test_compile_cache_is_placed_from_outside(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: importing thunder_tpu and running
    one ``tt.jit`` leaves ``jax.config.jax_compilation_cache_dir`` equal to
    it, and nothing but JAX's own entries lands there — no
    ``kernel_quarantine.json``, no calibration overlay. (A fresh process:
    JAX reads the variable at import.)"""
    cache = tmp_path / "outside-cache"
    code = (
        "import os, numpy as np, jax\n"
        "import thunder_tpu as tt\n"
        "used = tt.enable_compilation_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)\n"
        "jf = tt.jit(lambda a: tt.ops.sum(tt.ops.matmul(a, a)))\n"
        "jf(np.ones((64, 64), np.float32))\n"
        "print(used); print(jax.config.jax_compilation_cache_dir)\n"
        "print(sorted(os.listdir(used)))\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("THUNDER_TPU_QUARANTINE_DIR", None)
    env.pop("THUNDER_TPU_CALIBRATION_DIR", None)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=str(tmp_path),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    used, configured, listing = out.stdout.strip().splitlines()[-3:]
    assert used == configured == str(cache)
    assert "kernel_quarantine.json" not in listing
    assert "cost_calibration.json" not in listing
    assert listing != "[]"              # JAX did write its entries there
