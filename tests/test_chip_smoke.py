"""`chip_smoke.py`'s phases at tiny sizes on the CPU, Pallas kernels in
interpret mode — the first rehearsal of a chip run, kept as a test — and
the refusal to run anything without a chip."""
import json
import os
import subprocess
import sys

import pytest

import chip_smoke
from paddle_tpu.models import ErnieConfig, GPTConfig
from paddle_tpu.serving import attention as paged

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def interpret_kernels(monkeypatch):
    monkeypatch.setattr(paged, "KERNEL_MODE", "interpret")


def _last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_kernels_phase(interpret_kernels, capsys):
    out = chip_smoke.kernels_phase(
        seed=0, interpret=True,
        flash_cases=(((1, 128, 2, 64), False), ((1, 128, 2, 128), True)),
        norm_shape=(64, 256), paged_heads=2, paged_head_dim=32,
        paged_tokens=16)
    assert out["ok"], out
    assert set(out["dispatch"]) == {"decode_pallas_interpret",
                                    "ragged_pallas_interpret"}
    assert _last_line(capsys) == out       # the printed line is the result


def test_kernels_phase_fails_on_a_reference_fallback():
    """KERNEL_MODE 'auto' on the CPU takes the jnp references: the phase
    must call that a failure, not a pass within tolerance."""
    out = chip_smoke.kernels_phase(
        seed=0, interpret=True, flash_cases=(((1, 128, 2, 64), False),),
        norm_shape=(64, 256), paged_heads=2, paged_head_dim=32,
        paged_tokens=16)
    assert not out["checks"]["paged_kernels_dispatched"]
    assert not out["ok"]


def test_train_phase():
    out = chip_smoke.train_phase(ErnieConfig.tiny(), batch=4, seq=32,
                                 steps=4, seed=0)
    checks = dict(out["checks"])
    # no Pallas kernel is lowered on the CPU, and the phase says so
    assert checks.pop("tpu_custom_call") is False
    assert all(checks.values()), out
    assert not out["ok"]
    assert len(out["losses"]) == 4
    assert out["param_dtype"] == "bf16" and out["stage"] == 2


def test_serve_phase(interpret_kernels):
    out = chip_smoke.serve_phase(
        GPTConfig.tiny(), prompt_lens=(5, 40, 17, 33), max_new_tokens=6,
        seed=0, max_batch_size=4, max_seq_len=64)
    assert out["ok"], out
    assert out["fault_events"] == 0
    assert out["dispatch"].get("decode_pallas_interpret", 0) > 0
    assert [t[0] for t in out["first_tokens"]] == out["dense_argmax"]


def test_serve_phase_fails_on_a_reference_fallback():
    out = chip_smoke.serve_phase(
        GPTConfig.tiny(), prompt_lens=(5, 17), max_new_tokens=4, seed=0,
        max_batch_size=2, max_seq_len=64)
    assert out["dispatch"].get("decode_reference", 0) > 0
    assert not out["checks"]["paged_kernels_dispatched"]
    assert not out["ok"]


def test_sharded_train_phase():
    out = chip_smoke.sharded_train_phase(ErnieConfig.tiny(), batch=8,
                                         seq=32, steps=3, seed=0)
    assert out["ok"], out
    assert out["params_bit_identical"]     # the CPU backend's pinned fact
    assert out["state_devices"] == 4


def test_sharded_serve_phase(interpret_kernels):
    out = chip_smoke.sharded_serve_phase(
        GPTConfig.tiny(), prompt_lens=(9, 40), max_new_tokens=6, seed=0,
        max_batch_size=4, max_seq_len=64)
    assert out["ok"], out
    assert out["kv_pool_devices"] == 4


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_without_a_chip_runs_no_phase(argv):
    """`python chip_smoke.py` in a sandbox: non-zero, no phase, no
    result line — nothing is computed on the CPU under a chip's name."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    run = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert run.returncode != 0
    assert run.stdout == ""
    assert "needs a TPU" in run.stderr
