"""CPU rehearsal of chip_smoke.py.

Its phase functions run here at synth_xs size with the Pallas kernels in
interpret mode, so the script cannot rot between chip runs; its top-k
check must reject a wrong answer; and ``main`` must refuse to run
without a TPU.
"""
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke


def test_chip_smoke_phases_rehearse_on_cpu():
    train, _ = chip_smoke.phase_data("synth_xs", 0)
    # synth_xs is too small for a quarter-ratio sketch to fit its budget
    sketch = chip_smoke.phase_cluster(train, dim=16, ratio=0.5)
    trainer = chip_smoke.phase_train(
        train, sketch, dim=16, batch=256, fused_steps=8, host_steps=3,
        seed=0, lookup_backend="pallas", chunk=4)
    art, responses = chip_smoke.phase_serve(
        trainer, buckets=(1, 8), k=5, requests_per_bucket=2, seed=0)
    assert art.model["lookup_backend"] == "pallas"
    assert sorted(responses) == ["dense", "fused"]
    assert all(len(out) == 4 for out in responses.values())
    chip_smoke.phase_check(art, responses, k=5)


def test_check_topk_rejects_a_wrong_selection():
    rng = np.random.default_rng(0)
    U = rng.standard_normal((6, 8)).astype(np.float32)
    V = rng.standard_normal((50, 8)).astype(np.float32)
    users = np.array([0, 3, 5])
    s = U[users] @ V.T
    ids = np.argsort(-s, axis=1, kind="stable")[:, :4].astype(np.int32)
    vals = np.take_along_axis(s, ids, axis=1)
    assert chip_smoke.check_topk(users, vals, ids, U, V, 4) == (3, 1.0)
    worst = np.argsort(s, axis=1)[:, :1]
    bad = ids.copy()
    bad[:, -1:] = worst                    # swap in the lowest-scored item
    bad_vals = np.take_along_axis(s, bad, axis=1)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_topk(users, bad_vals, bad, U, V, 4)
    with pytest.raises(chip_smoke.SmokeFailure):    # values off the scores
        chip_smoke.check_topk(users, vals + 0.5, ids, U, V, 4)


def test_chip_smoke_main_refuses_cpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


SHARDED_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
assert jax.device_count() == 4
import chip_smoke
train, _ = chip_smoke.phase_data("synth_xs", 0)
chip_smoke.phase_sharded(train, dim=16, ratio=0.5, batch=256, steps=8,
                         seed=0, n_devices=4, lookup_backend="pallas",
                         chunk=4)
print("SHARDED_SMOKE_OK")
"""


def test_chip_smoke_sharded_phase_rehearses_on_four_cpu_devices():
    """The --chips 4 phase on four virtual CPU devices (device count is
    process-global, hence the subprocess)."""
    out = subprocess.run([sys.executable, "-c", SHARDED_CODE],
                         capture_output=True, text=True, timeout=600,
                         cwd=chip_smoke.ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_SMOKE_OK" in out.stdout
