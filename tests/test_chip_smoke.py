"""chip_smoke.py off the chip: its phase functions at the small shape on the
CPU mesh, and its refusal to report anything from a CPU.

The chip run itself (``python chip_smoke.py`` through the chip tool) is what
proves the system starts on the TPU; these tests keep the script's phases
and checks from rotting between chip runs. The plane phase runs at the
tests/test_e2e_fake.py shape (16x16 frames, fc_units 16); the fused phase's
env renders 84x84 at every size, so it only narrows the net.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402 — jax-free at import, like the parent process


@pytest.fixture
def child_env(monkeypatch, tmp_path):
    """What a phase child's process would carry. Registered with monkeypatch
    so the variables chip_smoke and cli.main assign are restored afterwards."""
    monkeypatch.setenv("BA3C_AUDIT", "1")
    monkeypatch.setenv("BA3C_PARAM_DIGEST", "1")
    monkeypatch.setenv("BA3C_FLIGHT_DIR", str(tmp_path))


@pytest.mark.parametrize("phase", list(chip_smoke.PHASES))
def test_phase_at_small_shape_on_cpu_mesh(phase, tmp_path, child_env):
    info = chip_smoke.PHASES[phase](
        chip_smoke.SMALL, str(tmp_path), platform="cpu"
    )
    assert info["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    if phase == "fused":
        assert info["updates"] == 3 * chip_smoke.SMALL.fused_steps_per_epoch
        assert info["first_dispatch_s"] > 0
        assert info["resume_first_dispatch_s"] > 0
    elif phase == "plane":
        assert info["ingest_copies_per_block"] == 1.0
        assert info["children"] >= chip_smoke.SMALL.plane_envs
        assert info["staging_blocks"] == chip_smoke.SMALL.staging_blocks
    elif phase == "forwards":
        assert info["int8_arm"] == "int8"
        assert info["float32_dvalue"] < info["int8_dvalue"] < chip_smoke.BAND_VALUE
    elif phase == "grouped":
        # off the chip the product is ragged_dot itself: no kernel, no gap
        assert info["pallas_kernels"] == 0 and info["rows_held"] > 0
        assert info["forward_max_abs_err"] == info["dw_max_abs_err"] == 0.0
    elif phase == "sparse_attn":
        # off the chip the op is the masked-dense form, whole: no kernel, and
        # float32 rounding's gap to the same form in blocks of queries
        assert info["pallas_kernels"] == 0 and 0.5 < info["kept_share"] < 1.0
        assert info["out_max_abs_err"] < 1e-2 * info["out_max_abs"]
        assert info["dk_max_abs_err"] < 1e-2 * info["dk_max_abs"]
    elif phase == "select":
        # off the chip the searches are the plain form itself: no kernel; the
        # regimes' masks are counted a row: min(k, live)
        rows, keys, k, _ = chip_smoke.SMALL.select_dims
        assert info["pallas_kernels"] == 0 and info["radix_lines"] > 0
        assert info["decode_fits_selected"] == sum(k - r for r in range(rows))
        assert info["decode_overflows_selected"] == rows * k
        assert info["decode_mixed_selected"] == k // 2 + 1 + (rows - 1) * k
    elif phase == "ssd":
        # off the chip the recurrence is its plain form itself: no kernel
        assert info["pallas_kernels"] == 0
        assert info["y_max_rel_err"] == info["dB_max_rel_err"] == 0.0
    elif phase == "delta":
        # off the chip the rule is its plain form itself: no kernel
        assert info["pallas_kernels"] == 0
        assert sorted(info["gaps"]) == ["near_least", "near_one", "trained"]
        for gaps in info["gaps"].values():
            assert gaps["o_max_rel_err"] == gaps["dalpha_max_rel_err"] == 0.0
    else:
        assert info["sharded_over"] == list(range(8))
        assert info["replicated_leaves"] > 0


def test_phase_refuses_another_platform(tmp_path):
    with pytest.raises(SystemExit, match="needs platform 'tpu'"):
        chip_smoke.phase_forwards(chip_smoke.SMALL, str(tmp_path))


def test_chip_smoke_exits_nonzero_on_cpu():
    """No accelerator: another exit code than 0 and no result line."""
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs platform 'tpu'" in proc.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """Without the program beside it the script has nothing to drive."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
