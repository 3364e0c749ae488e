"""chip_smoke.py off the chip: it refuses to run without a TPU or
without the repository beside it, printing no result, and its phases
hold their checks when the twin kernels stand in for the device (small
sizes; the chip runs them at full size with the real kernels)."""

import os
import pathlib
import shutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    proc = _run(REPO / "chip_smoke.py", REPO)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_refuses_without_the_repository(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(REPO / "chip_smoke.py", lone)
    proc = _run(lone, tmp_path)
    assert proc.returncode != 0
    assert "checkout" in proc.stderr
    assert proc.stdout == ""


def test_phases_hold_on_twin_kernels(monkeypatch):
    import chip_smoke as S
    from harmony_tpu import aot
    from harmony_tpu import sched

    monkeypatch.setenv("HARMONY_KERNEL_TWIN", "1")
    guard = S.Guard()
    aot.warmup(aot.load_manifest())  # twins: marks every program warm
    guard.warmed()
    try:
        assert S.phase_quorum(7, guard, n_keys=24, n_valid=5) == 7
        assert S.phase_replay(7, guard, n_keys=16, width=8) == 8
        assert S.phase_single(7, guard, width=8) == 8
        assert S.phase_localnet(guard, keys_per_node=2, blocks=1) >= 1
    finally:
        sched.reset()
    with pytest.raises(S.SmokeFailure, match="did not move"):
        guard.start()
        guard.after("idle", moved=("verify",))
