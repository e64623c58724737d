"""chip_smoke.py refuses off TPU, and its phases hold on the CPU.

The script is the on-chip check; here it runs in a subprocess pinned to the
CPU.  Without the rehearsal switch it must stop at the device check before
any phase; with it, every phase runs at a tiny size (Pallas in interpret
mode) and passes its reference check, and the script still prints no
result.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("serve", "window", "heavy", "paper", "kernels")


def _run(*args, rehearsal: bool):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if rehearsal:
        env["CHIP_SMOKE_REHEARSAL"] = "1"
    else:
        env.pop("CHIP_SMOKE_REHEARSAL", None)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )


def test_refuses_without_tpu_before_any_phase():
    out = _run(rehearsal=False)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert "[smoke]" not in out.stdout
    assert '"ok"' not in out.stdout


def test_rehearsal_passes_every_phase_then_refuses():
    out = _run("--seed", "3", rehearsal=True)
    assert out.returncode != 0, out.stderr[-3000:]
    assert "rehearsal finished" in out.stderr, out.stderr[-3000:]
    for phase in PHASES:
        assert f"[smoke] {phase}" in out.stdout, out.stdout
    assert out.stdout.count("kernels ") == 14  # 7 axes x 2 Pallas backends
    assert '"ok"' not in out.stdout
