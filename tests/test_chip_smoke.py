"""chip_smoke.py is the chip's check, not the CPU's: here it is only
rehearsed. The rehearsal must run every leg and say what it is; the
real invocation must refuse a CPU before it trains anything. Both run
in child interpreters, as the driver runs the script."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PASS_MARK = '"ok": true'


def _smoke(*argv):
    # The conftest's environment (JAX_PLATFORMS=cpu, eight virtual
    # devices, the CPU compile cache) is inherited, so the rehearsal also
    # walks the several-device placement checks.
    return subprocess.run([sys.executable, "chip_smoke.py", *argv],
                          cwd=REPO, capture_output=True, text=True,
                          timeout=600)


def test_rehearsal_runs_every_leg_and_never_passes():
    proc = _smoke("--rehearsal")
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = proc.stdout
    for leg in ("LEG kernels: ok", "LEG data (", "LEG train (cli.game_train"
                "): ok", "LEG serve (cli.serve): ok", "LEG compile cache:"):
        assert leg in out, (leg, out)
    assert "sharded" in out and "over 8 devices" in out
    assert "REHEARSAL (cpu)" in out.splitlines()[1]
    assert out.splitlines()[-1].startswith("REHEARSAL (cpu)")
    assert PASS_MARK not in out


def test_without_a_chip_it_refuses_before_training():
    proc = _smoke()
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout and "not 'tpu'" in proc.stderr
    assert "LEG" not in proc.stdout and PASS_MARK not in proc.stdout
