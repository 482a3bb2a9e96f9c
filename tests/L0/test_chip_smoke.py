"""``chip_smoke.py`` is the on-chip check: off the chip it must fail
loudly instead of passing at CPU sizes. (What it does ON the chip only the
chip can say; ``--cpu-rehearsal`` is exercised by hand before a chip run,
not here — it compiles three models.)"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_refuses_to_run_without_a_tpu():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode not in (0, None), r.stdout
    assert "platform 'cpu'" in r.stderr and "not a TPU" in r.stderr, r.stderr
    assert r.stdout == "", r.stdout  # no phase ran, no result printed
