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


def test_result_line_holds_the_contract_keys_and_no_other():
    import collections
    import json

    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    Dev = collections.namedtuple("Dev", "platform device_kind")
    line = chip_smoke.result_line(
        chip_smoke.describe([Dev("tpu", "TPU v5 lite")]))
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
