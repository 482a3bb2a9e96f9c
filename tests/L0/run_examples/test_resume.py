"""Checkpoint/resume acceptance (ref: ``examples/imagenet/main_amp.py``
``--resume`` reproducing the loss curve after a restart)."""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))
SCRIPT = os.path.join(REPO, "examples", "imagenet", "main_amp.py")

ARGS = ["-a", "resnet10", "--image-size", "32", "--num-classes", "10",
        "-b", "8", "--print-freq", "1", "--opt-level", "O2"]


def run(args, env_extra=None):
    # the child must stay on the CPU: this pytest process has a backend
    # of its own, and a chip belongs to one process at a time
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    r = subprocess.run([sys.executable, SCRIPT] + ARGS + args,
                       capture_output=True, text=True, timeout=1200,
                       env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    return {int(m.group(1)): m.group(2) for m in re.finditer(
        r"step\s+(\d+)\s+loss (\d+\.\d+)", r.stdout)}


def test_kill_and_resume_reproduces_loss_curve(tmp_path):
    ck_a = str(tmp_path / "a.ckpt")
    ck_b = str(tmp_path / "b.ckpt")

    straight = run(["--steps", "6", "--checkpoint", ck_a])
    # "killed" run: stops after 3 steps, saved at step 2
    run(["--steps", "3", "--checkpoint", ck_b])
    resumed = run(["--steps", "6", "--checkpoint", ck_b,
                   "--resume", ck_b])

    assert set(resumed) == {3, 4, 5}  # continued where it left off
    for s in (3, 4, 5):
        # bitwise-printed parity: deterministic synthetic data + exactly
        # restored (params, bn stats, optimizer, scaler) state
        assert resumed[s] == straight[s], (s, resumed[s], straight[s])


def test_checkpoint_atomicity(tmp_path):
    from apex_tpu.utils.checkpoint import load_checkpoint, save_checkpoint

    path = str(tmp_path / "t.ckpt")
    tree = {"a": jnp.arange(5, dtype=jnp.bfloat16),
            "b": [jnp.float32(1.5), np.int32(7)]}
    save_checkpoint(path, tree)
    out = load_checkpoint(path)
    assert out["a"].dtype == jnp.bfloat16  # ml_dtypes round-trips
    np.testing.assert_array_equal(out["a"],
                                  np.arange(5, dtype=jnp.bfloat16))
    # overwrite must go through rename (no partial file even on reload)
    save_checkpoint(path, {"a": jnp.zeros((3,))})
    out = load_checkpoint(path)
    np.testing.assert_array_equal(out["a"], np.zeros((3,)))
    assert [p for p in os.listdir(tmp_path) if p.endswith(".tmp")] == []
