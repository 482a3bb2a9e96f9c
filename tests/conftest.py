"""Test rig: run everything on an 8-virtual-device CPU mesh.

The reference tests multi-GPU paths only with real GPUs
(``skipIf(torch.cuda.device_count() < N)``, SURVEY.md §4). On TPU/JAX we can
do better: XLA's CPU backend exposes N virtual devices, so every DP/TP/PP/SP
code path is exercised in CI with no accelerator. Pallas kernels run in
interpreter mode off-TPU (see ``apex_tpu.utils.platform``).

The suite always runs on the CPU backend, whatever the environment says:
a pytest process that spawns jax children must never hold a chip.
"""

import jax
import pytest

from apex_tpu.utils.compile_cache import enable_compile_cache

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# Persistent compilation cache: the suite's wall time is dominated by XLA
# compiles, and most test programs are identical run to run. The
# subprocess-driving tests (examples) land in the same directory
# because every entry point calls the same helper.
enable_compile_cache()


# ``quick`` tier (`./run_tests.sh quick` == `-m quick`): everything
# OUTSIDE the compile- and subprocess-heavy modules below and the L1
# convergence sweeps — the contributor/driver inner loop. The full
# `-m 'not slow'` tier remains the gate; quick only ADDS a marker, it
# never hides a test from the default run.
_HEAVY_MODULES = {
    "test_resume.py",           # kill-and-resume subprocess
    "test_graft_entry.py",      # in-process dryrun (all mesh shapes)
    "test_gpt.py",              # tp8/pp/cp shard_map compiles
    "test_models.py",           # resnet18/50 builds
    "test_determinism.py",      # profiler + bitwise train steps
    "test_pipeline_memory.py",  # compiled-memory analysis
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        p = item.path
        if p.name not in _HEAVY_MODULES and "L1" not in p.parts:
            item.add_marker(pytest.mark.quick)


@pytest.fixture(autouse=True)
def _reset_parallel_state():
    """Each test starts with no global mesh installed."""
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    yield
    parallel_state.destroy_model_parallel()
