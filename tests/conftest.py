"""Test config: the suite is CPU-only by design. It pins the CPU backend
with 8 virtual devices so multi-device sharding paths compile and run
without TPU hardware (`python chip_smoke.py` is the on-chip check)."""

import os
import pathlib
import sys

REPO_ROOT = str(pathlib.Path(__file__).resolve().parent.parent)
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from horovod_tpu.run.util import use_compile_cache  # noqa: E402

os.environ["JAX_PLATFORMS"] = os.environ.get("HVD_TPU_TEST_PLATFORM", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()
# Persistent compile cache: in-process tests recompile the same jit
# programs every suite run otherwise (the launcher workers get the same
# directory via cpu_worker_env).
use_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def cpu_devices():
    import jax
    return jax.devices("cpu")


def clean_worker_env(extra_env=None):
    """Worker-subprocess env: delegates to the framework's single
    source of truth (horovod_tpu.run.util.cpu_worker_env), adding the
    repo root to PYTHONPATH."""
    from horovod_tpu.run.util import cpu_worker_env
    return cpu_worker_env(extra_env=extra_env, repo_root=REPO_ROOT)


@pytest.fixture
def run_launcher():
    """Runs a worker script under the launcher (`-np N` on localhost) —
    the shared harness for the multi-process tests (SURVEY.md §4)."""
    import subprocess

    def _run(np_, script, extra_env=None, timeout=300):
        env = clean_worker_env(extra_env)
        script_path = os.path.join(REPO_ROOT, "tests", script)
        return subprocess.run(
            [sys.executable, "-m", "horovod_tpu.run.run", "-np", str(np_),
             "--", sys.executable, script_path],
            env=env, timeout=timeout, capture_output=True, text=True)

    return _run


@pytest.fixture
def cpu_mesh_1d():
    """8-device mesh over axis 'hvd' on the CPU backend."""
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices("cpu")), ("hvd",))
