"""jax.distributed bootstrap e2e: horovod_tpu topology drives
jax.distributed.initialize so jit programs span hosts (the reference's
multi-host NCCL role, carried by XLA collectives over ICI/DCN —
SURVEY §2.6/§5.8). CPU backend stands in for multi-host here; the
cross-process collectives ride jax's own distributed runtime."""

import pytest

pytestmark = pytest.mark.e2e


def test_jax_distributed_bootstrap_4proc(run_launcher):
    """4-process global mesh, 2 virtual devices per process (8 global):
    device view, cross-process psum, the flagship DP train step, FSDP
    with params sharded across process boundaries, the hierarchical
    (dp_cross x dp_local) two-level train step, and pipeline stages
    spanning processes — loss agreement allgathered across all 4
    processes for every step flavor."""
    result = run_launcher(
        4, "jax_distributed_worker.py",
        extra_env={
            "JAX_PLATFORMS": "cpu",
            # 2 local devices per process: the 2-D (cross, local) mesh
            # needs a real local axis (and 4x8 inherited from the
            # pytest env would oversubscribe the host).
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        },
        timeout=900)
    assert result.returncode == 0, result.stdout + result.stderr
    for marker in ("PASS global_device_view (8 devices over 4 processes)",
                   "PASS cross_process_sum",
                   "PASS cross_process_train_step",
                   "PASS cross_process_fsdp_step",
                   "PASS cross_process_hierarchical_step",
                   "PASS cross_process_pp_step"):
        assert marker in result.stdout, (marker, result.stdout)
