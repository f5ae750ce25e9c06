"""The no-fallback rules of the bring-up (ISSUE 21): the chip check fails
without a TPU, the compile cache is placed from outside, and the native
core always goes through make."""

import os
import subprocess
import sys
import time

import pytest

from conftest import REPO_ROOT


def _run_on_cpu(script, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, os.path.join(REPO_ROOT, script)]
                          + list(argv), env=env, capture_output=True,
                          text=True, timeout=120)
    return proc, time.monotonic() - t0


def test_chip_smoke_fails_without_a_tpu():
    """One short subprocess that must fail before it builds a model, with
    no result line."""
    proc, secs = _run_on_cpu("chip_smoke.py")
    assert proc.returncode != 0, proc.stdout
    assert '"ok"' not in proc.stdout, proc.stdout
    assert "found no TPU" in proc.stderr, proc.stderr
    assert secs < 60, secs


def test_chip_smoke_four_chip_option_fails_without_a_tpu():
    proc, _ = _run_on_cpu("chip_smoke.py", "--chips", "4")
    assert proc.returncode != 0, proc.stdout
    assert '"ok"' not in proc.stdout, proc.stdout


def test_compile_cache_helper_honours_the_environment():
    from horovod_tpu.run.util import cpu_worker_env, use_compile_cache

    env = {"JAX_COMPILATION_CACHE_DIR": "/some/dir"}
    assert use_compile_cache(env) == "/some/dir"
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"
    worker = cpu_worker_env(base_env=env)
    assert worker["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"


def test_compile_cache_helper_defaults_inside_the_checkout():
    from horovod_tpu.run.util import cpu_worker_env, use_compile_cache

    env = {}
    fixed = os.path.join(REPO_ROOT, ".jax_cache")
    assert use_compile_cache(env) == fixed
    assert env["JAX_COMPILATION_CACHE_DIR"] == fixed
    # Every process of the checkout shares it: the launcher's CPU workers
    # get the same directory.
    assert cpu_worker_env(base_env={})["JAX_COMPILATION_CACHE_DIR"] == fixed
    with open(os.path.join(REPO_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_native_core_always_goes_through_make(monkeypatch):
    """A libhorovod_tpu.so that merely exists may be older than the sources
    copied with it: the loader runs make (a no-op when fresh) before every
    first load instead of returning as soon as the file is there."""
    from horovod_tpu.common import basics

    assert os.path.exists(basics._LIB_PATH)  # the suite has loaded it
    calls = []
    monkeypatch.setattr(
        basics.subprocess, "run",
        lambda cmd, **kw: calls.append((cmd, kw["cwd"])))
    basics._ensure_built()
    assert len(calls) == 1 and calls[0][0][0] == "make", calls
    assert os.path.samefile(calls[0][1], basics._NATIVE_DIR)


def test_moe_phase_steps_on_the_cpu(monkeypatch):
    """The smoke's routed-feed-forward step (`chip_smoke.moe_step`: QK-norm,
    dropless top-k over gated experts, the router's auxiliary losses,
    `make_train_step`) at a tiny size on the CPU: the loss falls and no
    assignment is dropped, by the smoke's own checks."""
    import jax

    import chip_smoke
    from horovod_tpu import parallel

    monkeypatch.setitem(chip_smoke.SIZES, "moe", dict(
        chip_smoke.SIZES["moe"], vocab_size=128, num_heads=2, embed_dim=32,
        mlp_dim=16, max_seq_len=32, moe_experts=8, moe_top_k=3))
    monkeypatch.setitem(chip_smoke.SIZES, "moe_batch", 2)
    monkeypatch.setitem(chip_smoke.SIZES, "moe_len", 32)
    mesh = parallel.data_parallel_mesh(devices=jax.devices("cpu")[:1])
    step, state, routing = chip_smoke.moe_step(mesh, 3, attention="dense")
    params, opt_state, batch = step.place(*state)
    chip_smoke.check_routing(routing(params, batch), 3 * 2 * 32)
    params, opt_state, losses, _ = chip_smoke.run_steps(
        step, params, opt_state, batch, 3)
    chip_smoke.check_losses(losses)
    chip_smoke.check_routing(routing(params, batch), 3 * 2 * 32)
    with pytest.raises(chip_smoke.PhaseFailed):
        chip_smoke.check_routing(routing(params, batch), 3 * 2 * 32 + 1)


def test_kernels_phase_expects_what_the_plan_names(capsys):
    """The smoke's attention shapes: the kernels it requires in the
    compiled program are `hvd.profile.flash_plan`'s, so the backward as
    one kernel at the L=1024 LM's shape and at a benchmark cell's, as two
    at the long grouped shape, whose dK/dV is the gridded kernel;
    `print_flash_plan` prints the one-entry backward plan."""
    import jax.numpy as jnp

    import chip_smoke

    got = [chip_smoke.flash_kernels(*shape, jnp.bfloat16)
           for shape in chip_smoke.SIZES["attn"]]
    one, two = (["hvd_flash_fwd", "hvd_flash_bwd"],
                ["hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"])
    assert got == [one, two, one]
    assert (2, 16, 16, 2048, 128) in chip_smoke.SIZES["attn"]
    chip_smoke.print_flash_plan(*chip_smoke.SIZES["attn"][1], jnp.bfloat16)
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 3 and lines[2].startswith(
        "  hvd_flash_dkv: gridded held by the k block, blocks 1024 x 512, "
        "grid (2, 32, 32) = 2048 steps")
    chip_smoke.print_flash_plan(2, 16, 16, 2048, 128, jnp.bfloat16)
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 2
    assert lines[1].startswith(
        "  hvd_flash_bwd: resident held by the k block, blocks 512 x 1024, "
        "grid (32, 2) = 64 steps, VMEM 10.0 MiB of a limit of")


def test_kernels_phase_knows_the_block_diffusion_shape(capsys):
    """The smoke's mask-ruled attention: the kernels it requires at the
    benchmark cell's shape are the plan's (the forward and the ONE backward
    kernel, resident and held by the q block), `print_flash_plan` prints the
    tiles each visits, and its case
    (kernel against the dense masked softmax) agrees on the CPU at a small
    size, where `flash_attention` is the blockwise form."""
    import jax
    import jax.numpy as jnp

    import chip_smoke
    from horovod_tpu.ops import BlockDiffusionMask

    B, H, G, L, D, block = chip_smoke.SIZES["attn_block_diffusion"]
    rule = BlockDiffusionMask(L, block)
    shape = (B, H, G, 2 * L, D, jnp.bfloat16)
    assert chip_smoke.flash_kernels(*shape, mask=rule) == [
        "hvd_flash_fwd", "hvd_flash_bwd"]
    chip_smoke.print_flash_plan(*shape, mask=rule)
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 2
    # the tiles by k blocks; then how each kernel walks them (PR 53): the
    # forward by k blocks alone, the backward a cut k block's lone 256-key
    # sub-tile alone
    for line, walk in zip(lines, (
            "512 keys: sub-tiles visited 1280 (masked 384)",
            "256 keys: sub-tiles visited 2304 (masked 512)")):
        assert line.endswith(
            "tiles visited 1280 (masked 384), skipped 2816; a lone sub-tile "
            "is of " + walk)
    assert lines[1].startswith(
        "  hvd_flash_bwd: resident held by the q block, blocks 1024 x 512, "
        "grid (4, 64) = 256 steps, VMEM 27.5 MiB of a limit of 52")
    small = BlockDiffusionMask(128, 4)
    name, kernel, reference, qkvw = chip_smoke.attention_case(
        1, 4, 2, 256, 64, jnp.float32, 0, mask=small)
    assert "BlockDiffusionMask(128, 4)" in name
    with jax.default_matmul_precision("highest"):
        for got, want in zip(kernel(*qkvw), reference(*qkvw)):
            assert chip_smoke.rel_err(got, want) < 1e-5


@pytest.mark.parametrize("block", [0, 4])
def test_backward_forms_agree_on_the_interpreter(monkeypatch, capsys, block):
    """`backward_forms_agree` at a shape small enough for the interpreter
    whose plan is the smoke's (8 query heads on one kv head: the one kernel
    held by the q block, and one byte under it dQ beside the gridded dK/dV),
    causal and under the block-diffusion rule: the two agree in f32 to
    1e-6, and a tolerance no kernel meets fails the phase."""
    import importlib

    import jax.numpy as jnp

    import chip_smoke
    from horovod_tpu.ops import BlockDiffusionMask

    fa = importlib.import_module("horovod_tpu.ops.flash_attention")
    for name, at in (("_pallas_forward_lse", 5), ("_pallas_backward", 8)):
        monkeypatch.setattr(fa, name, lambda *a, _f=getattr(fa, name),
                            _at=at, **kw: _f(*a[:_at], True, *a[_at + 1:],
                                             **kw))
    rule = BlockDiffusionMask(512, block) if block else None
    call = (1, 8, 1, 1024, 128, jnp.float32, 0, rule)
    chip_smoke.backward_forms_agree(*call, 1e-6)
    out = capsys.readouterr().out
    assert out.count("  ok  ") == 2 and "hvd_flash_bwd held by the q" in out
    with pytest.raises(chip_smoke.PhaseFailed):
        chip_smoke.backward_forms_agree(*call, -1.0)
