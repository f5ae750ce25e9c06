"""`hvd.profile` (docs/TRACING.md, "The in-`jit` step"): the profiler control
on the CPU backend, and the names the program puts on the train step —
scopes in the lowered step's scope paths, `name=` on every Pallas kernel —
which change no number and put no Python on the per-step path."""

import ast
import contextlib
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu import models, profile
from horovod_tpu.models.resnet import BottleneckBlock, ResNet
from horovod_tpu.ops.flash_attention import (_flash, flash_ring_bwd_step,
                                             flash_ring_step)
from horovod_tpu.ops.losses import chunked_softmax_cross_entropy
from horovod_tpu.parallel import data_parallel_mesh, make_train_step
from horovod_tpu.parallel.train import cross_entropy_loss

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LENGTH, VOCAB = 32, 64


def _busy():
    jnp.dot(jnp.ones((8, 8)), jnp.ones((8, 8))).block_until_ready()


def _host_event_names(path):
    data = jax.profiler.ProfileData.from_file(path)
    return {e.name for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events}


# --------------------------------------------------------------------------
# The control
# --------------------------------------------------------------------------

def test_importing_horovod_tpu_imports_no_jax():
    code = ("import sys, threading, horovod_tpu as hvd; "
            "assert hvd.profile.span('x') is hvd.profile.span('y'); "
            "assert 'jax' not in sys.modules, 'jax was imported'; "
            "assert threading.active_count() == 1")
    subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT, check=True,
                   timeout=120)


def test_start_stop_twice_leaves_two_readable_traces(tmp_path):
    assert hvd.profile is profile and not profile.active()
    paths = []
    for run in ("a", "b"):
        profile.start(tmp_path / run)
        assert profile.active()
        _busy()
        paths.append(profile.stop())
        assert not profile.active()
    assert len(set(paths)) == 2
    for path in paths:
        assert path.endswith(".xplane.pb")
        assert jax.profiler.ProfileData.from_file(path).planes


@pytest.mark.parametrize("misuse", ["double_start", "idle_stop"])
def test_misuse_raises(tmp_path, misuse):
    if misuse == "idle_stop":
        with pytest.raises(RuntimeError, match="no trace is active"):
            profile.stop()
        return
    profile.start(tmp_path)
    try:
        with pytest.raises(RuntimeError, match="already active"):
            profile.start(tmp_path)
        assert profile.active()  # the refused start left the trace running
    finally:
        profile.stop()
    assert not profile.active()


def test_span_is_the_shared_null_context_while_idle():
    assert not profile.active()
    assert profile.span("a") is profile.span("b")
    assert isinstance(profile.span("a"), contextlib.nullcontext)


def test_spans_land_on_the_host_plane_while_active(tmp_path):
    profile.start(tmp_path)
    with profile.span("my_dispatch"):
        _busy()
    names = _host_event_names(profile.stop())
    assert "my_dispatch" in names


# --------------------------------------------------------------------------
# The scopes of the train step
# --------------------------------------------------------------------------

def _tiny_lm():
    cfg = models.TransformerConfig(
        vocab_size=VOCAB, num_layers=2, num_heads=2, embed_dim=16,
        mlp_dim=32, max_seq_len=LENGTH, attention="dense")
    model = models.Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, LENGTH), 0, VOCAB)
    params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]

    def loss_fn(params, batch):
        hid = model.apply({"params": params}, batch["x"],
                          return_hidden=True)
        return chunked_softmax_cross_entropy(
            hid, params["lm_head"]["kernel"],
            jnp.roll(batch["x"], -1, axis=1), chunk=16)

    return params, {"x": tokens}, loss_fn, optax.adam(1e-2)


def _tiny_resnet():
    model = ResNet(stage_sizes=[1, 1, 1, 1], block_cls=BottleneckBlock,
                   num_classes=10, num_filters=8, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32, 3))
    y = jnp.arange(8) % 10
    variables = model.init(jax.random.PRNGKey(0), x[:1], train=False)
    stats = variables["batch_stats"]

    def loss_fn(params, batch):
        logits, _ = model.apply({"params": params, "batch_stats": stats},
                                batch["x"], train=True,
                                mutable=["batch_stats"])
        return cross_entropy_loss(logits, batch["y"])

    return (variables["params"], {"x": x, "y": y}, loss_fn,
            optax.sgd(0.01, momentum=0.9))


MODELS = {"transformer": _tiny_lm, "resnet": _tiny_resnet}
STEP_KINDS = {"plain": {}, "zero1": {"zero1": True},
              "accum_steps": {"accum_steps": 2}}
MODEL_PARTS = {
    "transformer": [profile.EMBED, profile.BLOCK + "/block_1/attn",
                    profile.BLOCK + "/block_1/mlp/mlp_in", profile.HEAD],
    "resnet": [profile.STEM, profile.HEAD] + list(profile.STAGES)}
COLLECTIVE = re.compile(
    r"/(psum|pmean|all_gather|reduce_scatter|psum_scatter|ppermute)\d*$")


def _placed_step(model, kind):
    params, batch, loss_fn, opt = MODELS[model]()
    mesh = data_parallel_mesh(devices=jax.devices("cpu")[:4])
    step = make_train_step(loss_fn, opt, mesh, donate=False,
                           **STEP_KINDS[kind])
    return step, step.place(params, opt.init(params), batch)


def _scope_paths(model, kind):
    step, state = _placed_step(model, kind)
    text = step.lower(*state).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


@pytest.mark.parametrize("kind", list(STEP_KINDS))
@pytest.mark.parametrize("model", list(MODELS))
def test_lowered_step_names_its_phases(model, kind):
    paths = _scope_paths(model, kind)

    def some(scope, *parts):
        """Whether a path leads through `scope` (a pattern, from a path
        element's start) and then holds every one of `parts`."""
        start = re.compile("(^|/)" + scope)
        return any(start.search(p) and all(x in p for x in parts)
                   for p in paths)

    inside = profile.FWD_BWD + "/"
    if kind == "accum_steps":
        # The scan's body is lowered as a function of its own, called
        # under the phase's scope; the paths inside it start afresh
        # (XLA joins the two when it inlines the call).
        assert some(profile.FWD_BWD + "/while/body/closed_call")
        inside = ""

    # the model's parts under the forward-and-backward phase, in both
    # directions: a backward operation carries `transpose(jvp(...))`
    for part in MODEL_PARTS[model]:
        assert some(inside + r"jvp\(", part), (part, "forward")
        assert some(inside + r"transpose\(jvp\(", part), (part, "backward")
    assert some(inside + r"jvp\(%s\)" % profile.LOSS)
    # the chunked loss forms its gradient in the forward pass; what its
    # backward rule does (scale by the cotangent) is under the scope too
    assert some(inside + r"transpose\(", profile.LOSS)
    assert some(profile.OPTIMIZER + "/")
    assert some(profile.PARAM_GATHER + "/all_gather") == (kind == "zero1")
    wanted = "reduce_scatter" if kind == "zero1" else "psum"
    assert some(profile.GRAD_SYNC + "/" + wanted)
    # every collective of the step lies under the phase that owns it
    for p in paths:
        if COLLECTIVE.search(p):
            assert (profile.GRAD_SYNC in p or profile.PARAM_GATHER in p), p
    # the phases stand beside each other: no operation is under two
    for p in paths:
        phases = [t for t in re.split(r"[/()]", p)
                  if t in profile.PHASE_SCOPES]
        assert len(set(phases)) <= 1, p


@pytest.mark.parametrize("kind", list(STEP_KINDS))
def test_names_change_no_number(kind, monkeypatch):
    def two_steps():
        step, (params, opt_state, batch) = _placed_step("transformer", kind)
        for _ in range(2):
            params, opt_state, loss = step(params, opt_state, batch)
        return jax.device_get((loss, params))

    named = two_steps()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = two_steps()
    for a, b in zip(jax.tree_util.tree_leaves(named),
                    jax.tree_util.tree_leaves(bare)):
        np.testing.assert_array_equal(a, b)


def test_plain_step_is_the_jitted_callable_itself():
    step, _ = _placed_step("transformer", "plain")
    assert type(step) is type(jax.jit(lambda x: x))  # no Python per step


def test_idle_host_spans_make_no_profiler_call(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("a profiler call while no trace is active")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    step, state = _placed_step("transformer", "zero1")  # place: hvd_place
    params, opt_state, loss = step(*state)  # wrapper: hvd_step_dispatch
    assert np.isfinite(float(loss))


def test_host_spans_of_the_zero1_wrapper_land_on_the_host_plane(tmp_path):
    params, batch, loss_fn, opt = _tiny_lm()
    mesh = data_parallel_mesh(devices=jax.devices("cpu")[:4])
    step = make_train_step(loss_fn, opt, mesh, donate=False, zero1=True)
    profile.start(tmp_path)
    try:
        state = step.place(params, None, batch)
        jax.block_until_ready(step(*state))
    finally:
        path = profile.stop()
    assert set(profile.HOST_SPANS) <= _host_event_names(path)


# --------------------------------------------------------------------------
# The kernels' names
# --------------------------------------------------------------------------

def _kernel_names(jaxpr_text):
    return {name for name in profile.KERNELS
            if re.search(r"name=%s\b" % name, jaxpr_text)}


# `vmem_budget` -> the kernels of a forward and backward: everything
# resident makes the backward one kernel, nothing resident the two of old.
@pytest.mark.parametrize("budget,expected", [
    (2 ** 40, {profile.FLASH_FWD, profile.FLASH_BWD}),
    (0, {profile.FLASH_FWD, profile.FLASH_DQ, profile.FLASH_DKV}),
])
def test_flash_kernels_carry_their_names(budget, expected):
    from horovod_tpu.ops.flash_attention import (_pallas_backward,
                                                 _pallas_forward_lse)
    q = jnp.ones((1, 2, 128, 64), jnp.float32)

    def grads(q, k, v):
        out, lse = _pallas_forward_lse(q, k, v, 0.125, True, True,
                                       vmem_budget=budget)
        return _pallas_backward(q, k, v, out, lse, out, 0.125, True, True,
                                vmem_budget=budget)

    assert _kernel_names(str(jax.make_jaxpr(grads)(q, q, q))) == expected

    def loss(q, k, v):
        return _flash(q, k, v, 0.125, True, True, None).sum()

    # What a model's call runs: the default budget, which this shape fits.
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    assert _kernel_names(text) == {profile.FLASH_FWD, profile.FLASH_BWD}


# A benchmark cell's shape, whose backward is one kernel, and L=8192,
# where the budget keeps the two.
@pytest.mark.parametrize("B,L,bwd_names", [
    (2, 2048, {profile.FLASH_BWD}),
    (1, 8192, {profile.FLASH_DQ, profile.FLASH_DKV}),
])
@pytest.mark.parametrize("backward", [False, True])
def test_flash_plan_under_hvd_profile_is_the_kernels_own(backward, B, L,
                                                         bwd_names):
    """`hvd.profile.flash_plan` is the plan the kernels run (the ops
    module imports `profile`, so the import is made at the call), keyed by
    the kernels' names, on both sides of the backward's choice."""
    from horovod_tpu.ops import flash_attention as fa

    fa = sys.modules[fa.__module__]  # the module, not the function
    got = profile.flash_plan(B, 16, L, 128, backward=backward)
    assert got == fa.flash_plan(B, 16, L, 128, backward=backward)
    assert set(got) == (bwd_names if backward else {profile.FLASH_FWD})
    assert all(p.path == "resident" for p in got.values())


# (B, L, D, V, chunk) -> (rows, iterations): the two LM cells' shapes cut
# alike though one passes B=2 and the other B=1; `chunk=L` stays one shot; a
# caller's B * chunk past the budget is kept; B * L = 3 * 1021 has no
# divisor under the budget's 1334 rows but 1021, which is B * chunk.
LOSS_PLANS = {
    "lm1b4": ((2, 2048, 2048, 50304, 512), (1024, 4)),
    "olmoe1b7": ((1, 4096, 2048, 50304, 512), (1024, 4)),
    "one_shot": ((1, 4096, 2048, 50304, 4096), (4096, 1)),
    "callers_chunk_past_the_budget": ((8, 2048, 2048, 50304, 512),
                                      (4096, 4)),
    "no_divisor_but_the_callers": ((1, 3063, 2048, 50304, 1021),
                                   (1021, 3)),
    "small_vocabulary_one_iteration": ((8, 32, 16, 64, 16), (256, 1)),
    "one_token": ((1, 1, 2048, 50304, 1), (1, 1)),
}


@pytest.mark.parametrize("case", list(LOSS_PLANS))
def test_loss_plan_under_hvd_profile_is_the_losss_own(case):
    """`hvd.profile.loss_plan` is the plan `chunked_softmax_cross_entropy`
    runs: rows from B * L, V and the budget, never fewer than B * chunk,
    three passes of the head, and the bytes a chunk and the residuals take."""
    from horovod_tpu.ops import losses

    (B, L, D, V, chunk), (rows, iterations) = LOSS_PLANS[case]
    plan = profile.loss_plan(B, L, D, V, chunk, jnp.bfloat16)
    assert plan == losses.loss_plan(B, L, D, V, chunk, jnp.bfloat16)
    assert (plan["rows"], plan["iterations"]) == (rows, iterations)
    assert plan["head_passes"] == 3
    assert plan["rows"] >= B * chunk and B * L % plan["rows"] == 0
    assert plan["logits_bytes"] == 2 * rows * V
    assert plan["logits_bytes"] <= max(2 * B * chunk * V,
                                       losses.LOGITS_BUDGET_BYTES // 2)
    assert plan["residual_bytes"] == 4 * D * V + 2 * B * L * D


def test_loss_plan_rejects_what_the_loss_rejects():
    with pytest.raises(ValueError, match="divisible"):
        profile.loss_plan(1, 10, 4, 7, 3)


def test_ring_kernels_carry_their_three_names():
    B, H, L, D = 1, 2, 128, 64
    q = jnp.ones((B * H, L, D), jnp.float32)
    o = jnp.zeros((B * H, L, D), jnp.float32)
    stripe = jnp.zeros((B * H, L, 8), jnp.float32)

    def forward(q, k, v):
        return flash_ring_step(q, k, v, o, stripe, stripe, 0, 0,
                               interpret=True)

    def backward(q, k, v):
        return flash_ring_bwd_step(q, k, v, q, stripe, stripe, o, o, o,
                                   0, 0, interpret=True)

    text = str(jax.make_jaxpr(forward)(q, q, q)) + str(
        jax.make_jaxpr(backward)(q, q, q))
    assert _kernel_names(text) == {profile.RING_ATTN, profile.RING_ATTN_DQ,
                                   profile.RING_ATTN_DKV}


def _name_choices(value):
    """The expressions a `name=` may evaluate to: both arms of `a if c else
    b` (one call site that carries two of the program's names)."""
    if isinstance(value, ast.IfExp):
        return _name_choices(value.body) + _name_choices(value.orelse)
    return [ast.unparse(value)]


def _pallas_call_names(path):
    """The `name=` keyword of every `pl.pallas_call(...)` in a source file
    (each arm of a conditional one), None where one has no name."""
    with open(path) as f:
        tree = ast.parse(f.read())
    out = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "pallas_call"):
            named = [kw.value for kw in node.keywords if kw.arg == "name"]
            out.extend(_name_choices(named[0]) if named else [None])
    return out


def _kernel_sources():
    ops = os.path.join(REPO_ROOT, "horovod_tpu", "ops")
    files = sorted(os.path.join(ops, f) for f in os.listdir(ops)
                   if f.endswith(".py"))
    return files + [os.path.join(REPO_ROOT, "horovod_tpu", "parallel",
                                 "ring.py")]


def test_every_pallas_call_has_one_of_the_programs_names():
    seen = []
    for path in _kernel_sources():
        for name in _pallas_call_names(path):
            assert name is not None, "%s: a pl.pallas_call without name=" % path
            assert name.startswith("profile."), (path, name)
            seen.append(getattr(profile, name.split(".", 1)[1]))
    assert sorted(seen) == sorted(profile.KERNELS)  # each name exactly once
