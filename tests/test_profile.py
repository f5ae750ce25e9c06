"""`hvd.profile` (docs/TRACING.md, "The in-`jit` step"): the profiler control
on the CPU backend, and the names the program puts on the train step —
scopes in the lowered step's scope paths, `name=` on every Pallas kernel —
which change no number and put no Python on the per-step path; and the
record of getting going, which is kept in memory all the time."""

import ast
import contextlib
import json
import os
import re
import subprocess
import sys
import threading

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
# What attention does outside its kernels, and a Mamba-2 mixer outside its
# scan: five scopes where the work happens, and how far a fusion keeps them
# --------------------------------------------------------------------------

_INNER_BASE = dict(vocab_size=VOCAB, num_layers=2, num_heads=2, embed_dim=16,
                   mlp_dim=32, max_seq_len=LENGTH, attention="dense",
                   dtype=jnp.float32)
# case: (what the configuration adds, the parts its attention holds each with
# a module or an operation that must lie under it, the attention's or the
# mixer's own parameters in block_0)
INNER_CASES = {
    "plain": (
        {},
        {profile.ATTN_PROJ: ("query", "key", "value", "out"),
         profile.ATTN_ROPE: ("cos", "sin")},
        ["key", "out", "query", "value"]),
    "head_norm": (
        dict(num_kv_heads=1, head_dim=8, qk_norm="head"),
        {profile.ATTN_PROJ: ("query", "out"),
         profile.ATTN_NORM: ("q_norm", "k_norm"),
         profile.ATTN_ROPE: ("cos",)},
        ["k_norm", "key", "out", "q_norm", "query", "value"]),
    "whole_norm": (
        dict(qk_norm=True),
        {profile.ATTN_PROJ: ("key",),
         profile.ATTN_NORM: ("q_norm", "k_norm"),
         profile.ATTN_ROPE: ("sin",)},
        ["k_norm", "key", "out", "q_norm", "query", "value"]),
    "yarn_kinds": (
        dict(num_kv_heads=1, head_dim=8, qk_norm="head",
             attention_types=("window", "full"), attention_window=4,
             rope_base=500000.0, rope_yarn=models.Yarn(16.0, 32.0, 1.0, 8)),
        {profile.ATTN_PROJ: ("value", "out"),
         profile.ATTN_NORM: ("q_norm",),
         profile.ATTN_ROPE: ("cos",)},
        ["k_norm", "key", "out", "q_norm", "query", "value"]),
    "latent": (
        dict(kv_lora_rank=8, q_lora_rank=12, qk_nope_dim=8, qk_rope_dim=4,
             v_head_dim=8, rope_yarn=models.Yarn(64.0, 32, 1, 4096, 1.0, 1.0)),
        {profile.ATTN_PROJ: ("q_a", "q_b", "kv_a", "kv_b", "out"),
         profile.ATTN_NORM: ("q_norm", "kv_norm"),
         profile.ATTN_ROPE: ("cos", "slice")},
        ["kv_a", "kv_b", "kv_norm", "out", "q_a", "q_b", "q_norm"]),
    "ssm": (
        dict(num_kv_heads=1, head_dim=8, rotary=False,
             layer_types=("ssm", "attn"), ssm_heads=4, ssm_head_dim=8,
             ssm_groups=2, ssm_state=8, ssm_conv=4, ssm_chunk=8),
        {profile.ATTN_PROJ: ("query", "out")},
        ["A_log", "D", "conv_bias", "conv_kernel", "dt_bias", "in_proj",
         "norm", "out_proj"]),
}


def _inner_model(case):
    model = models.Transformer(
        models.TransformerConfig(**dict(_INNER_BASE, **INNER_CASES[case][0])))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, LENGTH), 0, VOCAB)
    params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]
    return model, params, tokens


def _inner_grad(case):
    model, params, tokens = _inner_model(case)
    return jax.jit(jax.grad(lambda p: jnp.sum(jnp.square(
        model.apply({"params": p}, tokens))))), params


def _inner_paths(case):
    fun, params = _inner_grad(case)
    text = fun.lower(params).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


def _tokens(path):
    return [t for t in re.split(r"[/()]", path) if t]


@pytest.mark.parametrize("case", list(INNER_CASES))
def test_attention_names_what_it_does_outside_its_kernels(case):
    paths = [_tokens(p) for p in _inner_paths(case)]
    under_attn = [t for t in paths if profile.BLOCK in t and "attn" in t]
    wanted = INNER_CASES[case][1]
    for part in profile.ATTN_PARTS:
        held = [t for t in under_attn if part in t]
        if part not in wanted:
            assert not held, (part, held[:1])  # no such work, no such name
            continue
        # the part lies INSIDE flax's `attn`, in both directions, and holds
        # the modules and operations it is named for
        assert all(t.index("attn") < t.index(part) for t in held)
        assert any("transpose" in t for t in held), part
        assert any("transpose" not in t for t in held), part
        for what in wanted[part]:
            assert any(x.startswith(what) for t in held
                       for x in t[t.index(part) + 1:]), (part, what)
    for t in paths:
        # the parts stand beside each other, and nowhere but under `attn`
        parts = [x for x in t if x in profile.ATTN_PARTS]
        assert len(set(parts)) <= 1, t
        assert not parts or "attn" in t, t
    # what is left directly under `attn` (the scores and the softmax of the
    # dense path here; reshapes and copies around the kernels on the chip)
    # is under none of them: it is the reducer's `rest`
    rest = [t for t in under_attn if not set(t) & set(profile.ATTN_PARTS)]
    assert any(x.startswith(("exp", "reduce_max")) for t in rest for x in t)
    # not model scopes: the half still reads `hvd_block/attn`
    assert not set(profile.ATTN_PARTS) & set(profile.MODEL_SCOPES)


def test_a_mixer_names_what_it_does_outside_its_scan():
    paths = [_tokens(p) for p in _inner_paths("ssm")]
    under = [t for t in paths if profile.SSM in t]
    assert profile.SSM_PROJ in profile.SSM_SCOPES
    assert profile.SSM_GATE in profile.SSM_SCOPES
    for part, names in ((profile.SSM_PROJ, ("in_proj", "out_proj")),
                        (profile.SSM_GATE, ("softplus", "silu", "rsqrt",
                                            "exp", "square"))):
        held = [t for t in under if part in t]
        assert all(t.index(profile.SSM) < t.index(part) for t in held)
        assert any("transpose" in t for t in held), part
        assert any("transpose" not in t for t in held), part
        for what in names:
            assert any(x.startswith(what) for t in held
                       for x in t[t.index(part) + 1:]), (part, what)
    # the convolution and the scan stay outside both, and nothing of the two
    # lies outside the mixer
    new = {profile.SSM_PROJ, profile.SSM_GATE}
    for t in paths:
        assert not new & set(t) or profile.SSM in t, t
        if profile.SSD in t or profile.SSM_CONV in t:
            assert not new & set(t), t
    assert any(profile.SSD in t for t in under)
    assert any(profile.SSM_CONV in t for t in under)


@pytest.mark.parametrize("case", list(INNER_CASES))
def test_the_inner_scopes_change_no_parameter(case, monkeypatch):
    _, named, _ = _inner_model(case)
    mixer = "ssm" if case == "ssm" else "attn"
    assert sorted(named["block_0"][mixer]) == INNER_CASES[case][2]
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    _, bare, _ = _inner_model(case)
    assert jax.tree_util.tree_structure(named) \
        == jax.tree_util.tree_structure(bare)
    for a, b in zip(jax.tree_util.tree_leaves(named),
                    jax.tree_util.tree_leaves(bare)):
        np.testing.assert_array_equal(a, b)


# One clean fusion (a projection's convert beside its dot), one mixed (a
# norm's scaling fused into a rotation, part of it as a fusion of its own
# inside, the way libtpu fuses a producer), one with none of the scopes, and
# an instruction that is no fusion: the text a compiled step has, cut down.
_FUSED_TEXT = """HloModule jit_shard_step, entry_computation_layout={()->f32[]}

%fused_computation.1 (param_0.1: bf16[8,16], param_1.2: bf16[16,4]) -> bf16[8,4] {
  %param_0.1 = bf16[8,16]{1,0} parameter(0)
  %param_1.2 = bf16[16,4]{1,0} parameter(1)
  %convert.3 = f32[8,16]{1,0} convert(%param_0.1), metadata={op_name="jit(shard_step)/hvd_fwd_bwd/jvp(Transformer)/hvd_block/block_0/attn/hvd_attn_proj/query/convert_element_type"}
  ROOT %dot.4 = bf16[8,4]{1,0} dot(%convert.3, %param_1.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(shard_step)/hvd_fwd_bwd/jvp(Transformer)/hvd_block/block_0/attn/hvd_attn_proj/query/dot_general" source_file="t.py" source_line=7}
}

%fused_computation.9.clone (param_0.9: f32[8,4]) -> f32[8,4] {
  %param_0.9 = f32[8,4]{1,0} parameter(0)
  %rsqrt.9 = f32[8,4]{1,0} rsqrt(%param_0.9), metadata={op_name="jit(shard_step)/hvd_fwd_bwd/jvp(Transformer)/hvd_block/block_0/attn/hvd_attn_norm/q_norm/rsqrt"}
  ROOT %multiply.9 = f32[8,4]{1,0} multiply(%rsqrt.9, %param_0.9), metadata={op_name="jit(shard_step)/hvd_fwd_bwd/jvp(Transformer)/hvd_block/block_0/attn/hvd_attn_norm/q_norm/mul"}
}

%fused_computation.2 (param_0.5: bf16[8,4], param_1.6: f32[4]) -> (bf16[8,4], f32[8]) {
  %param_0.5 = bf16[8,4]{1,0} parameter(0)
  %param_1.6 = f32[4]{0} parameter(1)
  %constant.7 = f32[] constant(2)
  %multiply.8 = f32[8,4]{1,0} multiply(%param_0.5, %param_0.5), metadata={op_name="jit(shard_step)/hvd_fwd_bwd/jvp(Transformer)/hvd_block/block_0/attn/hvd_attn_norm/q_norm/mul"}
  %reduce.9 = f32[8]{0} reduce(%multiply.8, %constant.7), dimensions={1}, to_apply=%add, metadata={op_name="jit(shard_step)/hvd_fwd_bwd/jvp(Transformer)/hvd_block/block_0/attn/hvd_attn_norm/q_norm/reduce_sum"}
  %fusion.9.clone = f32[8,4]{1,0} fusion(%multiply.8), kind=kLoop, calls=%fused_computation.9.clone
  %cosine.10 = f32[8,4]{1,0} cosine(%fusion.9.clone), metadata={op_name="jit(shard_step)/hvd_fwd_bwd/transpose(jvp(Transformer))/hvd_block/block_0/attn/hvd_attn_rope/cos"}
  %bitcast.11 = f32[8,4]{1,0} bitcast(%cosine.10)
  ROOT %tuple.12 = (bf16[8,4]{1,0}, f32[8]{0}) tuple(%bitcast.11, %reduce.9)
}

%fused_computation.3 (param_0.13: f32[8]) -> f32[8] {
  %param_0.13 = f32[8]{0} parameter(0)
  ROOT %negate.14 = f32[8]{0} negate(%param_0.13), metadata={op_name="jit(shard_step)/hvd_optimizer/neg"}
}

ENTRY %main.20 (Arg_0.1: bf16[8,16], Arg_1.2: bf16[16,4], Arg_2.3: f32[4]) -> f32[8] {
  %Arg_0.1 = bf16[8,16]{1,0} parameter(0)
  %Arg_1.2 = bf16[16,4]{1,0} parameter(1)
  %Arg_2.3 = f32[4]{0} parameter(2)
  %fusion.1 = bf16[8,4]{1,0} fusion(%Arg_0.1, %Arg_1.2), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(shard_step)/hvd_fwd_bwd/jvp(Transformer)/hvd_block/block_0/attn/hvd_attn_proj/query/dot_general"}
  %fusion.2 = (bf16[8,4]{1,0}, f32[8]{0}) fusion(%fusion.1, %Arg_2.3), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(shard_step)/hvd_fwd_bwd/transpose(jvp(Transformer))/hvd_block/block_0/attn/hvd_attn_rope/cos"}
  %get-tuple-element.15 = f32[8]{0} get-tuple-element(%fusion.2), index=1
  %exponential.16 = f32[8]{0} exponential(%get-tuple-element.15), metadata={op_name="jit(shard_step)/hvd_fwd_bwd/jvp(Transformer)/hvd_block/block_0/attn/hvd_attn_norm/exp"}
  ROOT %fusion.3 = f32[8]{0} fusion(%exponential.16), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(shard_step)/hvd_optimizer/neg"}
}
"""


def test_fused_scopes_tells_a_clean_fusion_from_a_mixed_one():
    assert profile.fused_scopes(_FUSED_TEXT, profile.ATTN_PARTS) == {
        "fusion.1": {"scope": profile.ATTN_PROJ,
                     "inner": {profile.ATTN_PROJ: 2}, "mixed": False},
        # counted where its own name says (the rotation), though four of
        # its seven instructions are the norm's (two of them inside a fusion
        # of its own, which is counted by what IT holds) and two carry no
        # name
        "fusion.2": {"scope": profile.ATTN_ROPE,
                     "inner": {profile.ATTN_NORM: 4, profile.ATTN_ROPE: 1,
                               None: 2}, "mixed": True}}
    # of the scopes asked for alone; with none of them, nothing
    assert profile.fused_scopes(_FUSED_TEXT, (profile.ATTN_NORM,)) == {
        "fusion.2": {"scope": None, "inner": {profile.ATTN_NORM: 4, None: 3},
                     "mixed": False}}
    # the innermost of the scopes on a path is the one that counts, so the
    # block's scope may be asked for beside the parts inside it
    wide = profile.fused_scopes(_FUSED_TEXT,
                                profile.ATTN_PARTS + (profile.BLOCK,))
    assert wide["fusion.1"]["inner"] == {profile.ATTN_PROJ: 2}
    assert wide["fusion.2"]["scope"] == profile.ATTN_ROPE
    assert profile.fused_scopes(_FUSED_TEXT, (profile.BLOCK,))["fusion.1"] \
        == {"scope": profile.BLOCK, "inner": {profile.BLOCK: 2},
            "mixed": False}
    assert profile.fused_scopes(_FUSED_TEXT, (profile.SSM_GATE,)) == {}
    assert profile.fused_scopes("", profile.ATTN_PARTS) == {}


def test_product_fusions_says_what_rides_with_a_matmul():
    query = ("jit(shard_step)/hvd_fwd_bwd/jvp(Transformer)/hvd_block/block_0/"
             "attn/hvd_attn_proj/query/dot_general")
    # the fusion with the reduce holds no product and is not listed
    assert profile.product_fusions(_FUSED_TEXT) == {
        "fusion.1": {"op_name": query, "results": ["bf16[8,4]"],
                     "reduces": []}}
    # a product beside a reduction: both results, and the reduce's own (a
    # producer fusion inside is counted by what it holds)
    joined = profile.product_fusions(
        _FUSED_TEXT.replace(" cosine(", " convolution("))
    assert set(joined) == {"fusion.1", "fusion.2"}
    assert joined["fusion.2"]["results"] == ["bf16[8,4]", "f32[8]"]
    assert joined["fusion.2"]["reduces"] == ["f32[8]"]
    assert joined["fusion.2"]["op_name"].endswith("hvd_attn_rope/cos")
    assert profile.product_fusions("") == {}


@pytest.mark.parametrize("case", ["head_norm", "ssm"])
def test_fused_scopes_reads_a_compiled_step(case):
    fun, params = _inner_grad(case)
    text = fun.lower(params).compile().as_text()
    scopes = profile.ATTN_PARTS + profile.SSM_SCOPES[1:]
    found = profile.fused_scopes(text, scopes)
    assert found
    for name, fusion in found.items():
        assert re.search(r"%?" + re.escape(name) + r" = .* fusion\(", text)
        held = [s for s in fusion["inner"] if s is not None]
        assert set(held) <= set(scopes) and fusion["scope"] in scopes + (None,)
        assert fusion["mixed"] == (len(held) > 1)
        assert held or fusion["scope"] is not None
        assert all(n > 0 for n in fusion["inner"].values())
    # XLA's CPU compiler fuses the elementwise work as the chip's does: the
    # rotation's, or the gate's, is there under its own name
    wanted = profile.SSM_GATE if case == "ssm" else profile.ATTN_ROPE
    assert any(f["scope"] == wanted and f["inner"].get(wanted)
               for f in found.values())


# --------------------------------------------------------------------------
# Getting going: the spans a process makes a bounded number of times
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def getting_going(tmp_path_factory):
    """The record of `tests/profile_phases_worker.py`, run twice on one
    fresh compile cache: {"cold": ..., "warm": ...}."""
    from conftest import clean_worker_env

    env = clean_worker_env({"JAX_COMPILATION_CACHE_DIR": str(
        tmp_path_factory.mktemp("compile_cache"))})
    out = {}
    for run in ("cold", "warm"):
        done = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tests",
                                          "profile_phases_worker.py")],
            env=env, cwd=REPO_ROOT, capture_output=True, text=True,
            timeout=300)
        assert done.returncode == 0, done.stderr[-2000:]
        out[run] = json.loads(done.stdout.splitlines()[-1])
    return out


@pytest.fixture(autouse=True)
def room_in_the_record(monkeypatch):
    """The record is the process's, and a worker of the suite may have
    filled it with other files' compiles before these tests run."""
    monkeypatch.setattr(profile, "PHASES_CAP", len(profile.phases()) + 4096)


def _named(record, name, fun_name=None):
    return [(i, p) for i, p in enumerate(record) if p["name"] == name
            and fun_name in (None, p["attrs"].get("fun_name"))]


def test_record_holds_every_setup_span_under_its_parent(getting_going):
    record = getting_going["cold"]["phases"]
    assert {p["name"] for p in record} == set(profile.SETUP_SPANS)
    (init, p), = _named(record, profile.SPAN_INIT)
    assert p["parent"] is None
    for inside in (profile.SPAN_NATIVE_BUILD, profile.SPAN_NATIVE_INIT):
        (_, child), = _named(record, inside)
        assert child["parent"] == init
    (_, made), = _named(record, profile.SPAN_MAKE_STEP)
    assert made["parent"] is None
    assert len(_named(record, profile.SPAN_PLACE)) == 2
    for stage in (profile.SPAN_JAX_TRACE, profile.SPAN_JAX_LOWER,
                  profile.SPAN_JAX_COMPILE):
        # the first call and the second batch shape
        assert len(_named(record, stage, profile.STEP_FUN_NAME)) == 2, stage
    assert getting_going["cold"]["dropped"] == 0


@pytest.mark.parametrize("run", ["cold", "warm"])
def test_children_lie_inside_their_parents(getting_going, run):
    record = getting_going[run]["phases"]
    inside = [0] * len(record)
    for p in record:
        assert p["end_ns"] >= p["start_ns"]
        if p["parent"] is not None:
            parent = record[p["parent"]]
            assert parent["start_ns"] <= p["start_ns"]
            assert p["end_ns"] <= parent["end_ns"]
            inside[p["parent"]] += p["end_ns"] - p["start_ns"]
    for p, children in zip(record, inside):  # self time is not negative
        assert children <= p["end_ns"] - p["start_ns"], p


def test_a_steps_nested_traces_are_counted_and_not_kept(getting_going):
    record = getting_going["cold"]["phases"]
    (_, first), _ = _named(record, profile.SPAN_JAX_TRACE,
                           profile.STEP_FUN_NAME)
    assert first["attrs"]["nested"] > 0  # `jnp.mean` and the like
    for p in record:
        if p["parent"] is not None:
            assert record[p["parent"]]["name"].startswith("hvd_")


def test_compile_is_a_miss_then_a_hit_on_one_cache(getting_going):
    for run, cache in (("cold", "miss"), ("warm", "hit")):
        found = _named(getting_going[run]["phases"],
                       profile.SPAN_JAX_COMPILE, profile.STEP_FUN_NAME)
        assert [p["attrs"]["cache"] for _, p in found] == [cache] * 2
        for _, p in found:
            assert (p["attrs"]["retrieval_s"] is not None) == (cache == "hit")
        count = getting_going[run]["compiles"][profile.STEP_FUN_NAME]
        assert (count["hits"], count["misses"]) == (
            (0, 2) if cache == "miss" else (2, 0))


def test_second_batch_shape_is_the_steps_one_recompile(getting_going):
    ran = getting_going["cold"]
    count = ran["compiles"][profile.STEP_FUN_NAME]
    assert count["requests"] == 2 and count["recompiles"] == 1
    (at,) = count["recompiled_at_ns"]
    (_, first), (i, second) = _named(ran["phases"], profile.SPAN_JAX_COMPILE,
                                     profile.STEP_FUN_NAME)
    assert at == second["start_ns"] > first["end_ns"]
    # the repeated call came before the second shape, and compiled nothing
    assert i >= ran["before_second_shape"]
    assert all(v > 0 for v in count["seconds"].values())
    # `compiles` of a part of the record: the first shape alone
    alone = profile.compiles(ran["phases"][:ran["before_second_shape"]])
    assert alone[profile.STEP_FUN_NAME]["recompiles"] == 0


def test_record_survives_shutdown(getting_going):
    assert getting_going["cold"]["survived_shutdown"]


def test_step_fun_name_is_the_jitted_functions():
    step, state = _placed_step("transformer", "plain")
    text = step.lower(*state).as_text()
    assert "module @jit_%s " % profile.STEP_FUN_NAME in text


def test_phase_is_recorded_while_idle_and_a_span_is_not():
    assert not profile.active()
    before = len(profile.phases())
    with profile.span("per_call"):
        with profile.phase("outer_phase"):
            with profile.phase("inner_phase"):
                pass
    outer, inner = profile.phases()[before:]
    assert (outer["name"], outer["parent"]) == ("outer_phase", None)
    assert (inner["name"], inner["parent"]) == ("inner_phase", before)
    assert outer["start_ns"] <= inner["start_ns"] <= inner["end_ns"] \
        <= outer["end_ns"]


def test_phase_of_another_thread_is_not_a_child_of_this_ones():
    before = len(profile.phases())

    def other():
        with profile.phase("other_thread"):
            pass

    with profile.phase("this_thread"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    mine, theirs = profile.phases()[before:]
    assert theirs["name"] == "other_thread" and theirs["parent"] is None


def test_phases_of_many_threads_lose_nothing():
    threads, depth, rounds = 32, 3, 10
    before = len(profile.phases())

    def work(k):
        for _ in range(rounds):
            with contextlib.ExitStack() as stack:
                for d in range(depth):
                    stack.enter_context(profile.phase("t%d_d%d" % (k, d)))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=work, args=(k,))
                for k in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    record = profile.phases()
    assert len(record) == before + threads * depth * rounds
    for p in record[before:]:
        assert p["end_ns"] is not None
        thread, d = p["name"].split("_d")
        if d == "0":
            assert p["parent"] is None
        else:  # the span one level up, of the same thread
            assert record[p["parent"]]["name"] == "%s_d%d" % (thread,
                                                             int(d) - 1)


def test_phase_agrees_with_its_twin_on_the_host_plane(tmp_path):
    before = len(profile.phases())
    profile.start(tmp_path)
    try:
        with profile.phase("traced_phase"):
            _busy()
    finally:
        path = profile.stop()
    # (a first `_busy()` compiles inside the phase: spans of its own)
    (kept,) = [p for p in profile.phases()[before:]
               if p["name"] == "traced_phase"]
    data = jax.profiler.ProfileData.from_file(path)
    (began,) = [dict(plane.stats)["profile_start_time"]
                for plane in data.planes if plane.name == "Task Environment"]
    (event,) = [e for plane in data.planes if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events
                if e.name == "traced_phase"]
    assert abs(began + event.start_ns - kept["start_ns"]) < 1e6
    assert abs(event.duration_ns
               - (kept["end_ns"] - kept["start_ns"])) < 1e6


def test_cap_holds_and_counts_what_it_drops(monkeypatch):
    held, dropped = len(profile.phases()), profile.dropped()
    monkeypatch.setattr(profile, "PHASES_CAP", held + 1)
    with profile.phase("kept"):
        with profile.phase("dropped_1"):
            with profile.phase("dropped_2"):
                pass
    assert len(profile.phases()) == held + 1
    assert profile.dropped() == dropped + 2
    assert profile.phases()[-1]["end_ns"] is not None
    monkeypatch.setattr(profile, "PHASES_CAP", held + 2)
    with profile.phase("after"):  # nothing was left open
        pass
    assert profile.phases()[-1]["parent"] is None


def test_listening_twice_adds_one_listener():
    from jax._src import monitoring

    _placed_step("transformer", "plain")  # `make_train_step` listens
    profile.listen()
    for listeners, ours in (
            (monitoring.get_scalar_listeners(), profile._on_runtime_begin),
            (monitoring.get_event_time_span_listeners(),
             profile._on_runtime_end),
            (monitoring.get_event_listeners(), profile._on_cache_event),
            (monitoring.get_event_duration_listeners(),
             profile._on_cache_event)):
        assert listeners.count(ours) == 1


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
        return _flash(q, k, v, 0.125, True, True).sum()

    # What a model's call runs: the default budget, which this shape fits.
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    assert _kernel_names(text) == {profile.FLASH_FWD, profile.FLASH_BWD}


# A benchmark cell's shape, whose backward is one kernel held by the k
# block, L=8192, where it is held by the q block, and L=16384, where the
# budget keeps the two (dK/dV gridded).
@pytest.mark.parametrize("B,L,bwd_names", [
    (2, 2048, {profile.FLASH_BWD}),
    (1, 8192, {profile.FLASH_BWD}),
    (1, 16384, {profile.FLASH_DQ, profile.FLASH_DKV}),
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
    assert all(p.path == ("gridded" if name == profile.FLASH_DKV
                          else "resident") for name, p in got.items())


# A benchmark cell's flash call -> its plans, field for field but `held`:
# (path, block_q, block_k, grid, grid_steps, resident_bytes, vmem_bytes,
# vmem_limit_bytes, tiles visited, masked, skipped). The five cells whose
# backward is one kernel held by the k block stand as PR 44's tree gave
# them; SDAR's forward too. Since PR 49 SDAR's backward and Nemotron's one
# attention call (16 query heads on one kv head) are the one kernel held by
# the q block (k, v, dk, dv and the two accumulators: 24 and 12 MiB), as is
# a grouped call at L=8192; a plain one at 16384 fits no resident form of
# the backward but dQ's. Since PR 56 so is Kanana's call, scores of two
# products at 8192 positions (one buffer a whole-sequence operand, the shared
# key's gradient summed over the heads in a third accumulator: 24 MiB).
_MiB = 2 ** 20
_SDAR_TILES = (1280, 384, 2816)
CELL_PLANS = {
    "lm1b4_1chip": (dict(B=2, H=16, L=2048), {
        "hvd_flash_fwd": ("resident", 512, 512, (32, 4), 128, 2 * _MiB,
                          3 * _MiB, 16 * _MiB, None, None, None),
        "hvd_flash_bwd": ("resident", 512, 1024, (32, 2), 64, 8 * _MiB,
                          10 * _MiB, 30 * _MiB, None, None, None)}),
    "olmoe1b7_1chip": (dict(B=1, H=16, L=4096), {
        "hvd_flash_fwd": ("resident", 512, 512, (16, 8), 128, 4 * _MiB,
                          5 * _MiB, 16 * _MiB, None, None, None),
        "hvd_flash_bwd": ("resident", 512, 1024, (16, 4), 64, 16 * _MiB,
                          18 * _MiB, 40 * _MiB, None, None, None)}),
    "xing29b_1chip": (dict(B=1, H=32, L=4096, shared_dim=64), {
        "hvd_flash_fwd": ("resident", 512, 512, (32, 8), 256, 6 * _MiB,
                          7602176, 19 * _MiB, None, None, None),
        "hvd_flash_bwd": ("resident", 512, 1024, (32, 4), 128, 22 * _MiB,
                          25 * _MiB, 50 * _MiB, None, None, None)}),
    "kanana30b_1chip": (dict(B=1, H=32, L=8192, shared_dim=64), {
        "hvd_flash_fwd": ("resident", 512, 512, (32, 16), 512, 12 * _MiB,
                          13893632, 26 * _MiB, None, None, None),
        "hvd_flash_bwd": ("resident", 512, 1024, (32, 16), 512, 24 * _MiB,
                          27525120, 52 * _MiB, None, None, None)}),
    "sdar30b_1chip": (dict(B=1, H=32, L=8192, group=8, mask=(4096, 4)), {
        "hvd_flash_fwd": ("resident", 1024, 512, (4, 64), 256, 8 * _MiB,
                          10 * _MiB, 30 * _MiB) + _SDAR_TILES,
        "hvd_flash_bwd": ("resident", 1024, 512, (4, 64), 256, 24 * _MiB,
                          28835840, 52 * _MiB) + _SDAR_TILES}),
    "nemo3s120b_1chip": (dict(B=1, H=16, L=4096, group=16), {
        "hvd_flash_fwd": ("resident", 1024, 512, (1, 64), 64, 4 * _MiB,
                          6 * _MiB, 25 * _MiB, None, None, None),
        "hvd_flash_bwd": ("resident", 1024, 512, (1, 64), 64, 12 * _MiB,
                          16252928, 37 * _MiB, None, None, None)}),
    "grouped_L8192": (dict(B=2, H=6, L=8192, group=3), {
        "hvd_flash_bwd": ("resident", 1536, 512, (4, 16), 64, 24 * _MiB,
                          30670848, 63 * _MiB, None, None, None)}),
    "plain_L16384": (dict(B=1, H=16, L=16384), {
        "hvd_flash_dq": ("resident", 512, 512, (16, 32), 512, 16 * _MiB,
                         18612224, 31 * _MiB, None, None, None),
        "hvd_flash_dkv": ("gridded", 512, 1024, (16, 16, 32), 8192, 0,
                          4718592, None, None, None, None)}),
}
CELL_PLANS["lm1b4_dp4"] = CELL_PLANS["lm1b4_1chip"]
CELL_PLANS["ouro2b6_1chip"] = CELL_PLANS["olmoe1b7_1chip"]
# The calls whose one-kernel backward is held by the q block.
_Q_HELD_BWD = ("sdar30b_1chip", "nemo3s120b_1chip", "grouped_L8192",
               "kanana30b_1chip")


def _cell_plans(B, H, L, mask=None, **kw):
    from horovod_tpu.ops import BlockDiffusionMask

    mask = mask and BlockDiffusionMask(*mask)
    return {name: plan for backward in (False, True)
            for name, plan in profile.flash_plan(
                B, H, L, 128, backward=backward, mask=mask, **kw).items()}


@pytest.mark.parametrize("cell", sorted(CELL_PLANS))
def test_flash_plans_of_the_cells_are_the_parents(cell):
    call, expected = CELL_PLANS[cell]
    plans = _cell_plans(**call)
    assert set(expected) <= set(plans)
    assert ("hvd_flash_bwd" in plans) != ("hvd_flash_dq" in plans)
    for name, want in expected.items():
        got = plans[name]._asdict()
        # A k block is held by dK/dV and by the one-kernel backward in its
        # first form, a q block by the forward, dQ and the one kernel in its
        # second (the next two tests say when).
        assert got.pop("held") == (
            "k" if name == "hvd_flash_dkv" or (
                name == "hvd_flash_bwd" and cell not in _Q_HELD_BWD) else "q")
        assert got.pop("gate") is None  # PR 63's: no cell's plan here has one
        # The parent's fields, then PR 53's (`cut_k`, `subtiles_visited`,
        # `subtiles_masked`): with no rule None; under the cell's rule the
        # forward by k blocks alone, the backward's lone sub-tiles alone.
        values = tuple(got.values())
        assert values[:len(want)] == want, name
        assert values[len(want):] == (
            (None,) * 3 if "mask" not in call else
            (512,) + _SDAR_TILES[:2] if name == "hvd_flash_fwd" else
            (256, 2304, 512)), name


def test_flash_plan_holds_sdars_backward_by_the_q_block():
    """The block-diffusion cell's call: q, dO, dQ, lse and delta of a kv
    head's 8 query heads are 256 MiB with dQ's accumulator, so the backward
    is ONE kernel in its SECOND form: a q block a grid step on the forward's
    grid and blocks, k, v, dk and dv whole and two f32 accumulators in
    VMEM, 24 MiB, the tiles counted by the key runs of each q block. One
    byte less and it is dQ of old beside the gridded dK/dV."""
    from horovod_tpu.ops import BlockDiffusionMask, flash_attention as fa

    fa = sys.modules[fa.__module__]  # the module, not the function
    call = CELL_PLANS["sdar30b_1chip"][0]
    plans = _cell_plans(**call)
    assert sorted(plans) == ["hvd_flash_bwd", "hvd_flash_fwd"]
    bwd, fwd = plans["hvd_flash_bwd"], plans["hvd_flash_fwd"]
    assert (bwd.path, bwd.held, bwd.block_q, bwd.block_k, bwd.grid,
            bwd.grid_steps) == ("resident", "q", 1024, 512, (4, 64), 256)
    assert (bwd.block_q, bwd.block_k, bwd.grid) == (
        fwd.block_q, fwd.block_k, fwd.grid)
    assert (bwd.tiles_visited, bwd.tiles_masked,
            bwd.tiles_skipped) == _SDAR_TILES
    L, D = 8192, 128
    # k, v, dk, dv in bf16, two buffers each; two f32 accumulators, one
    assert bwd.resident_bytes == 2 * 4 * L * D * 2 + 2 * L * D * 4 \
        == fa.RESIDENT_VMEM_BUDGET
    # beside them a block of q, dO and dQ and of lse and delta, two buffers
    assert bwd.vmem_bytes == bwd.resident_bytes + 2 * (
        3 * 1024 * D * 2 + 2 * 1024 * 128 * 4) < bwd.vmem_limit_bytes
    two = fa.flash_plan(1, 32, L, D, 8, backward=True,
                        vmem_budget=bwd.resident_bytes - 1,
                        mask=BlockDiffusionMask(4096, 4))
    assert sorted(two) == ["hvd_flash_dkv", "hvd_flash_dq"]
    gridded = two["hvd_flash_dkv"]
    assert (gridded.path, gridded.held, gridded.grid, gridded.grid_steps,
            gridded.tiles_visited) == ("gridded", "k", (4, 16, 64), 4096,
                                       1280)


# (B, H, L, group) of calls outside the cells -> the backward's form where
# the one kernel held by the k block does not fit: a head group's rows
# multiply what a k-held form holds (3 KiB a row at D=128 in bf16 for dK/dV,
# more for the one kernel) and not what the one kernel held by the q block
# does (3 KiB a position), so it takes every call up to L=8192, and past it
# the backward is two kernels, dK/dV gridded.
@pytest.mark.parametrize("B,H,L,group,expected", [
    (2, 6, 8192, 3, ("hvd_flash_bwd", "resident", "q")),
    (1, 32, 2048, 8, ("hvd_flash_bwd", "resident", "q")),
    (1, 32, 4096, 4, ("hvd_flash_bwd", "resident", "q")),
    (1, 16, 4096, 2, ("hvd_flash_bwd", "resident", "q")),  # 12 MiB
    (1, 12, 8192, 6, ("hvd_flash_bwd", "resident", "q")),  # 24 MiB
    (1, 8, 16384, 2, ("hvd_flash_dkv", "gridded", "k")),   # 48 MiB q-held
    (1, 16, 8192, 1, ("hvd_flash_bwd", "resident", "q")),  # G1_L8192
])
def test_flash_plan_backward_form_outside_the_cells(B, H, L, group, expected):
    plans = profile.flash_plan(B, H, L, 128, group, backward=True)
    name, path, held = expected
    assert sorted(plans) == ([name] if name == "hvd_flash_bwd"
                             else ["hvd_flash_dkv", "hvd_flash_dq"])
    p = plans[name]
    assert (p.path, p.held) == (path, held)
    if name == "hvd_flash_bwd":
        # k, v, dk, dv in bf16, two buffers each; two f32 accumulators, one
        assert p.resident_bytes == 2 * 4 * L * 128 * 2 + 2 * L * 128 * 4
    else:
        assert plans["hvd_flash_dq"].path == "resident"
    # the grid's block axis counts blocks of the held side (the two can be
    # as many: `held` says which)
    assert p.grid[:2] == (B * H // group, L * group // p.block_q
                          if p.held == "q" else L // p.block_k)


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


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_the_kda_convolutions_kernels_carry_their_names(direction):
    """`ops.kda_conv.kda_qkv`'s two kernels, a tuple of their own in
    `KERNELS` (PR 64): the forward's program holds `hvd_kda_qkv` alone, the
    gradient's `hvd_kda_qkv_bwd` too."""
    from horovod_tpu.ops.kda_conv import kda_qkv
    proj = jnp.ones((1, 32, 3 * 128 + 8), jnp.float32)
    w = jnp.ones((4, 3 * 128), jnp.float32)

    def forward(proj, w):
        return sum(jnp.sum(t) for t in kda_qkv(proj, w, 1, 128,
                                               interpret=True))

    fn = forward if direction == "forward" else jax.grad(forward, (0, 1))
    assert _kernel_names(str(jax.make_jaxpr(fn)(proj, w))) == set(
        profile.KDA_CONV_KERNELS[:1 if direction == "forward" else 2])
    assert profile.KERNELS[-2:] == profile.KDA_CONV_KERNELS


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
