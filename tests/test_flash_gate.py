"""The head gate of gated attention INSIDE the flash kernels (PR 63):
`flash_attention(..., gate=)` multiplies head h's output at a position by one
f32 scalar. The forward kernels multiply a block's normalisers by the gates'
reciprocals where they finalize it (acc g / l = acc / (l / g)) and save the
gated rows' log-normaliser; the backward kernels are the ungated ones in
every form, handed that and delta / gate, which is the gate's gradient too.

Every case: a head group (1, 6, 8: the plain call and the two of
`laguna33b_1chip`) x a mask (the causal triangle and both rules) x a form of
the backward (the one kernel held by the q block, the one held by the k
block, the gridded pair), in Pallas' interpreter, against the dense masked
softmax times the gate: out, dQ, dK, dV and dgate. The gate drawn holds exact
zeros (zero rows, finite gradients, a zero returned for the gate's own); a
gate of ones gives the ungated call's values bit for bit; the plan names
where the product runs.

That an UNGATED call is the program it was is a statement about jaxprs: the
hashes below are the parent's, recorded by running this file as a script in a
checkout of it:

    JAX_PLATFORMS=cpu PYTHONPATH=<parent> python tests/test_flash_gate.py
"""

import hashlib
import importlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu import profile
from horovod_tpu.ops import BandMask, BlockDiffusionMask

fa = importlib.import_module("horovod_tpu.ops.flash_attention")

L, D, BQP, BK = 256, 128, 64, 64
MASKS = {"causal": None, "band": BandMask(100),
         "block_diffusion": BlockDiffusionMask(128, 4)}
HEADS = {1: (2, 2), 6: (6, 1), 8: (8, 1)}  # group: (heads, kv heads)
# form: the sides `_BWD_HELD` lets `flash_plan` try for the one kernel
FORMS = {"q-held": ("q",), "k-held": ("k",), "gridded": ()}
CASES = [(group, mask, form)
         for group in HEADS for mask in MASKS for form in FORMS]


def _case_id(case):
    return "group%d-%s-%s" % case


def _operands(group):
    H, G = HEADS[group]
    keys = jax.random.split(jax.random.PRNGKey(63 + group), 5)
    q, k, v, w = (jax.random.normal(key, s, jnp.float32) for key, s in zip(
        keys, [(1, H, L, D), (1, G, L, D), (1, G, L, D), (1, H, L, D)]))
    # A sigmoid's values, with exact zeros: a whole head's run of positions
    # (across q blocks) and scattered single ones.
    gate = jax.nn.sigmoid(2.0 * jax.random.normal(keys[4], (1, H, L)))
    gate = gate.at[0, H - 1, 40:140].set(0.0).at[0, :, ::37].set(0.0)
    return q, k, v, w, gate


def _budget(form, group, rule):
    """The VMEM budget under which `flash_plan` gives the case's form (with
    `_BWD_HELD` narrowed): everything resident, or for the gridded pair
    nothing (under a rule the forward and dQ exist resident only: dK/dV
    alone gridded)."""
    if form != "gridded":
        return 2 ** 40
    if rule is None:
        return 0
    H, _ = HEADS[group]
    return fa.flash_plan(1, H, L, D, group, jnp.float32, True, group * BQP,
                         BK, 2 ** 40, mask=rule)[
                             profile.FLASH_DKV].resident_bytes - 1


def _kernels(q, k, v, w, gate, group, rule, budget):
    """(out, dq, dk, dv[, dgate]) of the kernels in the interpreter."""
    kw = dict(block_q=group * BQP, block_k=BK, vmem_budget=budget,
              rule=rule, **({} if gate is None else {"gate": gate}))
    out, lse = fa._pallas_forward_lse(q, k, v, D ** -0.5, rule is None, True,
                                      **kw)
    return (out,) + tuple(fa._pallas_backward(
        q, k, v, out, lse, w, D ** -0.5, rule is None, True, **kw))


def _dense(q, k, v, w, gate, group, rule):
    pos = np.arange(L)
    seen = jnp.asarray(pos[:, None] >= pos[None, :] if rule is None else
                       rule.visible(pos[:, None], pos[None, :], np))

    def attend(q, k, v, gate):
        s = jnp.einsum("bhqd,bhkd->bhqk", q,
                       jnp.repeat(k, group, axis=1)) * D ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p,
                          jnp.repeat(v, group, axis=1)) * gate[..., None]

    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(attend, q, k, v, gate)
        return (out,) + vjp(w)


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_gated_kernels_equal_the_dense_form_times_the_gate(case,
                                                           monkeypatch):
    group, mask, form = case
    rule = MASKS[mask]
    H, _ = HEADS[group]
    monkeypatch.setattr(fa, "_BWD_HELD", FORMS[form])
    budget = _budget(form, group, rule)
    q, k, v, w, gate = _operands(group)

    # the plan: the forward takes the gate whatever its form, a backward
    # kernel of any form finds it in lse and delta; an ungated plan names no
    # gate and is otherwise the same
    for backward in (False, True):
        args = (1, H, L, D, group, q.dtype, backward, group * BQP, BK,
                budget)
        plans = fa.flash_plan(*args, mask=rule, gate=True)
        plain = profile.flash_plan(*args, mask=rule)
        assert sorted(plans) == sorted(plain)
        for name, p in plans.items():
            assert p.gate == ("lse" if backward else "kernel"), name
            assert plain[name].gate is None
            assert (p.path, p.held, p.block_q, p.block_k, p.grid) \
                == plain[name][:5]
            assert backward or p.vmem_bytes > plain[name].vmem_bytes
            assert not backward or p._replace(gate=None) == plain[name]
        if backward:
            assert sorted(plans) == (
                [profile.FLASH_DKV, profile.FLASH_DQ] if form == "gridded"
                else [profile.FLASH_BWD])
            assert {p.held for p in plans.values()} == (
                {"q", "k"} if form == "gridded" else {form[0]})

    got = _kernels(q, k, v, w, gate, group, rule, budget)
    want = _dense(q, k, v, w, gate, group, rule)
    assert len(got) == len(want) == 5
    zero = np.asarray(gate == 0.0)
    assert zero.any() and not zero.all()
    for g in got:
        assert np.isfinite(np.asarray(g)).all()
    for g, t in zip(got[:4], want):
        assert g.shape == t.shape
        np.testing.assert_allclose(g, t, rtol=2e-5, atol=2e-5)
    # a gate of zero: a row of zeros, and a zero for the gate's own gradient
    # (the dense form's is rowsum(dO o), which the model's sigmoid multiplies
    # by g (1 - g) = 0)
    assert not np.asarray(got[0])[zero].any()
    assert not np.asarray(got[4])[zero].any()
    np.testing.assert_allclose(np.asarray(got[4])[~zero],
                               np.asarray(want[4])[~zero],
                               rtol=2e-4, atol=2e-4)

    # a gate of ones: the ungated call's values, bit for bit
    ones = _kernels(q, k, v, w, jnp.ones_like(gate), group, rule, budget)
    plain = _kernels(q, k, v, w, None, group, rule, budget)
    assert len(plain) == 4
    for g, t in zip(ones, plain):
        assert np.array_equal(np.asarray(g), np.asarray(t))


@pytest.mark.parametrize("mask", sorted(MASKS))
def test_flash_attention_is_differentiable_in_its_gate(mask, monkeypatch):
    """The public call, [B, L, H, D] with the gate [B, L, H], through the
    kernels (`interpret` forced: the CPU's own path is the blockwise jnp
    form) and through that blockwise form: the same values and gradients,
    the gate's among them; and what the call refuses."""
    rule = MASKS[mask]
    q, k, v, w, gate = (jnp.moveaxis(x, 1, 2) for x in _operands(6))
    gate = jnp.maximum(gate, 1e-3)  # the blockwise form's gradient at 0 is
    kw = {"causal": True} if rule is None else {"mask": rule}  # not zero

    def loss(q, k, v, gate):
        return jnp.sum(fa.flash_attention(q, k, v, gate=gate, **kw) * w)

    want = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(q, k, v, gate)
    real = fa._flash_gated
    monkeypatch.setattr(
        fa, "_flash_gated", lambda q, k, v, gate, scale, causal, interpret,
        rule=None: real(q, k, v, gate, scale, causal, True, rule))
    got = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(q, k, v, gate)
    for g, t in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.shape == t.shape
        np.testing.assert_allclose(g, t, rtol=2e-4, atol=2e-4)
    with pytest.raises(ValueError, match="gate"):
        fa.flash_attention(q, k, v, gate=gate[..., :3], **kw)
    if rule is None:
        with pytest.raises(ValueError, match="gate"):
            fa.flash_attention(
                q, k[:, :, :1], v[:, :, :1], gate=gate,
                q_shared=q[..., :32], k_shared=k[:, :, :1, :32])
        with pytest.raises(ValueError, match="one score product"):
            fa.flash_plan(1, 6, L, D, 6, shared_dim=64, gate=True)


# --------------------------------------------------------------------------
# gate=None: the jaxpr the call had
# --------------------------------------------------------------------------

def _ungated_programs(gate_none):
    """{name: jaxpr text} of ungated calls, forward and backward, kernels'
    own jaxprs inside (interpret mode): the plain call whose backward is held
    by the k block, the grouped calls of the two kinds whose backward is held
    by the q block (the band's jitted, `_ruled_call`), and the public call on
    the CPU. ``gate_none``: what the public call is given beside its
    operands ({} in a checkout that has no such argument)."""
    def fwd_bwd(flash):
        def run(q, k, v, w):
            out, vjp = jax.vjp(flash, q, k, v)
            return (out,) + vjp(w)
        return run

    def text(fn, H, G, public=False):
        shape = (lambda h: (1, L, h, D)) if public else (
            lambda h: (1, h, L, D))
        args = [jnp.zeros(shape(h), jnp.float32) for h in (H, G, G, H)]
        with jax.default_matmul_precision("highest"):  # whatever a module set
            return re.sub(r"0x[0-9a-f]+", "0x",
                          str(jax.make_jaxpr(fn)(*args)))

    scale = D ** -0.5
    held = fa._BWD_HELD
    out = {"plain_k_held": text(fwd_bwd(
        lambda q, k, v: fa._flash(q, k, v, scale, True, True)), 2, 2)}
    fa._BWD_HELD = ("q",)
    try:
        out["group6_causal_q_held"] = text(fwd_bwd(
            lambda q, k, v: fa._flash(q, k, v, scale, True, True)), 6, 1)
        out["group8_band_q_held"] = text(fwd_bwd(
            lambda q, k, v: fa._flash(q, k, v, scale, False, True,
                                      BandMask(100))), 8, 1)
    finally:
        fa._BWD_HELD = held
    out["public_causal"] = text(fwd_bwd(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                           **gate_none)), 6, 1, public=True)
    out["public_band"] = text(fwd_bwd(
        lambda q, k, v: fa.flash_attention(q, k, v, mask=BandMask(100),
                                           **gate_none)), 8, 1, public=True)
    return {name: hashlib.sha256(t.encode()).hexdigest()[:16]
            for name, t in out.items()}


# As PR 63's parent (678ed79) traced them (jax 0.9.0). A change MEANT to move
# the ungated kernels replaces these; the gate is beside them and does not.
_UNGATED = {
    "plain_k_held": "55fa1c3e0883641b",
    "group6_causal_q_held": "b191bc830c60bca2",
    "group8_band_q_held": "7e7ffe71d942852e",
    "public_causal": "35f4414304697bed",
    "public_band": "89774a196e32a30f",
}


@pytest.fixture(scope="module")
def ungated_programs():
    return _ungated_programs({"gate": None})


@pytest.mark.parametrize("name", sorted(_UNGATED))
def test_an_ungated_call_traces_to_the_jaxpr_it_had(name, ungated_programs):
    """No new operand and no new equation, in the call or in a kernel."""
    assert ungated_programs[name] == _UNGATED[name]


if __name__ == "__main__":
    import inspect
    import json

    has_gate = "gate" in inspect.signature(fa.flash_attention).parameters
    print(json.dumps(_ungated_programs({"gate": None} if has_gate else {}),
                     indent=4))
