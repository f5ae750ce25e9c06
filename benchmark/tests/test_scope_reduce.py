"""The reduction by scope and kernel name against hand-worked answers.

`data/synthetic_scoped.xplane.pb` (written by
`make_synthetic_scoped_trace.py`; every interval and every `op_name` chosen
by hand; microseconds from the start of step 1; the `op_name` is the stat
`tf_op` of the event's metadata, as the TPU runtime stores it; `S/` stands
for `jit(shard_step)/shard_map/`, `F` for `hvd_fwd_bwd`):

device 0, ops line                                       phase        us
  copy.1            0-10   (no op_name, nothing before)    unscoped     10
  fusion.1         10-60   S/F/jvp(Transformer)/hvd_embed/..  fwd_bwd   50
  hvd_flash_fwd.1  60-160  ../hvd_block/block_0/attn/hvd_flash_fwd/pallas_call
                                                           fwd_bwd     100
  fusion.2        160-260  ../hvd_block/block_0/mlp/mlp_in/.. fwd_bwd  100
  while.1         260-400  S/F/jvp(hvd_loss)/while encloses fwd_bwd    10 (self)
    fusion.3      260-320  S/F/jvp(hvd_loss)/while/body/..  fwd_bwd    60 loss
    fusion.4      330-400  S/F/transpose(jvp(hvd_loss))/..  fwd_bwd    70 loss
  copy-done.2     400-420  (no op_name: counts with fusion.4) fwd_bwd  20 loss
  hvd_flash_dkv.1 420-560  S/F/transpose(F)/../attn/hvd_flash_dkv/pallas_call
                                                           fwd_bwd     140
  hvd_flash_dq.1  560-640  ../attn/hvd_flash_dq/pallas_call  fwd_bwd    80
  fusion.5        640-700  S/F/transpose(jvp(Transformer))/hvd_block/../mlp/..
                                                           fwd_bwd      60
  all-reduce.1    700-800  S/hvd_grad_sync/psum            grad_sync   100
  fusion.6        800-820  S/hvd_grad_sync/div             grad_sync    20
  fusion.7        820-900  S/hvd_optimizer/mul (by ref)    optimizer    80
  fusion.8        900-930  S/convert.88 (no phase scope)   unscoped     30
  (idle           930-1000)
  fusion.1       1000-1050                                 fwd_bwd      50
  hvd_flash_fwd.1 1050-1170                                fwd_bwd     120
  (idle          1170-1200)
  fusion.7       1200-1300                                 optimizer   100

  device 0: fwd_bwd 860 (of it loss 160), grad_sync 120, optimizer 180,
  unscoped 40: 1200 = busy (930 + 170 + 100). Kernels: hvd_flash_fwd 220,
  hvd_flash_dkv 140, hvd_flash_dq 80: 440 = every `tpu_custom_call`.

device 1, ops line
  fusion.1          0-400  fwd_bwd 400     fusion.7        700-800 optimizer 100
  all-reduce.1    400-700  grad_sync 300   hvd_flash_fwd.1 1000-1200 fwd_bwd 200
  device 1: fwd_bwd 600, grad_sync 300, optimizer 100: 1000 = busy.

Two programs (steps) on each device; means over the two devices, per step:
fwd_bwd 365, grad_sync 105, optimizer 70, unscoped 10 (sum 550 = busy),
loss 40 us; hvd_flash_fwd 105, hvd_flash_dkv 35, hvd_flash_dq 20 (sum 160 =
`flash_ms`). The metadata table also holds `fusion.9` twice with two
`op_name`s (two programs): ambiguous, so it has none.

`data/recorded_v5e_scoped_slice.xplane.pb` is cut from a trace recorded on
one v5e chip (my chip run, PR 23, cell lm1b4_1chip, first step, by a program
whose forward-and-backward scope was still called `hvd_model`): 83 events
with the 57 metadata entries they use and every stat of those kept. It
checks that the names are where the runtime really puts them.
`data/synthetic.xplane.pb` (PR 22) carries no `op_name`: a trace of a
program older than its names.
"""

import os

import pytest

from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.layer_metrics import (flash_bwd_ms, flash_fwd_ms, flash_ms,
                                     fwd_bwd_ms, grad_sync_ms, loss_ms,
                                     optimizer_ms, unscoped_ms)
from benchmark.run import load_plugin

# The readers of the two-kernel backward (the synthetic trace's program runs
# it): `sdar30b_1chip`'s, whose file names are no module names.
flash_dq_ms = load_plugin("layer_metrics", "flash_dq_ms.sdar")
flash_dkv_ms = load_plugin("layer_metrics", "flash_dkv_ms.sdar")

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "synthetic_scoped.xplane.pb")
RECORDED = os.path.join(DATA, "recorded_v5e_scoped_slice.xplane.pb")
UNNAMED = os.path.join(DATA, "synthetic.xplane.pb")
MS = 1e-3  # of a microsecond
NEW_READERS = (fwd_bwd_ms, grad_sync_ms, optimizer_ms, loss_ms, unscoped_ms,
               flash_fwd_ms, flash_bwd_ms, flash_dq_ms, flash_dkv_ms)


def reading(monkeypatch, path):
    """(trace, context) as the harness hands them to a reader after it has
    written the trace at `path`."""
    monkeypatch.setattr(sr, "trace_path", lambda context: path)
    trace = tr.load(path)
    return trace, {"cell": {"name": "a_cell"},
                   "steps_traced": trace.modules[min(trace.modules)]}


def test_scope_paths_are_read():
    f = ("jit(shard_step)/shard_map/hvd_fwd_bwd/transpose(jvp(Transformer))/"
         "hvd_block/block_3/mlp/mlp_out/transpose")
    assert sr.scopes("a/transpose(jvp(b))/c") == ["a", "transpose", "jvp",
                                                  "b", "c"]
    assert sr.phase_of(f) == "hvd_fwd_bwd"
    assert sr.part_of(f) == "hvd_fwd_bwd hvd_block/mlp bwd"
    # the primitive `transpose` is not the transform `transpose(`
    assert sr.part_of(f.replace("transpose(jvp(Transformer))",
                                "jvp(Transformer)")) == \
        "hvd_fwd_bwd hvd_block/mlp fwd"
    # a custom VJP's backward rule: `transpose(<the scope it was called in>)`
    assert sr.part_of("jit(s)/hvd_fwd_bwd/transpose(hvd_fwd_bwd)/jvp(T)/"
                      "hvd_block/block_0/attn/hvd_flash_dq/pallas_call") == \
        "hvd_fwd_bwd hvd_block/attn bwd"
    assert sr.phase_of("jit(s)/hvd_optimizer/hvd_grad_sync/x") == \
        "hvd_optimizer"  # the outermost phase scope counts
    assert sr.phase_of("jit(shard_step)/shard_map/convert.88") == sr.UNSCOPED
    assert sr.phase_of("") == sr.UNSCOPED
    assert sr.in_loss("jit(s)/hvd_fwd_bwd/transpose(jvp(hvd_loss))/while")
    assert not sr.in_loss("jit(s)/hvd_fwd_bwd/jvp(T)/hvd_head/mul")


def test_op_names_come_from_the_event_metadata():
    table = sr.op_names(SCOPED)
    assert sorted(table) == [0, 1]
    assert table[0]["fusion.7"] == \
        "jit(shard_step)/shard_map/hvd_optimizer/mul"  # stored by reference
    assert table[0]["hvd_flash_fwd.1"].endswith(
        "attn/hvd_flash_fwd/pallas_call")  # the colon it ends in is dropped
    assert table[0]["copy.1"] == "" and table[0]["copy-done.2"] == ""
    assert table[0]["fusion.9"] == ""  # two programs disagree about it
    assert "jit_shard_step(1)" in table[0]  # a name that is no instruction


def test_phases_and_kernels_against_the_hand_worked_sums(monkeypatch):
    trace, context = reading(monkeypatch, SCOPED)
    assert fwd_bwd_ms.read(trace, context) == pytest.approx(365 * MS)
    assert grad_sync_ms.read(trace, context) == pytest.approx(105 * MS)
    assert optimizer_ms.read(trace, context) == pytest.approx(70 * MS)
    assert unscoped_ms.read(trace, context) == pytest.approx(10 * MS)
    assert loss_ms.read(trace, context) == pytest.approx(40 * MS)
    assert flash_fwd_ms.read(trace, context) == pytest.approx(105 * MS)
    assert flash_dkv_ms.read(trace, context) == pytest.approx(35 * MS)
    assert flash_dq_ms.read(trace, context) == pytest.approx(20 * MS)
    # this program's backward is two kernels: the one-kernel reader is silent
    assert flash_bwd_ms.read(trace, context) is None


ONE, TWO = ["hvd_flash_fwd", "hvd_flash_bwd"], \
    ["hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"]


@pytest.mark.parametrize("kernels,plan,flash_ms_a_step", [
    ({"hvd_flash_fwd": 1.25, "hvd_flash_bwd": 2.5, "hvd_moe_gmm": 24.0},
     ONE, 3.75),
    ({"hvd_flash_fwd": 1.25, "hvd_flash_dq": 1.5, "hvd_flash_dkv": 2.25},
     TWO, 5.0),
    # the trace holds other kernels than the plan the counts follow: the
    # share would be off by 7/9 or 9/7, so none is given
    ({"hvd_flash_fwd": 1.25, "hvd_flash_bwd": 2.5}, TWO, None),
    ({"hvd_flash_fwd": 1.25, "hvd_flash_dq": 1.5, "hvd_flash_dkv": 2.25},
     ONE, None),
    ({"hvd_flash_fwd": 1.25}, ONE, None),
    ({"hvd_moe_gmm": 24.0}, ONE, None)])
def test_readers_follow_whichever_backward_kernels_ran(
        monkeypatch, capsys, kernels, plan, flash_ms_a_step):
    """`flash_bwd_ms` reads the one-kernel backward by its name;
    `flash_roofline.olmoe` is over the forward and whichever backward
    kernels ran, never over the grouped matmuls, silent where no flash
    kernel ran, and silent (with an INFO line that names both sides) where
    the kernels that ran are not the ones the plan names. The readers over
    `flash_ms` (`flash_roofline`, `.ouro`) hold the same rule."""
    monkeypatch.setattr(sr, "reduce",
                        lambda trace, context: {"kernels": kernels})
    context = {"counts": {"flash_executed_flops": 197e12 * 2e-3,
                          "flash_min_bytes": 819e9 * 1e-3,
                          "flash_kernels": plan},
               "peaks": {"bf16_flops_per_s": 197e12,
                         "hbm_bytes_per_s": 819e9}}
    assert flash_bwd_ms.read(None, context) == kernels.get("hvd_flash_bwd")
    assert flash_dq_ms.read(None, context) == kernels.get("hvd_flash_dq")
    roofline = load_plugin("layer_metrics", "flash_roofline.olmoe")
    got = roofline.read(None, context)
    whole = load_plugin("layer_metrics", "flash_roofline")
    monkeypatch.setattr(whole.flash_ms, "read", lambda trace, context: sum(
        v for k, v in kernels.items() if k.startswith("hvd_flash")) or None)
    got_whole = whole.read(None, context)
    said = capsys.readouterr().out
    if flash_ms_a_step is None:
        assert got is None and got_whole is None
        assert ("_not_read" in said) == any(
            k.startswith("hvd_flash") for k in kernels)
    else:  # the operations bind: 2 ms at peak over the kernels' time
        assert got == got_whole == pytest.approx(
            100.0 * 2.0 / flash_ms_a_step)
        assert "_binds" in said and "_not_read" not in said


def test_one_device_by_hand():
    trace = tr.load(SCOPED)
    got = sr.self_ns(trace.devices[0], sr.op_names(SCOPED)[0])
    us = 1000.0
    assert got["phases"] == {"hvd_fwd_bwd": 860 * us, "hvd_grad_sync": 120 * us,
                             "hvd_optimizer": 180 * us,
                             "hvd_param_gather": 0.0, "unscoped": 40 * us}
    assert got["loss"] == 160 * us
    assert got["kernels"] == {"hvd_flash_fwd": 220 * us,
                              "hvd_flash_dkv": 140 * us,
                              "hvd_flash_dq": 80 * us}
    # what was attributed by position stays visible in the by-scope table
    assert got["parts"]["hvd_fwd_bwd compiler-inserted"] == 20 * us
    assert got["parts"]["unscoped compiler-inserted"] == 10 * us
    assert got["parts"]["unscoped"] == 30 * us
    assert got["parts"]["hvd_fwd_bwd hvd_loss bwd"] == 70 * us
    assert got["parts"]["hvd_fwd_bwd hvd_loss fwd"] == 70 * us  # while + body
    assert got["parts"]["hvd_fwd_bwd hvd_block/attn bwd hvd_flash_dkv"] == \
        140 * us


def test_the_two_sum_rules(monkeypatch):
    """Phases and `unscoped` add up to the busy time; the three kernels add
    up to `flash_ms`, which selects by custom-call target, not by name."""
    trace, context = reading(monkeypatch, SCOPED)
    out = sr.reduce(trace, context)
    busy_ms = tr.mean_over_devices(trace, tr.busy) / 1e6 / 2
    assert busy_ms == pytest.approx(550 * MS)
    assert sum(out["phases"].values()) == pytest.approx(busy_ms)
    assert sum(out["parts"].values()) == pytest.approx(busy_ms)
    metrics = sum(m.read(trace, context) for m in (
        fwd_bwd_ms, grad_sync_ms, optimizer_ms, unscoped_ms))
    assert metrics == pytest.approx(busy_ms)
    kernels = sum(m.read(trace, context) for m in (
        flash_fwd_ms, flash_dq_ms, flash_dkv_ms))
    assert kernels == pytest.approx(flash_ms.read(trace, context))
    assert kernels == pytest.approx(160 * MS)


def test_an_unnamed_program_gives_none_for_every_new_metric(monkeypatch):
    trace, context = reading(monkeypatch, UNNAMED)
    assert sr.reduce(trace, context) is None
    for reader in NEW_READERS:
        assert reader.read(trace, context) is None
    assert flash_ms.read(trace, context) is not None  # the old one still reads


def test_a_step_without_a_collective_has_no_grad_sync(monkeypatch):
    trace, context = reading(monkeypatch, SCOPED)
    for n in trace.devices:
        trace.devices[n] = [e for e in trace.devices[n]
                            if e.name not in ("all-reduce.1", "fusion.6")]
    sr._reduced.clear()
    assert grad_sync_ms.read(trace, context) is None
    assert optimizer_ms.read(trace, context) == pytest.approx(70 * MS)
    sr._reduced.clear()


def test_the_reduction_is_made_once_a_run(monkeypatch, capsys):
    trace, context = reading(monkeypatch, SCOPED)
    sr._reduced.clear()
    calls = []
    real = sr.reduce_file
    monkeypatch.setattr(sr, "reduce_file",
                        lambda *a: calls.append(a) or real(*a))
    for reader in NEW_READERS:
        reader.read(trace, context)
    assert len(calls) == 1
    out = capsys.readouterr().out
    assert out.count("INFO ") == 1 and "device_ms_a_step_by_scope" in out


def test_names_are_where_the_runtime_puts_them():
    table = sr.op_names(RECORDED)[0]
    assert table["fusion.31"] == "jit(shard_step)/hvd_optimizer/add"
    named = [op for op in table.values() if op]
    assert len(table) == 57 and len(named) == 23
    assert table["copy-done.158"] == "" and table["slice-start.29"] == ""
    trace = tr.load(RECORDED)
    kernels = {sr.kernel_of(ev, table.get(ev.name, ""))
               for ev in trace.devices[0]}
    assert {"hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"} <= kernels
    got = sr.self_ns(trace.devices[0], table)
    assert sum(got["phases"].values()) == pytest.approx(
        tr.busy(trace.devices[0]))
    flash = tr.matching_ns(trace.devices[0], flash_ms.is_flash_kernel)
    assert sum(got["kernels"].values()) == pytest.approx(flash)
