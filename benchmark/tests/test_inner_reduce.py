"""`inner_reduce.self_ns` and `reduce_file` on hand-made events with scope
paths: what counts to which part of attention (the three scopes, the flash
kernels by name, `rest`) and of a Mamba-2 mixer, that the parts add up to
what the readers from outside take (`mellum_reduce`, `sdar_reduce`,
`xing_reduce`, `nemo3_reduce` on the same events), where an instruction
without an `op_name` goes, and that a program without the names reads as
nothing."""

import os

import pytest

from benchmark import (inner_reduce, mellum_reduce, nemo3_reduce, sdar_reduce,
                       xing_reduce)
from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Event

STEP = "jit(shard_step)/hvd_fwd_bwd/"
FWD = STEP + "jvp(Transformer)/hvd_block/"
BWD = STEP + "transpose(jvp(Transformer))/hvd_block/"
AGAIN = BWD + "jvp(Transformer)/hvd_block/checkpoint/rematted_computation/"
W, F = "hvd_attn_window/", "hvd_attn_full/"
PROJ, NORM, ROPE = ("attn/hvd_attn_proj/", "attn/hvd_attn_norm/",
                    "attn/hvd_attn_rope/")
SSM = "hvd_ssm/ssm/"


def event(name, start, end, target=""):
    opcode = "custom-call" if target else "fusion"
    return Event(name, opcode, "bf16[8]", target, start, end)


EVENTS = [
    event("fusion.1", 0, 40),                                 # embedding
    event("fusion.2", 40, 60),                                # norm1, window
    event("fusion.3", 60, 130),                               # q proj, w
    event("fusion.4", 130, 145),                              # q_norm, w
    event("fusion.5", 145, 170),                              # rotary, w
    event("copy.6", 170, 180),              # compiler-inserted, no op_name
    event("fusion.7", 180, 190),                              # transpose, w
    event("hvd_flash_fwd.1", 190, 240, "tpu_custom_call"),    # band
    event("copy.8", 240, 250),              # no op_name, after a kernel
    event("fusion.9", 250, 300),                              # out proj, w
    event("fusion.10", 300, 305),                             # residual, w
    event("hvd_moe_gmm.1", 305, 400, "tpu_custom_call"),      # not attention
    event("fusion.11", 400, 480),                             # q proj, full
    event("hvd_flash_fwd.2", 480, 680, "tpu_custom_call"),    # causal
    event("fusion.12", 680, 700),                 # in a block with no kind
    event("fusion.13", 700, 730),                 # under a hyper-connection
    event("fusion.14", 730, 830),                             # in_proj
    event("fusion.15", 830, 860),                             # softplus
    event("fusion.16", 860, 900),                             # convolution
    event("while.17", 900, 1000),                             # the scan
    event("fusion.18", 930, 960),                             # in its body
    event("fusion.19", 1000, 1012),                           # slice of z
    event("fusion.20", 1012, 1100),                           # gate bwd
    event("fusion.21", 1100, 1300),                           # in_proj bwd
    event("fusion.22", 1300, 1360),                           # q proj again
    event("hvd_flash_fwd.3", 1360, 1410, "tpu_custom_call"),  # w, again
    event("hvd_flash_bwd.1", 1410, 1540, "tpu_custom_call"),  # w
    event("fusion.23", 1540, 1640),                           # k proj bwd, w
    event("fusion.24", 1640, 1670),                           # rotary bwd, f
    event("fusion.25", 1670, 1770),                           # optimizer
]
TABLE = {
    "fusion.1": STEP + "jvp(Transformer)/hvd_embed/embed/gather",
    "fusion.2": FWD + "block_0/" + W + "norm1/mul",
    "fusion.3": FWD + "block_0/" + W + PROJ + "query/dot_general",
    "fusion.4": FWD + "block_0/" + W + NORM + "q_norm/mul",
    "fusion.5": FWD + "block_0/" + W + ROPE + "mul",
    "fusion.7": FWD + "block_0/" + W + "attn/transpose",
    "hvd_flash_fwd.1": FWD + "block_0/" + W + "attn/jit(_ruled_hvd_flash_"
                       "fwd)/hvd_flash_fwd/pallas_call",
    "fusion.9": FWD + "block_0/" + W + PROJ + "out/dot_general",
    "fusion.10": FWD + "block_0/" + W + "add",
    "hvd_moe_gmm.1": FWD + "block_0/mlp/moe_mlp/hvd_moe/hvd_moe_experts/"
                     "hvd_moe_gmm/pallas_call",
    "fusion.11": FWD + "block_3/" + F + PROJ + "query/dot_general",
    "hvd_flash_fwd.2": FWD + "block_3/" + F + "attn/hvd_flash_fwd/"
                       "pallas_call",
    "fusion.12": FWD + "block_4/" + NORM + "k_norm/mul",
    "fusion.13": FWD + "block_4/hvd_hc/hvd_hc_mix/attn/mul",
    "fusion.14": FWD + "block_5/" + SSM + "hvd_ssm_proj/in_proj/dot_general",
    "fusion.15": FWD + "block_5/" + SSM + "hvd_ssm_gate/jit(softplus)/exp",
    "fusion.16": FWD + "block_5/" + SSM + "hvd_ssm_conv/mul",
    "while.17": FWD + "block_5/" + SSM + "hvd_ssd/while",
    "fusion.18": FWD + "block_5/" + SSM + "hvd_ssd/while/body/dot_general",
    "fusion.19": FWD + "block_5/" + SSM + "slice",
    "fusion.20": BWD + "block_5/" + SSM + "hvd_ssm_gate/mul",
    "fusion.21": BWD + "block_5/" + SSM + "hvd_ssm_proj/in_proj/transpose",
    "fusion.22": AGAIN + "block_0/" + W + PROJ + "query/dot_general",
    "hvd_flash_fwd.3": AGAIN + "block_0/" + W + "attn/hvd_flash_fwd/"
                       "pallas_call",
    "hvd_flash_bwd.1": BWD + "block_0/" + W + "attn/hvd_flash_bwd/"
                       "pallas_call",
    "fusion.23": BWD + "block_0/" + W + PROJ + "key/transpose",
    "fusion.24": BWD + "block_3/" + F + ROPE + "mul",
    "fusion.25": "jit(shard_step)/hvd_optimizer/mul",
}


class Trace:
    """What `tr.load` returns, as far as the reducers read it."""

    def __init__(self, devices):
        self.devices = devices


def test_self_ns_by_hand():
    out = inner_reduce.self_ns(EVENTS, TABLE)
    assert out["attn"] == {
        "hvd_attn_proj fwd": 70 + 50 + 80, "hvd_attn_proj again": 60,
        "hvd_attn_proj bwd": 100,
        "hvd_attn_norm fwd": 15 + 20,
        # the unnamed copy counts with the rotation before it
        "hvd_attn_rope fwd": 25 + 10, "hvd_attn_rope bwd": 30,
        "hvd_flash_fwd fwd": 50 + 200, "hvd_flash_fwd again": 50,
        "hvd_flash_bwd bwd": 130,
        # the norm before the attention, the transpose, the residual add,
        # and the unnamed copy after a kernel: under its scopes, no kernel
        "rest fwd": 20 + 10 + 5 + 10}
    assert out["ssm"] == {
        "hvd_ssm_proj fwd": 100, "hvd_ssm_proj bwd": 200,
        "hvd_ssm_gate fwd": 30, "hvd_ssm_gate bwd": 88,
        "hvd_ssm_conv fwd": 40,
        "hvd_ssd fwd": 70 + 30,  # the `while`'s self time and its body's
        "rest fwd": 12}
    # every instruction of an area once, with the part it was counted to:
    # what `fused_scopes`' fusions are joined to, by name
    rows = out["instructions"]
    assert rows["attn"]["fusion.3"] == ["hvd_attn_proj", 70]
    assert rows["attn"]["copy.6"] == ["hvd_attn_rope", 10]
    assert rows["ssm"]["while.17"] == ["hvd_ssd", 70]
    assert sum(ns for _, ns in rows["attn"].values()) \
        == sum(out["attn"].values())
    assert sum(ns for _, ns in rows["ssm"].values()) \
        == sum(out["ssm"].values())
    # neither the grouped matmul, nor what a hyper-connection does around a
    # branch, nor the optimizer
    for name in ("hvd_moe_gmm.1", "fusion.13", "fusion.25", "fusion.1"):
        assert name not in rows["attn"] and name not in rows["ssm"]


def test_the_parts_add_up_to_what_the_outside_readers_take():
    inner = inner_reduce.self_ns(EVENTS, TABLE)
    attn, ssm = sum(inner["attn"].values()), sum(inner["ssm"].values())
    # every layer of a stack with kinds has one: the two kinds' scopes
    # hold all of the area but the block without a kind
    by_kind = mellum_reduce.self_ns(EVENTS, TABLE)
    assert attn - 20 == sum(by_kind["attn"].values())
    # a block's `attn` half + the flash kernels: the norm before the
    # attention and the residual add under a kind's scope are not in it
    # (no cell has both); what a hyper-connection does around the branch is
    # `hc_ms`'s, and SDAR's reader, whose cell has none, does not ask
    assert attn - 20 - 5 == xing_reduce.self_ns(EVENTS, TABLE)["mla"]
    assert attn - 20 - 5 + 30 == sdar_reduce.self_ns(EVENTS, TABLE)["attn"]
    assert ssm == nemo3_reduce.self_ns(EVENTS, TABLE)["ssm"]
    assert inner["ssm"]["hvd_ssd fwd"] \
        == nemo3_reduce.self_ns(EVENTS, TABLE)["ssd"]


@pytest.fixture
def traced(monkeypatch):
    """`reduce_file` over the hand-made events as two devices' (the second
    twice as slow), two steps traced."""
    slow = [event(e.name, 2 * e.start, 2 * e.end, e.target)
            for e in EVENTS]
    monkeypatch.setattr(inner_reduce.sr, "op_names",
                        lambda path: {0: TABLE, 1: TABLE})
    return inner_reduce.reduce_file("unread", Trace({0: EVENTS, 1: slow}), 2)


def test_reduce_file_is_ms_a_step_over_the_devices(traced):
    scale = 1.5 / 2 / 1e6  # the mean of 1x and 2x, two steps, ns -> ms
    attn, ssm = traced["attn"], traced["ssm"]
    assert attn["parts"] == pytest.approx({
        "hvd_attn_proj": 360 * scale, "hvd_attn_norm": 35 * scale,
        "hvd_attn_rope": 65 * scale, "hvd_flash_fwd": 300 * scale,
        "hvd_flash_bwd": 130 * scale, "rest": 45 * scale})
    assert ssm["parts"] == pytest.approx({
        "hvd_ssm_proj": 300 * scale, "hvd_ssm_gate": 118 * scale,
        "hvd_ssm_conv": 40 * scale, "hvd_ssd": 100 * scale,
        "rest": 12 * scale})
    assert sum(attn["by_direction"].values()) == pytest.approx(935 * scale)
    assert attn["by_direction"]["hvd_attn_proj again"] == pytest.approx(
        60 * scale)
    # the longest instructions outside the kernels, each with its part
    assert [row[:2] for row in attn["longest"][:3]] == [
        ["fusion.23", "hvd_attn_proj"], ["fusion.11", "hvd_attn_proj"],
        ["fusion.3", "hvd_attn_proj"]]
    assert all(not row[0].startswith("hvd_flash") for row in attn["longest"])
    assert len(attn["longest"]) == inner_reduce.LONGEST
    assert ssm["longest"][0] == ["fusion.21", "hvd_ssm_proj",
                                 pytest.approx(200 * scale)]
    assert [row[0] for row in ssm["longest"]][1:3] == ["fusion.14",
                                                       "fusion.20"]


def test_the_readers_read_their_parts(traced, monkeypatch):
    monkeypatch.setattr(inner_reduce, "reduce", lambda trace, ctx: traced)
    scale = 1.5 / 2 / 1e6
    for area, part, ns in (
            ("attn", "ATTN_PROJ", 360), ("attn", "ATTN_NORM", 35),
            ("attn", "ATTN_ROPE", 65), ("ssm", "SSM_PROJ", 300),
            ("ssm", "SSM_GATE", 118)):
        assert inner_reduce.ms(None, None, area, part) == pytest.approx(
            ns * scale)
    # a step with nothing under a name, and a name the program lacks
    traced["attn"]["parts"].pop("hvd_attn_norm")
    assert inner_reduce.ms(None, None, "attn", "ATTN_NORM") is None
    monkeypatch.delattr(inner_reduce.sr.names, "SSM_GATE")
    assert inner_reduce.ms(None, None, "ssm", "SSM_GATE") is None


def test_a_program_without_the_names_reads_as_none(monkeypatch):
    """The parent of the PR that brought the parts: its `profile` has no
    `ATTN_PARTS`, and every reader over this reducer leaves its metric
    out."""
    monkeypatch.delattr(inner_reduce.sr.names, "ATTN_PARTS")
    assert inner_reduce.inner_names() is None
    assert inner_reduce.reduce_file("/nonexistent", None, 1) is None


def test_a_trace_of_a_program_without_the_parts_reads_as_none():
    """The recorded trace of a dense LM whose attention carries flax's
    names alone (`hvd_block/block_0/attn/hvd_flash_fwd`): no part to read,
    so nothing is reported, not an area that is all `rest`."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "synthetic_scoped.xplane.pb")
    assert inner_reduce.reduce_file(path, tr.load(path), 2) is None


def test_names_are_the_programs(monkeypatch):
    monkeypatch.setattr(inner_reduce.sr.names, "ATTN_PARTS",
                        ("hvd_attn_proj", "hvd_attn_norm", "hvd_rotary"))
    out = inner_reduce.self_ns(EVENTS, TABLE)["attn"]
    assert "hvd_attn_rope fwd" not in out
    assert out["rest fwd"] == 45 + 35 and out["rest bwd"] == 30
    table = {k: v.replace("hvd_attn_rope", "hvd_rotary")
             for k, v in TABLE.items()}
    assert inner_reduce.self_ns(EVENTS, table)["attn"]["hvd_rotary fwd"] == 35
