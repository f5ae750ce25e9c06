"""`loop_reduce.self_ns` on hand-made events with scope paths: what counts
as the looped stack, by pass and direction, what as the exits, and that the
loss, the embedding and the optimizer are neither."""

import pytest

from benchmark import loop_reduce
from benchmark.trace_reduce import Event

STEP = "jit(shard_step)/shard_map/hvd_fwd_bwd/"
FWD = STEP + "jvp(Transformer)/"
BWD = STEP + "transpose(jvp(Transformer))/"


def event(name, start, end, target=""):
    opcode = "custom-call" if target else "fusion"
    return Event(name, opcode, "bf16[8]", target, start, end)


EVENTS = [
    event("fusion.1", 0, 50),                                    # embedding
    event("fusion.2", 50, 150),                                  # pass 1
    event("hvd_flash_fwd.1", 150, 350, "tpu_custom_call"),
    event("copy.9", 350, 360),              # compiler-inserted, no op_name
    event("fusion.3", 360, 400),                                 # its norm_f
    event("fusion.4", 400, 520),                                 # pass 2
    event("fusion.5", 520, 540),                                 # the gate
    event("fusion.6", 540, 560),                                 # weights
    event("while.1", 560, 900),                                  # the loss
    event("fusion.7", 900, 930),                                 # entropy bwd
    event("fusion.8", 930, 1100),                                # pass 2 bwd
    event("hvd_flash_dkv.1", 1100, 1400, "tpu_custom_call"),     # pass 1 bwd
    event("fusion.9", 1400, 1430),           # a sum over passes, no pass
    event("fusion.10", 1430, 1500),                              # optimizer
]
TABLE = {
    "fusion.1": FWD + "hvd_embed/embed/gather",
    "fusion.2": FWD + "hvd_loop/pass_1/hvd_block/block_0/mlp/mlp_up/dot",
    "hvd_flash_fwd.1": FWD + "hvd_loop/pass_1/hvd_block/block_0/attn/"
                       "hvd_flash_fwd/pallas_call",
    "fusion.3": FWD + "hvd_loop/pass_1/hvd_head/norm_f/mul",
    "fusion.4": FWD + "hvd_loop/pass_2/hvd_block/block_0/attn/out/dot",
    "fusion.5": FWD + "hvd_exit/exit_gate/dot_general",
    "fusion.6": STEP + "jvp(hvd_exit)/exp",
    "while.1": STEP + "jvp(hvd_loss)/while",
    "fusion.7": STEP + "transpose(jvp(hvd_exit))/mul",
    "fusion.8": BWD + "hvd_loop/pass_2/hvd_block/block_0/mlp/mlp_out/"
                "transpose",
    "hvd_flash_dkv.1": STEP + "transpose(hvd_fwd_bwd)/jvp(Transformer)/"
                       "hvd_loop/pass_1/hvd_block/block_0/attn/"
                       "hvd_flash_dkv/pallas_call",
    "fusion.9": BWD + "hvd_loop/add_any",
    "fusion.10": "jit(shard_step)/shard_map/hvd_optimizer/mul",
}


def test_self_ns_by_hand():
    out = loop_reduce.self_ns(EVENTS, TABLE)
    # the unnamed copy counts with the flash kernel before it
    assert out["passes"] == {
        "pass_1 fwd": 100 + 200 + 10 + 40, "pass_2 fwd": 120,
        "pass_2 bwd": 170, "pass_1 bwd": 300, "hvd_loop bwd": 30}
    assert out["loop"] == sum(out["passes"].values()) == 970
    assert out["exit"] == 20 + 20 + 30
    # embedding 50, loss 340 and optimizer 70 are the rest of the 1500
    assert out["loop"] + out["exit"] + 50 + 340 + 70 == 1500


def test_a_program_without_the_names_reads_as_none(monkeypatch):
    monkeypatch.delattr(loop_reduce.sr.names, "LOOP")
    assert loop_reduce.loop_names() is None
    assert loop_reduce.reduce_file("/nonexistent", None, 1) is None


def test_names_are_the_programs(monkeypatch):
    """The scopes are read from `horovod_tpu.profile`: a renamed scope is
    followed, not silently read as zero."""
    monkeypatch.setattr(loop_reduce.sr.names, "EXIT", "hvd_leave")
    out = loop_reduce.self_ns(EVENTS, TABLE)
    assert out["exit"] == 0 and out["loop"] == 970
    table = {k: v.replace("hvd_exit", "hvd_leave") for k, v in TABLE.items()}
    assert loop_reduce.self_ns(EVENTS, table)["exit"] == 70


def test_a_trace_of_another_program_reads_as_none():
    """The recorded trace of the dense LM names no loop: `loop_ms` and
    `exit_ms` are left out of such a line."""
    import os

    from benchmark import trace_reduce as tr

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "synthetic_scoped.xplane.pb")
    assert loop_reduce.reduce_file(path, tr.load(path), 2) is None
