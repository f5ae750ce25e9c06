"""`kanana_reduce.self_ns` on hand-made events with scope paths: what counts
as latent attention (a block's `attn` half and the flash kernels), as the
flash kernels (by name, not by `tpu_custom_call`), forward and backward
apart whichever backward kernels ran; and that a trace without a flash
kernel reads as nothing."""

from benchmark import kanana_reduce
from benchmark.trace_reduce import Event

STEP = "jit(shard_step)/hvd_fwd_bwd/"
FWD = STEP + "jvp(Transformer)/"
AGAIN = STEP + "checkpoint/rematted_computation/Transformer/"
BWD = STEP + "transpose(jvp(Transformer))/"
ATTN = "hvd_block/block_1/attn/"


def event(name, start, end, target=""):
    opcode = "custom-call" if target else "fusion"
    return Event(name, opcode, "bf16[8]", target, start, end)


EVENTS = [
    event("fusion.1", 0, 40),                                   # embedding
    event("fusion.2", 40, 110),                                 # q
    event("fusion.3", 110, 130),                                # kv_norm
    event("hvd_flash_fwd.1", 130, 330, "tpu_custom_call"),
    event("copy.7", 330, 340),              # compiler-inserted, no op_name
    event("hvd_moe_gmm.1", 340, 490, "tpu_custom_call"),        # no flash
    event("fusion.4", 490, 520),                                # shared pair
    event("while.1", 520, 720),                                 # the loss
    event("hvd_flash_fwd.2", 720, 900, "tpu_custom_call"),      # recomputed
    event("hvd_flash_bwd.1", 900, 1500, "tpu_custom_call"),
    event("fusion.5", 1500, 1560),                              # kv_a bwd
    event("fusion.6", 1560, 1660),                              # optimizer
]
TABLE = {
    "fusion.1": FWD + "hvd_embed/embed/gather",
    "fusion.2": FWD + ATTN + "hvd_attn_proj/q/dot_general",
    "fusion.3": FWD + ATTN + "hvd_attn_norm/kv_norm/mul",
    "hvd_flash_fwd.1": FWD + ATTN + "hvd_flash_fwd/pallas_call",
    "hvd_moe_gmm.1": FWD + "hvd_block/block_1/mlp/moe_mlp/hvd_moe/"
                     "hvd_moe_experts/jit(_gmm)/hvd_moe_gmm/pallas_call",
    "fusion.4": FWD + "hvd_block/block_1/mlp/moe_mlp/hvd_moe/"
                "hvd_moe_shared/dot_general",
    "while.1": STEP + "jvp(hvd_loss)/while",
    "hvd_flash_fwd.2": AGAIN + ATTN + "hvd_flash_fwd/pallas_call",
    "hvd_flash_bwd.1": BWD + ATTN + "hvd_flash_bwd/pallas_call",
    "fusion.5": BWD + ATTN + "hvd_attn_proj/kv_a/dot_general",
    "fusion.6": "jit(shard_step)/hvd_optimizer/mul",
}


def test_self_ns_by_hand():
    out = kanana_reduce.self_ns(EVENTS, TABLE)
    assert out["flash_kernels"] == {"hvd_flash_fwd": 200 + 180,
                                    "hvd_flash_bwd": 600}
    assert (out["flash_fwd"], out["flash_bwd"], out["flash"]) \
        == (380, 600, 980)
    # the unnamed copy counts with the flash kernel before it: under attn,
    # but it is no kernel
    assert out["mla"] == 70 + 20 + 200 + 10 + 180 + 600 + 60
    assert out["mla_parts"] == {"fwd": 70 + 20 + 200 + 10 + 180,
                                "bwd": 600 + 60}
    assert "hvd_moe_gmm" not in out["flash_kernels"]


def test_two_backward_kernels_add_up():
    events = EVENTS[:9] + [
        event("hvd_flash_dq.1", 900, 1100, "tpu_custom_call"),
        event("hvd_flash_dkv.1", 1100, 1500, "tpu_custom_call")] + EVENTS[10:]
    table = dict(TABLE, **{
        "hvd_flash_dq.1": BWD + ATTN + "hvd_flash_dq/pallas_call",
        "hvd_flash_dkv.1": BWD + ATTN + "hvd_flash_dkv/pallas_call"})
    out = kanana_reduce.self_ns(events, table)
    assert out["flash_bwd"] == 200 + 400
    assert out["flash_kernels"]["hvd_flash_dkv"] == 400


def test_a_program_without_the_names_reads_as_none(monkeypatch):
    from benchmark import scope_reduce as sr

    monkeypatch.setattr(sr, "names", None)
    assert kanana_reduce.reduce_file("nowhere", None, 10) is None
