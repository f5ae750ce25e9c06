"""`xing_reduce.self_ns` on hand-made events with scope paths: what counts
as the hyper-connections (by inner scope and direction), as latent
attention, as the flash kernels (by name, not by `tpu_custom_call`), as the
prediction module; and that the views overlap as they are meant to."""

from benchmark import xing_reduce
from benchmark.trace_reduce import Event

STEP = "jit(shard_step)/hvd_fwd_bwd/"
FWD = STEP + "jvp(Transformer)/"
BWD = STEP + "transpose(jvp(Transformer))/"


def event(name, start, end, target=""):
    opcode = "custom-call" if target else "fusion"
    return Event(name, opcode, "bf16[8]", target, start, end)


EVENTS = [
    event("fusion.1", 0, 40),                                   # embedding
    event("fusion.2", 40, 100),                                 # maps
    event("fusion.3", 100, 130),                                # read
    event("fusion.4", 130, 200),                                # q_a
    event("hvd_flash_fwd.1", 200, 400, "tpu_custom_call"),
    event("copy.7", 400, 410),              # compiler-inserted, no op_name
    event("fusion.5", 410, 450),                                # write
    event("hvd_moe_gmm.1", 450, 600, "tpu_custom_call"),        # no flash
    event("fusion.6", 600, 650),                                # shared
    event("fusion.7", 650, 700),                                # mtp proj
    event("fusion.8", 700, 760),                                # mtp maps
    event("hvd_flash_fwd.2", 760, 900, "tpu_custom_call"),      # mtp attn
    event("while.1", 900, 1100),                                # the loss
    event("hvd_flash_bwd.1", 1100, 1500, "tpu_custom_call"),
    event("fusion.9", 1500, 1580),                              # write bwd
    event("fusion.10", 1580, 1600),                             # remat maps
    event("fusion.11", 1600, 1700),                             # optimizer
]
TABLE = {
    "fusion.1": FWD + "hvd_embed/embed/gather",
    "fusion.2": FWD + "hvd_block/block_0/hvd_hc/hc_attn/hvd_hc_map/dot",
    "fusion.3": FWD + "hvd_block/block_0/hvd_hc/hc_attn/hvd_hc_mix/mul",
    "fusion.4": FWD + "hvd_block/block_0/attn/q_a/dot_general",
    "hvd_flash_fwd.1": FWD + "hvd_block/block_0/attn/hvd_flash_fwd/"
                       "pallas_call",
    "fusion.5": FWD + "hvd_block/block_0/hvd_hc/hvd_hc_mix/add",
    "hvd_moe_gmm.1": FWD + "hvd_block/block_1/mlp/moe_mlp/hvd_moe/"
                     "hvd_moe_experts/hvd_moe_gmm/pallas_call",
    "fusion.6": FWD + "hvd_block/block_1/mlp/moe_mlp/hvd_moe/"
                "hvd_moe_shared/shared_up/dot_general",
    "fusion.7": FWD + "hvd_mtp/mtp_proj/dot_general",
    "fusion.8": FWD + "hvd_mtp/hvd_block/mtp_block/hvd_hc/hc_mlp/"
                "hvd_hc_map/exp",
    "hvd_flash_fwd.2": FWD + "hvd_mtp/hvd_block/mtp_block/attn/"
                       "hvd_flash_fwd/pallas_call",
    "while.1": STEP + "jvp(hvd_loss)/while",
    "hvd_flash_bwd.1": STEP + "transpose(hvd_fwd_bwd)/jvp(Transformer)/"
                       "hvd_block/block_0/attn/hvd_flash_bwd/pallas_call",
    "fusion.9": BWD + "hvd_block/block_0/hvd_hc/hvd_hc_mix/mul",
    "fusion.10": BWD + "hvd_block/block_0/hvd_hc/checkpoint/hc_attn/"
                 "hvd_hc_map/dot",
    "fusion.11": "jit(shard_step)/hvd_optimizer/mul",
}


def test_self_ns_by_hand():
    out = xing_reduce.self_ns(EVENTS, TABLE)
    assert out["hc_parts"] == {
        "hvd_hc_map fwd": 60 + 60, "hvd_hc_mix fwd": 30 + 40,
        "hvd_hc_mix bwd": 80, "hvd_hc_map bwd": 20}
    assert out["hc"] == sum(out["hc_parts"].values()) == 290
    # the unnamed copy counts with the flash kernel before it: under attn,
    # but it is no kernel
    assert out["flash_kernels"] == {"hvd_flash_fwd": 200 + 140,
                                    "hvd_flash_bwd": 400}
    assert out["flash"] == 740
    assert out["mla"] == 70 + 200 + 10 + 140 + 400
    # the module: its projection, its block's maps and attention
    assert out["mtp"] == 50 + 60 + 140
    # the grouped matmul and the shared expert are none of these
    assert "hvd_moe_gmm" not in out["flash_kernels"]


def test_a_program_without_the_names_reads_as_none(monkeypatch):
    monkeypatch.delattr(xing_reduce.sr.names, "HC")
    assert xing_reduce.xing_names() is None
    assert xing_reduce.reduce_file("/nonexistent", None, 1) is None


def test_names_are_the_programs(monkeypatch):
    monkeypatch.setattr(xing_reduce.sr.names, "MTP", "hvd_next")
    assert xing_reduce.self_ns(EVENTS, TABLE)["mtp"] == 0
    table = {k: v.replace("hvd_mtp", "hvd_next") for k, v in TABLE.items()}
    assert xing_reduce.self_ns(EVENTS, table)["mtp"] == 250


def test_a_trace_of_another_program_reads_as_none():
    """The recorded trace of the dense LM names no hyper-connection: the
    new metrics are left out of such a line (the parent's, in this PR)."""
    import os

    from benchmark import trace_reduce as tr

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "synthetic_scoped.xplane.pb")
    assert xing_reduce.reduce_file(path, tr.load(path), 2) is None
