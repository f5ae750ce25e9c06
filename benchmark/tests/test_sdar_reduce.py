"""`sdar_reduce.self_ns` on hand-made events with scope paths: what counts
as block diffusion's own work (`hvd_bd`, by direction), as attention (a
block's `attn` half and the flash kernels), as the flash kernels (by name,
not by `tpu_custom_call`); and that a program without the name reads as
nothing."""

from benchmark import sdar_reduce
from benchmark.trace_reduce import Event

STEP = "jit(shard_step)/hvd_fwd_bwd/"
FWD = STEP + "jvp(Transformer)/"
BWD = STEP + "transpose(jvp(Transformer))/"


def event(name, start, end, target=""):
    opcode = "custom-call" if target else "fusion"
    return Event(name, opcode, "bf16[8]", target, start, end)


EVENTS = [
    event("fusion.1", 0, 30),                                   # the noise
    event("fusion.2", 30, 50),                                  # doubled ids
    event("fusion.3", 50, 90),                                  # embedding
    event("fusion.4", 90, 160),                                 # q proj
    event("fusion.5", 160, 180),                                # q_norm
    event("hvd_flash_fwd.1", 180, 380, "tpu_custom_call"),
    event("copy.7", 380, 390),              # compiler-inserted, no op_name
    event("hvd_moe_gmm.1", 390, 540, "tpu_custom_call"),        # no flash
    event("hvd_moe_rows.1", 540, 560, "tpu_custom_call"),       # no flash
    event("fusion.6", 560, 575),                                # the slice
    event("while.1", 575, 775),                                 # the loss
    event("fusion.7", 775, 800),            # the slice's transpose: a pad
    event("hvd_flash_dq.1", 800, 1100, "tpu_custom_call"),
    event("hvd_flash_dkv.1", 1100, 1500, "tpu_custom_call"),
    event("fusion.8", 1500, 1560),                              # k proj bwd
    event("fusion.9", 1560, 1660),                              # optimizer
]
TABLE = {
    "fusion.1": STEP + "hvd_bd/vmap(noise)/threefry2x32",
    "fusion.2": STEP + "hvd_bd/concatenate",
    "fusion.3": FWD + "hvd_embed/embed/gather",
    "fusion.4": FWD + "hvd_block/block_0/attn/query/dot_general",
    "fusion.5": FWD + "hvd_block/block_0/attn/q_norm/mul",
    "hvd_flash_fwd.1": FWD + "hvd_block/block_0/attn/hvd_flash_fwd/"
                       "pallas_call",
    "hvd_moe_gmm.1": FWD + "hvd_block/block_0/mlp/moe_mlp/hvd_moe/"
                     "hvd_moe_experts/hvd_moe_gmm/pallas_call",
    "hvd_moe_rows.1": FWD + "hvd_block/block_0/mlp/moe_mlp/hvd_moe/"
                      "hvd_moe_dispatch/hvd_moe_rows/pallas_call",
    "fusion.6": STEP + "jvp(hvd_bd)/slice",
    "while.1": STEP + "jvp(hvd_loss)/while",
    "fusion.7": STEP + "transpose(jvp(hvd_bd))/pad",
    "hvd_flash_dq.1": BWD + "hvd_block/block_0/attn/hvd_flash_dq/"
                      "pallas_call",
    "hvd_flash_dkv.1": BWD + "hvd_block/block_0/attn/hvd_flash_dkv/"
                       "pallas_call",
    "fusion.8": BWD + "hvd_block/block_0/attn/key/dot_general",
    "fusion.9": "jit(shard_step)/hvd_optimizer/mul",
}


def test_self_ns_by_hand():
    out = sdar_reduce.self_ns(EVENTS, TABLE)
    assert out["bd_parts"] == {"fwd": 30 + 20 + 15, "bwd": 25}
    assert out["bd"] == sum(out["bd_parts"].values()) == 90
    # the unnamed copy counts with the flash kernel before it: under attn,
    # but it is no kernel
    assert out["flash_kernels"] == {"hvd_flash_fwd": 200,
                                    "hvd_flash_dq": 300,
                                    "hvd_flash_dkv": 400}
    assert out["flash"] == 900
    assert out["attn"] == 70 + 20 + 200 + 10 + 300 + 400 + 60
    # the grouped matmul and the rows' kernel are none of these
    assert "hvd_moe_gmm" not in out["flash_kernels"]
    assert "hvd_moe_rows" not in out["flash_kernels"]


def test_a_program_without_the_name_reads_as_none(monkeypatch):
    """The parent of the PR that brought `hvd_bd`: its `profile` has no
    `BD`, and every reader over this reducer leaves its metric out."""
    monkeypatch.delattr(sdar_reduce.sr.names, "BD")
    assert sdar_reduce.sdar_names() is None
    assert sdar_reduce.reduce_file("/nonexistent", None, 1) is None


def test_names_are_the_programs(monkeypatch):
    monkeypatch.setattr(sdar_reduce.sr.names, "BD", "hvd_noise")
    assert sdar_reduce.self_ns(EVENTS, TABLE)["bd"] == 0
    table = {k: v.replace("hvd_bd", "hvd_noise") for k, v in TABLE.items()}
    assert sdar_reduce.self_ns(EVENTS, table)["bd"] == 90


def test_a_trace_of_another_program_reads_as_none():
    """The recorded trace of the dense LM names no block diffusion: the new
    metrics are left out of such a line."""
    import os

    from benchmark import trace_reduce as tr

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "synthetic_scoped.xplane.pb")
    assert sdar_reduce.reduce_file(path, tr.load(path), 2) is None
