"""`moe_reduce.self_ns` on hand-made events: what counts as the routed
feed-forward, what as a grouped-matmul kernel, and that a flash kernel (a
`tpu_custom_call` too) is neither."""

from benchmark import moe_reduce
from benchmark.trace_reduce import Event

STEP = "jit(shard_step)/hvd_fwd_bwd/"
MOE = "jvp(Transformer)/hvd_block/block_0/mlp/moe_mlp/hvd_moe/"
BWD = "transpose(hvd_fwd_bwd)/" + MOE


def event(name, start, end, target=""):
    opcode = "custom-call" if target else "fusion"
    return Event(name, opcode, "bf16[8]", target, start, end)


EVENTS = [
    event("fusion.1", 0, 100),                                   # attention
    event("hvd_flash_fwd.1", 100, 300, "tpu_custom_call"),
    event("fusion.2", 300, 330),                                 # router
    event("sort.1", 330, 350),
    event("copy.9", 350, 360),              # compiler-inserted, no op_name
    event("fusion.3", 360, 400),                                 # gather
    event("hvd_moe_gmm.1", 400, 700, "tpu_custom_call"),
    event("fusion.4", 700, 720),                                 # the gate
    event("fusion.5", 720, 760),                                 # combine
    event("hvd_moe_gmm_dlhs.1", 760, 1000, "tpu_custom_call"),
    event("closed_call.7", 1000, 1200, "tpu_custom_call"),  # named by path
    event("fusion.6", 1200, 1300),                               # optimizer
]
TABLE = {
    "fusion.1": STEP + "jvp(Transformer)/hvd_block/block_0/attn/dot_general",
    "hvd_flash_fwd.1": STEP + "jvp(Transformer)/hvd_block/block_0/attn/"
                       "hvd_flash_fwd/pallas_call",
    "fusion.2": STEP + MOE + "hvd_moe_route/dot_general",
    "sort.1": STEP + MOE + "hvd_moe_dispatch/sort",
    "fusion.3": STEP + MOE + "hvd_moe_dispatch/gather",
    "hvd_moe_gmm.1": STEP + MOE + "hvd_moe_experts/hvd_moe_gmm/pallas_call",
    "fusion.4": STEP + MOE + "hvd_moe_experts/mul",
    "fusion.5": STEP + MOE + "hvd_moe_combine/reduce_sum",
    "hvd_moe_gmm_dlhs.1": STEP + BWD + "hvd_moe_experts/hvd_moe_gmm_dlhs/"
                          "pallas_call",
    "closed_call.7": STEP + BWD + "hvd_moe_experts/hvd_moe_gmm_drhs/"
                     "pallas_call",
    "fusion.6": "jit(shard_step)/hvd_optimizer/mul",
}


def test_self_ns_by_hand():
    out = moe_reduce.self_ns(EVENTS, TABLE)
    # everything from the router (300) to the last kernel (1200): 900
    assert out["moe"] == 900
    assert out["gmm"] == 300 + 240 + 200
    assert out["kernels"] == {"hvd_moe_gmm": 300, "hvd_moe_gmm_dlhs": 240,
                              "hvd_moe_gmm_drhs": 200}
    # the unnamed copy counts with the sort before it
    assert out["scopes"] == {
        "hvd_moe_route": 30, "hvd_moe_dispatch": 20 + 10 + 40,
        "hvd_moe_experts": 300 + 20 + 240 + 200, "hvd_moe_combine": 40}
    assert sum(out["scopes"].values()) == out["moe"]


def test_a_flash_kernel_is_no_grouped_matmul():
    flash = EVENTS[1]
    assert flash.target == "tpu_custom_call"
    out = moe_reduce.self_ns([flash], TABLE)
    assert out["moe"] == 0 and out["kernels"] == {}
    # by instruction name where the event has no scope path
    out = moe_reduce.self_ns([EVENTS[6]], {})
    assert out["kernels"] == {"hvd_moe_gmm": 300} and out["moe"] == 300


def test_names_are_the_programs(monkeypatch):
    """The scope and the kernels are read from `horovod_tpu.profile`: a
    renamed kernel is followed, not silently read as zero."""
    profile = moe_reduce.sr.names
    renamed = ("hvd_moe_gmm", "hvd_moe_gmm_dlhs", "hvd_experts_dw")
    monkeypatch.setattr(profile, "MOE_GMM_KERNELS", renamed)
    monkeypatch.setattr(profile, "KERNELS", profile.KERNELS + renamed[2:])
    table = {k: v.replace("hvd_moe_gmm_drhs", "hvd_experts_dw")
             for k, v in TABLE.items()}
    out = moe_reduce.self_ns(EVENTS, table)
    assert out["kernels"]["hvd_experts_dw"] == 200
    assert out["gmm"] == 300 + 240 + 200


def test_a_program_without_the_names_reads_as_none(monkeypatch):
    """The parent of PR 26 has no `profile.MOE`: nothing is read, nothing
    raises (the trace is not even opened)."""
    monkeypatch.delattr(moe_reduce.sr.names, "MOE")
    assert moe_reduce.moe_names() is None
    assert moe_reduce.reduce_file("/no/such/trace", None, 1) is None
    monkeypatch.setattr(moe_reduce.sr, "names", None)
    assert moe_reduce.reduce_file("/no/such/trace", None, 1) is None
