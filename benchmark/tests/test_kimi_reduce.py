"""`kimi_reduce.self_ns` on hand-made events with scope paths: what counts
as a KDA layer's attention half and by which inner scope, forward and
backward apart; what counts as latent attention (the `hvd_attn_full` half
and the flash kernels) although BOTH mixers are flax's `attn`; and that a
program without the names reads as nothing."""

from benchmark import kimi_reduce
from benchmark.trace_reduce import Event

STEP = "jit(shard_step)/hvd_fwd_bwd/"
FWD = STEP + "jvp(Transformer)/"
AGAIN = STEP + "checkpoint/rematted_computation/Transformer/"
BWD = STEP + "transpose(jvp(Transformer))/"
KDA = "hvd_block/block_1/hvd_kda/"
FULL = "hvd_block/block_3/hvd_attn_full/attn/"


def event(name, start, end, target=""):
    opcode = "custom-call" if target else "fusion"
    return Event(name, opcode, "bf16[8]", target, start, end)


EVENTS = [
    event("fusion.1", 0, 40),                                   # embedding
    event("fusion.2", 40, 60),                                  # norm1
    event("fusion.3", 60, 160),                                 # in_proj
    event("fusion.4", 160, 190),                                # conv
    event("fusion.5", 190, 210),                                # gate
    event("hvd_kda_scores.1", 210, 300, "tpu_custom_call"),
    event("fusion.6", 300, 380),                                # solve
    event("copy.7", 380, 390),              # compiler-inserted, no op_name
    event("while.1", 390, 690),                                 # the scan
    event("hvd_moe_gmm.1", 690, 800, "tpu_custom_call"),
    event("fusion.8", 800, 850),                                # latent q
    event("hvd_flash_fwd.1", 850, 1050, "tpu_custom_call"),
    event("while.2", 1050, 1150),                               # the loss
    event("hvd_kda_scores.2", 1150, 1230, "tpu_custom_call"),   # recomputed
    event("hvd_flash_bwd.1", 1230, 1830, "tpu_custom_call"),
    event("while.3", 1830, 2430),                               # scan, bwd
    event("hvd_kda_scores_bwd.1", 2430, 2630, "tpu_custom_call"),
    event("fusion.9", 2630, 2700),                              # in_proj bwd
    event("fusion.10", 2700, 2800),                             # optimizer
]
TABLE = {
    "fusion.1": FWD + "hvd_embed/embed/gather",
    "fusion.2": FWD + KDA + "norm1/mul",
    "fusion.3": FWD + KDA + "attn/hvd_kda_proj/in_proj/dot_general",
    "fusion.4": FWD + KDA + "attn/hvd_kda_conv/mul",
    "fusion.5": FWD + KDA + "attn/hvd_kda_gate/softplus",
    "hvd_kda_scores.1": FWD + KDA + "attn/hvd_kda_chunk/jit(_pallas_own)/"
                        "hvd_kda_scores/pallas_call",
    "fusion.6": FWD + KDA + "attn/hvd_kda_chunk/triangular_solve",
    "while.1": FWD + KDA + "attn/hvd_kda_carry/while",
    "hvd_moe_gmm.1": FWD + "hvd_block/block_1/mlp/moe_mlp/hvd_moe/"
                     "hvd_moe_experts/jit(_gmm)/hvd_moe_gmm/pallas_call",
    "fusion.8": FWD + FULL + "hvd_attn_proj/q/dot_general",
    "hvd_flash_fwd.1": FWD + FULL + "hvd_flash_fwd/pallas_call",
    "while.2": STEP + "jvp(hvd_loss)/while",
    "hvd_kda_scores.2": AGAIN + KDA + "attn/hvd_kda_chunk/jit(_pallas_own)/"
                        "hvd_kda_scores/pallas_call",
    "hvd_flash_bwd.1": BWD + FULL + "hvd_flash_bwd/pallas_call",
    "while.3": BWD + KDA + "attn/hvd_kda_carry/while",
    "hvd_kda_scores_bwd.1": BWD + KDA + "attn/hvd_kda_chunk/"
                            "jit(_pallas_own)/hvd_kda_scores_bwd/pallas_call",
    "fusion.9": BWD + KDA + "attn/hvd_kda_proj/in_proj/dot_general",
    "fusion.10": "jit(shard_step)/hvd_optimizer/mul",
}


def test_self_ns_by_hand():
    out = kimi_reduce.self_ns(EVENTS, TABLE)
    # the unnamed copy counts with the solve before it
    chunk = 90 + 80 + 10 + 80 + 200
    assert out["kda_by_scope"] == {
        "hvd_kda": 20, "hvd_kda_proj": 100 + 70, "hvd_kda_conv": 30,
        "hvd_kda_gate": 20, "hvd_kda_chunk": chunk,
        "hvd_kda_carry": 300 + 600}
    assert out["kda"] == sum(out["kda_by_scope"].values()) \
        == sum(out["kda_parts"].values())
    assert out["kda_parts"]["hvd_kda_carry bwd"] == 600
    assert out["kda_parts"]["hvd_kda_chunk fwd"] == 90 + 80 + 10 + 80
    assert out["kda_parts"]["hvd_kda_chunk bwd"] == 200
    # latent attention: its half and the flash kernels, NOT the KDA layers'
    # `attn` module
    assert out["mla"] == 50 + 200 + 600
    assert out["flash_kernels"] == {"hvd_flash_fwd": 200,
                                    "hvd_flash_bwd": 600}
    assert out["flash"] == 800
    # the mixer's own kernels, inside the chunk's scope
    assert out["kda_kernels"] == {"hvd_kda_scores": 90 + 80,
                                  "hvd_kda_scores_bwd": 200}
    assert out["kda_kernel"] == 370 < chunk
    assert "hvd_moe_gmm" not in out["flash_kernels"]


def test_a_program_without_the_names_reads_as_none(monkeypatch):
    from benchmark import scope_reduce as sr

    monkeypatch.setattr(sr, "names", None)
    assert kimi_reduce.reduce_file("nowhere", None, 10) is None


def test_a_program_older_than_the_mixer_reads_as_none(monkeypatch):
    """The parent of the PR that brought the mixer: `horovod_tpu.profile`
    has no `KDA`, and every reader over this reducer leaves its metric
    out."""
    from benchmark import scope_reduce as sr

    monkeypatch.delattr(sr.names, "KDA")
    assert kimi_reduce.kimi_names() is None
    assert kimi_reduce.reduce_file("nowhere", None, 10) is None
