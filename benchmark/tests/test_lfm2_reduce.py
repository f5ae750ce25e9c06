"""`lfm2_reduce` on hand-made events with scope paths: what counts under the
conv mixer's scope, by the part inside it and by direction; that the
attention layers' kernels are read by their kind's scope; and that a program
without the mixer's name reads as nothing."""

from benchmark import lfm2_reduce
from benchmark.trace_reduce import Event

STEP = "jit(shard_step)/hvd_fwd_bwd/"
FWD = STEP + "jvp(Transformer)/hvd_block/"
BWD = STEP + "transpose(jvp(Transformer))/hvd_block/"
AGAIN = STEP + "transpose(jvp(Transformer))/hvd_block/checkpoint/" \
    "rematted_computation/"
S, F = "hvd_sconv/", "hvd_attn_full/"
PROJ, GATE = "attn/hvd_sconv_proj/", "attn/hvd_sconv_gate/"


def event(name, start, end, target=""):
    opcode = "custom-call" if target else "fusion"
    return Event(name, opcode, "bf16[8]", target, start, end)


EVENTS = [
    event("fusion.1", 0, 10),                                 # norm1, f
    event("fusion.2", 10, 50),                                # in_proj, f
    event("fusion.3", 50, 80),                                # the pass, f
    event("copy.4", 80, 85),                # compiler-inserted, no op_name
    event("fusion.5", 85, 110),                               # out_proj, f
    event("hvd_flash_fwd.1", 110, 310, "tpu_custom_call"),    # block_2
    event("hvd_moe_gmm.1", 310, 410, "tpu_custom_call"),      # no mixer
    event("fusion.6", 410, 440),                              # the pass again
    event("fusion.7", 440, 520),                              # the pass, b
    event("fusion.8", 520, 600),                              # in_proj, b
    event("hvd_flash_bwd.1", 600, 1100, "tpu_custom_call"),   # block_2
    event("fusion.9", 1100, 1200),                            # optimizer
]
TABLE = {
    "fusion.1": FWD + "block_0/" + S + "norm1/mul",
    "fusion.2": FWD + "block_0/" + S + PROJ + "in_proj/dot_general",
    "fusion.3": FWD + "block_0/" + S + GATE + "mul",
    "fusion.5": FWD + "block_0/" + S + PROJ + "out_proj/dot_general",
    "hvd_flash_fwd.1": FWD + "block_2/" + F + "attn/hvd_flash_fwd/"
                       "pallas_call",
    "hvd_moe_gmm.1": FWD + "block_2/mlp/moe_mlp/hvd_moe/hvd_moe_experts/"
                     "hvd_moe_gmm/pallas_call",
    "fusion.6": AGAIN + "block_0/" + S + GATE + "mul",
    "fusion.7": BWD + "block_0/" + S + GATE + "mul",
    "fusion.8": BWD + "block_0/" + S + PROJ + "in_proj/dot_general",
    "hvd_flash_bwd.1": BWD + "block_2/" + F + "attn/hvd_flash_bwd/"
                       "pallas_call",
    "fusion.9": "jit(shard_step)/hvd_optimizer/mul",
}


def test_the_mixers_time_by_hand():
    out = lfm2_reduce.self_ns(EVENTS, TABLE)
    # the unnamed copy counts with the pass before it
    assert out["by_scope"] == {"hvd_sconv": 10,
                               "hvd_sconv_proj": 40 + 25 + 80,
                               "hvd_sconv_gate": 30 + 5 + 30 + 80}
    assert out["sconv"] == sum(out["by_scope"].values()) == 300
    # a recomputed forward lies inside `transpose(`: it reads as backward
    assert out["parts"] == {
        "hvd_sconv fwd": 10, "hvd_sconv_proj fwd": 65,
        "hvd_sconv_proj bwd": 80, "hvd_sconv_gate fwd": 35,
        "hvd_sconv_gate bwd": 110}


def test_the_attention_layers_kernels_are_read_under_their_kind():
    out = lfm2_reduce.mellum_reduce.self_ns(EVENTS, TABLE)
    assert out["flash"]["full"] == {"hvd_flash_fwd": 200,
                                    "hvd_flash_bwd": 500}
    assert out["attn"]["full"] == 700 and out["flash_unscoped"] == 0


def test_a_program_without_the_name_reads_as_nothing(monkeypatch):
    class Older:
        KDA_SCOPES = ("hvd_kda",)

    monkeypatch.setattr(lfm2_reduce.sr, "names", Older)
    assert lfm2_reduce.sconv_names() is None
    assert lfm2_reduce.reduce_file("nowhere", None, 1) is None
    monkeypatch.setattr(lfm2_reduce.sr, "names", None)
    assert lfm2_reduce.reduce_file("nowhere", None, 1) is None


def test_the_roofline_is_the_one_pass_bytes_over_the_scopes_time(monkeypatch):
    reduced = {"sconv": 50.0, "by_scope": {"hvd_sconv_gate": 40.0,
                                           "hvd_sconv_proj": 9.0}}
    monkeypatch.setattr(lfm2_reduce, "reduce", lambda trace, ctx: reduced)
    context = {"counts": {"sconv_gate_min_bytes": 8.19e9},
               "peaks": {"hbm_bytes_per_s": 819e9}}
    assert lfm2_reduce.ms(None, context) == 50.0
    assert lfm2_reduce.ms(None, context, "SCONV_PROJ") == 9.0
    # 10 ms of bytes over 40 ms of device time
    assert abs(lfm2_reduce.gate_roofline(None, context) - 25.0) < 1e-9
    # nothing counted, or nothing ran: nothing to read
    assert lfm2_reduce.gate_roofline(None, dict(context, counts={})) is None
    reduced["by_scope"].pop("hvd_sconv_gate")
    assert lfm2_reduce.gate_roofline(None, context) is None
