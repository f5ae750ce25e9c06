"""`mellum_reduce.self_ns` on hand-made events with scope paths: what counts
under each attention kind's scope, which flash kernel belongs to which kind
(one name, two scopes), a kernel under neither; and that a program without
the names reads as nothing."""

from benchmark import mellum_reduce
from benchmark.trace_reduce import Event

STEP = "jit(shard_step)/hvd_fwd_bwd/"
FWD = STEP + "jvp(Transformer)/hvd_block/"
BWD = STEP + "transpose(jvp(Transformer))/hvd_block/"
AGAIN = STEP + "transpose(jvp(Transformer))/hvd_block/checkpoint/"
W, F = "hvd_attn_window/", "hvd_attn_full/"


def event(name, start, end, target=""):
    opcode = "custom-call" if target else "fusion"
    return Event(name, opcode, "bf16[8]", target, start, end)


EVENTS = [
    event("fusion.1", 0, 40),                                   # embedding
    event("fusion.2", 40, 60),                                  # norm1, w
    event("fusion.3", 60, 130),                                 # q proj, w
    event("hvd_flash_fwd.1", 130, 180, "tpu_custom_call"),      # band
    event("copy.4", 180, 190),              # compiler-inserted, no op_name
    event("hvd_moe_gmm.1", 190, 340, "tpu_custom_call"),        # no flash
    event("fusion.5", 340, 420),                                # q proj, f
    event("hvd_flash_fwd.2", 420, 620, "tpu_custom_call"),      # causal
    event("while.1", 620, 820),                                 # the loss
    event("hvd_flash_fwd.3", 820, 1020, "tpu_custom_call"),     # f, again
    event("hvd_flash_bwd.1", 1020, 1520, "tpu_custom_call"),    # f
    event("hvd_flash_fwd.4", 1520, 1570, "tpu_custom_call"),    # w, again
    event("hvd_flash_bwd.2", 1570, 1700, "tpu_custom_call"),    # w
    event("fusion.6", 1700, 1760),                              # k bwd, w
    event("hvd_flash_fwd.5", 1760, 1800, "tpu_custom_call"),    # no kind
    event("fusion.7", 1800, 1900),                              # optimizer
]
TABLE = {
    "fusion.1": STEP + "jvp(Transformer)/hvd_embed/embed/gather",
    "fusion.2": FWD + "block_0/" + W + "norm1/mul",
    "fusion.3": FWD + "block_0/" + W + "attn/query/dot_general",
    "hvd_flash_fwd.1": FWD + "block_0/" + W + "attn/jit(_ruled_hvd_flash_"
                       "fwd)/hvd_flash_fwd/pallas_call",
    "hvd_moe_gmm.1": FWD + "block_0/mlp/moe_mlp/hvd_moe/hvd_moe_experts/"
                     "hvd_moe_gmm/pallas_call",
    "fusion.5": FWD + "block_3/" + F + "attn/query/dot_general",
    "hvd_flash_fwd.2": FWD + "block_3/" + F + "attn/hvd_flash_fwd/"
                       "pallas_call",
    "while.1": STEP + "jvp(hvd_loss)/while",
    "hvd_flash_fwd.3": AGAIN + "block_3/" + F + "attn/hvd_flash_fwd/"
                       "pallas_call",
    "hvd_flash_bwd.1": BWD + "block_3/" + F + "attn/hvd_flash_bwd/"
                       "pallas_call",
    "hvd_flash_fwd.4": AGAIN + "block_0/" + W + "attn/hvd_flash_fwd/"
                       "pallas_call",
    "hvd_flash_bwd.2": BWD + "block_0/" + W + "attn/hvd_flash_bwd/"
                       "pallas_call",
    "fusion.6": BWD + "block_0/" + W + "attn/key/dot_general",
    "hvd_flash_fwd.5": FWD + "block_1/attn/hvd_flash_fwd/pallas_call",
    "fusion.7": "jit(shard_step)/hvd_optimizer/mul",
}


def test_self_ns_by_hand():
    out = mellum_reduce.self_ns(EVENTS, TABLE)
    # the unnamed copy counts with the band's kernel before it: under the
    # window's scope, but it is no kernel
    assert out["attn"] == {"window": 20 + 70 + 50 + 10 + 50 + 130 + 60,
                           "full": 80 + 200 + 200 + 500}
    assert out["flash"] == {
        "window": {"hvd_flash_fwd": 50 + 50, "hvd_flash_bwd": 130},
        "full": {"hvd_flash_fwd": 200 + 200, "hvd_flash_bwd": 500}}
    assert out["flash_unscoped"] == 40
    # the grouped matmul is none of these
    assert all("hvd_moe_gmm" not in by for by in out["flash"].values())


def test_a_program_without_the_names_reads_as_none(monkeypatch):
    """The parent of the PR that brought the attention kinds: its `profile`
    has no `ATTN_KINDS`, and every reader over this reducer leaves its
    metric out."""
    monkeypatch.delattr(mellum_reduce.sr.names, "ATTN_KINDS")
    assert mellum_reduce.kind_scopes() is None
    assert mellum_reduce.reduce_file("/nonexistent", None, 1) is None


def test_names_are_the_programs(monkeypatch):
    monkeypatch.setattr(mellum_reduce.sr.names, "ATTN_KINDS",
                        {"window": "hvd_local", "full": "hvd_attn_full"})
    out = mellum_reduce.self_ns(EVENTS, TABLE)
    assert out["attn"]["window"] == 0 and out["attn"]["full"] == 980
    table = {k: v.replace("hvd_attn_window", "hvd_local")
             for k, v in TABLE.items()}
    assert mellum_reduce.self_ns(EVENTS, table)["attn"]["window"] == 390


def test_a_trace_of_another_program_reads_as_none():
    """The recorded trace of the dense LM names no attention kind: the new
    metrics are left out of such a line."""
    import os

    from benchmark import trace_reduce as tr

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "synthetic_scoped.xplane.pb")
    assert mellum_reduce.reduce_file(path, tr.load(path), 2) is None


def test_the_roofline_is_of_the_kinds_own_counts(monkeypatch):
    """`flash_roofline` hands `flash_roofline.share` the kind's counts and
    the kind's time: 100 ms of kernels whose operations need 40 ms at the
    peak read 40%."""
    from benchmark.layer_metrics import flash_roofline

    monkeypatch.setattr(mellum_reduce, "flash_ms",
                        lambda trace, context, kind: 100.0)
    monkeypatch.setattr(flash_roofline, "flash_kernels_ms",
                        lambda trace, context: {"hvd_flash_fwd": 1.0,
                                                "hvd_flash_bwd": 1.0})
    context = {"peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12},
               "counts": {"flash_kernels": ["hvd_flash_bwd", "hvd_flash_fwd"],
                          "flash_by_kind": {
                              "window": {"executed_flops": 4e10,
                                         "min_bytes": 1e9},
                              "full": {"executed_flops": 9e10,
                                       "min_bytes": 1e9}}}}
    assert mellum_reduce.flash_roofline(None, context, "window") == 40.0
    assert mellum_reduce.flash_roofline(None, context, "full") == 90.0
    assert mellum_reduce.flash_roofline(None, context, "latent") is None
