"""`laguna_reduce` on hand-made events with scope paths: what counts under
the gate's scope, by the kind around it; the kinds' own times and kernels
(two head counts under one kernel name); and that a program without the
gate's name reads as nothing."""

from benchmark import laguna_reduce
from benchmark.trace_reduce import Event

STEP = "jit(shard_step)/hvd_fwd_bwd/"
FWD = STEP + "jvp(Transformer)/hvd_block/"
BWD = STEP + "transpose(jvp(Transformer))/hvd_block/"
AGAIN = STEP + "transpose(jvp(Transformer))/hvd_block/checkpoint/" \
    "rematted_computation/"
W, F = "hvd_attn_window/", "hvd_attn_full/"
GATE = "attn/hvd_attn_gate/"


def event(name, start, end, target=""):
    opcode = "custom-call" if target else "fusion"
    return Event(name, opcode, "bf16[8]", target, start, end)


EVENTS = [
    event("fusion.1", 0, 40),                                 # q proj, f
    event("fusion.2", 40, 50),                                # gate proj, f
    event("hvd_flash_fwd.1", 50, 250, "tpu_custom_call"),     # causal, 48
    event("fusion.3", 250, 280),                              # o * g, f
    event("fusion.4", 280, 300),                              # gate proj, w
    event("hvd_flash_fwd.2", 300, 360, "tpu_custom_call"),    # band, 64
    event("copy.5", 360, 365),              # compiler-inserted, no op_name
    event("fusion.6", 365, 400),                              # o * g, w
    event("hvd_moe_gmm.1", 400, 500, "tpu_custom_call"),      # no attention
    event("fusion.7", 500, 515),                              # gate again, w
    event("hvd_flash_bwd.1", 515, 700, "tpu_custom_call"),    # band, 64
    event("fusion.8", 700, 760),                              # d gate, w
    event("hvd_flash_bwd.2", 760, 1260, "tpu_custom_call"),   # causal, 48
    event("fusion.9", 1260, 1300),                            # d gate, f
    event("fusion.10", 1300, 1400),                           # optimizer
]
TABLE = {
    "fusion.1": FWD + "block_0/" + F + "attn/hvd_attn_proj/query/dot_general",
    "fusion.2": FWD + "block_0/" + F + GATE + "gate/dot_general",
    "hvd_flash_fwd.1": FWD + "block_0/" + F + "attn/hvd_flash_fwd/"
                       "pallas_call",
    "fusion.3": FWD + "block_0/" + F + GATE + "mul",
    "fusion.4": FWD + "block_1/" + W + GATE + "gate/dot_general",
    "hvd_flash_fwd.2": FWD + "block_1/" + W + "attn/jit(_ruled_hvd_flash_"
                       "fwd)/hvd_flash_fwd/pallas_call",
    "fusion.6": FWD + "block_1/" + W + GATE + "mul",
    "hvd_moe_gmm.1": FWD + "block_1/mlp/moe_mlp/hvd_moe/hvd_moe_experts/"
                     "hvd_moe_gmm/pallas_call",
    "fusion.7": AGAIN + "block_1/" + W + GATE + "gate/dot_general",
    "hvd_flash_bwd.1": BWD + "block_1/" + W + "attn/hvd_flash_bwd/"
                       "pallas_call",
    "fusion.8": BWD + "block_1/" + W + GATE + "gate/dot_general",
    "hvd_flash_bwd.2": BWD + "block_0/" + F + "attn/hvd_flash_bwd/"
                       "pallas_call",
    "fusion.9": BWD + "block_0/" + F + GATE + "gate/dot_general",
    "fusion.10": "jit(shard_step)/hvd_optimizer/mul",
}
KINDS = {"hvd_attn_window": "window", "hvd_attn_full": "full"}


def test_the_gates_time_by_hand():
    out = laguna_reduce.gate_ns(EVENTS, TABLE, KINDS)
    # forward, the forward again and the backward alike; the unnamed copy
    # counts with the band's kernel before it, which is no gate
    assert out == {"full": 10 + 30 + 40, "window": 20 + 35 + 15 + 60}
    # a gate under no kind's scope is still the gate's
    bare = {k: v.replace(W, "").replace(F, "") for k, v in TABLE.items()}
    assert laguna_reduce.gate_ns(EVENTS, bare, KINDS) == {"": 210}


def test_the_kinds_are_told_apart_by_scope_not_by_kernel_name():
    out = laguna_reduce.mellum_reduce.self_ns(EVENTS, TABLE)
    assert out["flash"] == {
        "full": {"hvd_flash_fwd": 200, "hvd_flash_bwd": 500},
        "window": {"hvd_flash_fwd": 60, "hvd_flash_bwd": 185}}
    assert out["attn"]["full"] == 40 + 10 + 200 + 30 + 500 + 40
    assert out["attn"]["window"] == 20 + 60 + 5 + 35 + 15 + 185 + 60
    assert out["flash_unscoped"] == 0


def test_a_program_without_the_name_reads_as_none(monkeypatch):
    """The parent of the PR that brought the gate: its `profile` has no
    `ATTN_GATE`, and the reader leaves its metric out."""
    monkeypatch.delattr(laguna_reduce.sr.names, "ATTN_GATE")
    assert laguna_reduce.gate_scope() is None
    assert laguna_reduce.reduce_file("/nonexistent", None, 1) is None


def test_the_name_is_the_programs(monkeypatch):
    monkeypatch.setattr(laguna_reduce.sr.names, "ATTN_GATE", "hvd_gate")
    assert laguna_reduce.gate_ns(EVENTS, TABLE, KINDS) == {}
    table = {k: v.replace("hvd_attn_gate", "hvd_gate")
             for k, v in TABLE.items()}
    assert sum(laguna_reduce.gate_ns(EVENTS, table, KINDS).values()) == 210


def test_a_trace_of_another_program_reads_as_none():
    """The recorded trace of the dense LM names no gate: `attn_gate_ms` is
    left out of such a line."""
    import os

    from benchmark import trace_reduce as tr

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "synthetic_scoped.xplane.pb")
    assert laguna_reduce.reduce_file(path, tr.load(path), 2) is None


def test_the_readers_hand_back_numbers(monkeypatch):
    """Every reader of the cell over one reduction: the kinds' times, their
    kernels', the gate's, and each kind's roofline on its own counts."""
    import importlib.util

    from benchmark.layer_metrics import flash_roofline

    reduced = {"attn": {"full": 90.0, "window": 190.0},
               "flash": {"full": {"hvd_flash_fwd": 20.0,
                                  "hvd_flash_bwd": 30.0},
                         "window": {"hvd_flash_fwd": 25.0,
                                    "hvd_flash_bwd": 55.0}},
               "flash_unscoped": 0.0}
    monkeypatch.setattr(laguna_reduce.mellum_reduce, "reduce",
                        lambda trace, context: reduced)
    monkeypatch.setattr(laguna_reduce, "reduce",
                        lambda trace, context: {"full": 1.5, "window": 4.5})
    monkeypatch.setattr(flash_roofline, "flash_kernels_ms",
                        lambda trace, context: {"hvd_flash_fwd": 1.0,
                                                "hvd_flash_bwd": 1.0})
    context = {"peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12},
               "counts": {"flash_kernels": ["hvd_flash_bwd", "hvd_flash_fwd"],
                          "flash_by_kind": {
                              "window": {"executed_flops": 4e10,
                                         "min_bytes": 1e9},
                              "full": {"executed_flops": 3e10,
                                       "min_bytes": 1e9}}}}
    here = os_path()
    want = {"attn_full_ms.laguna": 90.0, "attn_window_ms.laguna": 190.0,
            "flash_full_ms.laguna": 50.0, "flash_window_ms.laguna": 80.0,
            "flash_full_roofline.laguna": 60.0,
            "flash_window_roofline.laguna": 50.0, "attn_gate_ms": 6.0}
    for name, value in want.items():
        spec = importlib.util.spec_from_file_location(
            "m", here + "/" + name + ".py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.read(None, context) == value, name


def os_path():
    import os
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "layer_metrics")
