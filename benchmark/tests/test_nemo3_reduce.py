"""`nemo3_reduce.self_ns` on hand-made events with scope paths: what counts
as the Mamba-2 mixer (by inner scope and direction), as the scan, as the
flash kernels (by name, not by `tpu_custom_call`); the readers on a
synthetic trace and on one of another program."""

import os

import pytest

from benchmark import nemo3_reduce
from benchmark.run import load_plugin
from benchmark.trace_reduce import Event

STEP = "jit(shard_step)/hvd_fwd_bwd/"
FWD = STEP + "jvp(Transformer)/"
BWD = STEP + "transpose(jvp(Transformer))/"


def event(name, start, end, target=""):
    opcode = "custom-call" if target else "fusion"
    return Event(name, opcode, "bf16[8]", target, start, end)


EVENTS = [
    event("fusion.1", 0, 40),                                   # embedding
    event("fusion.2", 40, 140),                                 # in_proj
    event("fusion.3", 140, 170),                                # conv
    event("fusion.4", 170, 260),                                # scan
    event("copy.7", 260, 270),              # compiler-inserted, no op_name
    event("fusion.5", 270, 300),                                # gate, norm
    event("hvd_flash_fwd.1", 300, 500, "tpu_custom_call"),
    event("hvd_moe_gmm.1", 500, 650, "tpu_custom_call"),        # no flash
    event("fusion.6", 650, 700),                                # latent
    event("hvd_flash_bwd.1", 700, 1100, "tpu_custom_call"),
    event("fusion.7", 1100, 1300),                              # scan bwd
    event("fusion.8", 1300, 1350),              # scan again, recomputed
    event("fusion.9", 1350, 1400),                              # conv bwd
    event("fusion.10", 1400, 1500),                             # optimizer
]
TABLE = {
    "fusion.1": FWD + "hvd_embed/embed/gather",
    "fusion.2": FWD + "hvd_block/block_0/hvd_ssm/ssm/in_proj/dot_general",
    "fusion.3": FWD + "hvd_block/block_0/hvd_ssm/ssm/hvd_ssm_conv/add",
    "fusion.4": FWD + "hvd_block/block_0/hvd_ssm/ssm/hvd_ssd/dot_general",
    "fusion.5": FWD + "hvd_block/block_0/hvd_ssm/ssm/mul",
    "hvd_flash_fwd.1": FWD + "hvd_block/block_9/attn/hvd_flash_fwd/"
                       "pallas_call",
    "hvd_moe_gmm.1": FWD + "hvd_block/block_1/mlp/moe_mlp/hvd_moe/"
                     "hvd_moe_experts/jit(_gmm)/hvd_moe_gmm/pallas_call",
    "fusion.6": FWD + "hvd_block/block_1/mlp/moe_mlp/hvd_moe/"
                "hvd_moe_latent/latent_in/dot_general",
    "hvd_flash_bwd.1": BWD + "hvd_block/block_9/attn/hvd_flash_bwd/"
                       "pallas_call",
    "fusion.7": BWD + "hvd_block/block_0/hvd_ssm/ssm/hvd_ssd/dot_general",
    "fusion.8": BWD + "hvd_block/checkpoint/rematted_computation/block_0/"
                "hvd_ssm/ssm/hvd_ssd/exp",
    "fusion.9": BWD + "hvd_block/block_0/hvd_ssm/ssm/hvd_ssm_conv/mul",
    "fusion.10": "jit(shard_step)/hvd_optimizer/mul",
}


def test_self_ns_by_hand():
    out = nemo3_reduce.self_ns(EVENTS, TABLE)
    # the unnamed copy counts with the scan before it
    assert out["ssm_parts"] == {
        "hvd_ssm fwd": 100 + 30, "hvd_ssm_conv fwd": 30,
        "hvd_ssd fwd": 90 + 10, "hvd_ssd bwd": 200 + 50,
        "hvd_ssm_conv bwd": 50}
    assert out["ssm"] == sum(out["ssm_parts"].values()) == 560
    assert out["ssd"] == 100 + 250
    assert out["flash_kernels"] == {"hvd_flash_fwd": 200,
                                    "hvd_flash_bwd": 400}
    assert out["flash"] == 600


def test_a_program_without_the_names_reads_as_none(monkeypatch):
    monkeypatch.delattr(nemo3_reduce.sr.names, "SSM")
    assert nemo3_reduce.nemo3_names() is None
    assert nemo3_reduce.reduce_file("/nonexistent", None, 1) is None


def test_names_are_the_programs(monkeypatch):
    monkeypatch.setattr(nemo3_reduce.sr.names, "SSD", "hvd_scan")
    monkeypatch.setattr(nemo3_reduce.sr.names, "SSM_SCOPES",
                        ("hvd_ssm", "hvd_ssm_conv", "hvd_scan"))
    assert nemo3_reduce.self_ns(EVENTS, TABLE)["ssd"] == 0
    table = {k: v.replace("hvd_ssd", "hvd_scan") for k, v in TABLE.items()}
    assert nemo3_reduce.self_ns(EVENTS, table)["ssd"] == 350


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "synthetic_scoped.xplane.pb")


def test_a_trace_of_another_program_reads_as_none():
    """The recorded trace of the dense LM names no Mamba-2 mixer: the new
    metrics are left out of such a line (the parent's, in this PR)."""
    from benchmark import trace_reduce as tr

    assert nemo3_reduce.reduce_file(DATA, tr.load(DATA), 2) is None


@pytest.mark.parametrize("metric", [
    "ssm_ms", "ssd_ms", "ssd_roofline", "flash_ms.nemo3",
    "flash_roofline.nemo3"])
def test_each_reader_reads_a_number_or_nothing(metric, monkeypatch):
    """On the hand-made reduction a number, on another program's None."""
    reader = load_plugin("layer_metrics", metric)
    made = {"ssm": 5.6, "ssd": 3.5, "flash": 6.0}
    context = {"counts": {"ssd_executed_flops": 197e12 * 1.75e-3,
                          "ssd_min_bytes": 1.0,
                          "flash_executed_flops": 197e12 * 3e-3,
                          "flash_min_bytes": 1.0,
                          "flash_kernels": ["hvd_flash_fwd",
                                            "hvd_flash_bwd"]},
               "peaks": {"bf16_flops_per_s": 197e12,
                         "hbm_bytes_per_s": 819e9}}
    monkeypatch.setattr(nemo3_reduce, "ms",
                        lambda trace, context, what: made[what])
    if metric == "flash_roofline.nemo3":
        from benchmark.layer_metrics import flash_roofline
        monkeypatch.setattr(
            flash_roofline, "flash_kernels_ms",
            lambda trace, context: {"hvd_flash_fwd": 2.0,
                                    "hvd_flash_bwd": 4.0})
    want = {"ssm_ms": 5.6, "ssd_ms": 3.5, "ssd_roofline": 50.0,
            "flash_ms.nemo3": 6.0, "flash_roofline.nemo3": 50.0}[metric]
    assert reader.read(None, context) == pytest.approx(want)
    monkeypatch.setattr(nemo3_reduce, "ms",
                        lambda trace, context, what: None)
    assert reader.read(None, context) is None
    if "roofline" in metric:  # and where the builder counted nothing
        monkeypatch.setattr(nemo3_reduce, "ms",
                            lambda trace, context, what: made[what])
        assert reader.read(None, dict(context, counts={})) is None


@pytest.mark.parametrize("metric", [
    "moe_ms.nemo3", "moe_gmm_ms.nemo3", "moe_shuffle_ms.nemo3",
    "moe_gmm_roofline.nemo3"])
def test_the_suffixed_readers_are_the_readers_that_exist(metric):
    import importlib

    mine = load_plugin("layer_metrics", metric)
    theirs = importlib.import_module(
        "benchmark.layer_metrics." + metric[:-len(".nemo3")])
    assert mine.read is theirs.read
