"""The analytic counts against hand-worked values for both configurations."""

import json
import os

import pytest

from benchmark import flops

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_transformer_parameters_by_hand():
    # a layer: 4 * 2048^2 = 16,777,216 and 2 * 2048 * 8192 = 33,554,432,
    # together 50,331,648; head 2048 * 50304 = 103,022,592
    assert flops.transformer_matmul_params(2048, 8192, 50304, 8) == \
        8 * 50_331_648 + 103_022_592 == 505_675_776
    # + embedding 103,022,592 + norms (2 * 8 + 1) * 2048 = 34,816
    assert flops.transformer_params(2048, 8192, 50304, 8) == 608_733_184


def test_transformer_model_flops_by_hand():
    # dense: 6 * 505,675,776 = 3,034,054,656 a token
    # attention: 8 layers * 6 products * (2 * 16 * 2048^2 * 128 / 2) / 2048
    #          = 8 * 6 * 8,589,934,592 / 2048 = 201,326,592 a token
    got = flops.transformer_model_flops_per_token(
        2048, 8192, 50304, 8, 16, 128, 2048)
    assert got == 3_034_054_656 + 201_326_592
    assert got * 4096 == pytest.approx(13.252e12, rel=1e-4)  # a step


def test_configuration_file_gives_the_same_counts():
    c = config("neox1b4_w2048")
    assert (c["hidden_size"], c["num_attention_heads"],
            c["intermediate_size"], c["vocab_size"],
            c["max_position_embeddings"]) == (2048, 16, 8192, 50304, 2048)
    assert c["hidden_size"] // c["num_attention_heads"] == 128
    assert flops.transformer_params(
        c["hidden_size"], c["intermediate_size"], c["vocab_size"],
        c["num_hidden_layers"]) == 608_733_184


ONE = ["hvd_flash_fwd", "hvd_flash_bwd"]
TWO = ["hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"]


def test_flash_kernel_counts_by_hand():
    # one product, B=2 H=16 L=2048 D=128, causal: 2*2*16*2048^2*128/2
    assert flops.attention_matmul_flops(2, 16, 2048, 128) == 17_179_869_184
    assert flops.attention_matmul_flops(2, 16, 2048, 128, causal=False) == \
        34_359_738_368
    # forward 2 + the one-kernel backward 5 products; as two kernels dQ 3 +
    # dK/dV 4: the scores and dP are formed twice
    assert flops.flash_executed_flops(ONE, 2, 16, 2048, 128) == \
        7 * 17_179_869_184
    assert flops.flash_executed_flops(TWO, 2, 16, 2048, 128) == \
        9 * 17_179_869_184
    assert flops.flash_executed_flops(["hvd_flash_fwd"], 2, 16, 2048,
                                      128) == 2 * 17_179_869_184
    # a q-like tensor: 2*16*2048*128*2 bytes = 16,777,216; a row statistic
    # 2*16*2048*4 = 262,144. forward 4 tensors + 1 row, dQ 5 + 2, dK/dV 6 + 2
    assert flops.flash_min_bytes(TWO, 2, 16, 16, 2048, 128) == \
        15 * 16_777_216 + 5 * 262_144
    # the one-kernel backward reads q, k, v, dO once for all three results:
    # 7 tensors + 2 rows where the two kernels move 11 + 4
    assert flops.flash_min_bytes(ONE, 2, 16, 16, 2048, 128) == \
        11 * 16_777_216 + 3 * 262_144
    # grouped-query: k and v (8 of the 15) shrink with the kv heads
    assert flops.flash_min_bytes(TWO, 2, 16, 4, 2048, 128) == \
        7 * 16_777_216 + 8 * 4_194_304 + 5 * 262_144


@pytest.mark.parametrize("cell,shape,products", [
    ("lm1b4_1chip / lm1b4_dp4", (2, 16, 2048, 128, 1), 7),
    ("olmoe1b7_1chip", (1, 16, 4096, 128, 1), 7),
    ("ouro2b6_1chip", (1, 16, 4096, 128, 1), 7),
    ("past the one-kernel backward's budget", (1, 16, 8192, 128, 1), 9),
    ("a kv head's eight queries at 8192", (1, 32, 8192, 128, 8), 9)])
def test_the_count_follows_the_kernels_the_plan_names(cell, shape, products):
    import jax.numpy as jnp

    from horovod_tpu import profile

    batch, heads, length, head_dim, group = shape
    forward, backward = (list(profile.flash_plan(
        batch, heads, length, head_dim, group, jnp.bfloat16, b))
        for b in (False, True))
    assert forward == [profile.FLASH_FWD]
    assert backward == ([profile.FLASH_BWD] if products == 7
                        else [profile.FLASH_DQ, profile.FLASH_DKV])
    one = flops.attention_matmul_flops(batch, heads, length, head_dim)
    assert flops.flash_executed_flops(forward + backward, batch, heads,
                                      length, head_dim) == products * one
    assert set(forward + backward) <= set(flops.FLASH_EXECUTED_MATMULS)


@pytest.mark.parametrize("cell,passes", [
    ("lm1b4_1chip", 1), ("lm1b4_dp4", 1), ("olmoe1b7_1chip", 1),
    ("ouro2b6_1chip", 4)])
def test_a_causal_cells_builder_counts_the_one_kernel_backward(cell, passes):
    """The builders hand `flops` the kernels the program's plan names for
    the cell's own shapes: since PR 33 one backward kernel, so 7 products a
    layer (pass), where the count once stood at 9."""
    import jax

    from benchmark.run import BENCH_DIR, ROOT, find_cell, load_json, \
        load_plugin
    from horovod_tpu import parallel

    found, entry = find_cell(load_json(os.path.join(ROOT, "BENCHMARK.json")),
                             cell)
    c = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     found["traffic"] + ".json"))
    c.pop("rehearse", None), traffic.pop("rehearse", None)
    # one chip's share of the step: the counts are per device
    traffic["batch"] //= found["chips"]
    mesh = parallel.data_parallel_mesh(devices=jax.devices("cpu")[:1])
    counts = load_plugin("builders", c["builder"]).build(
        c, traffic, mesh, 0, abstract=True)["counts"]
    assert counts["flash_kernels"] == ["hvd_flash_fwd", "hvd_flash_bwd"]
    heads = c["num_attention_heads"]
    one = flops.attention_matmul_flops(
        traffic["batch"], heads, traffic["seq_len"],
        c["hidden_size"] // heads)
    assert counts["flash_executed_flops"] == \
        7 * passes * c["num_hidden_layers"] * one


def test_resnet50_by_hand():
    convs = dict((n, (h, k, ci, co))
                 for n, h, k, ci, co in flops.resnet50_convs())
    assert convs["conv_init"] == (112, 7, 3, 64)        # 118,013,952 MACs
    assert 112 * 112 * 49 * 3 * 64 == 118_013_952
    assert convs["s0.b0.conv1"] == (56, 1, 64, 64)
    assert convs["s0.b0.proj"] == (56, 1, 64, 256)
    assert convs["s1.b0.conv1"] == (56, 1, 256, 128)    # before the stride
    assert convs["s1.b0.conv2"] == (28, 3, 128, 128)    # v1.5: 3x3 strides
    assert convs["s3.b2.conv3"] == (7, 1, 512, 2048)
    assert convs["classifier"] == (1, 1, 2048, 1000)
    assert len(convs) == 1 + 16 * 3 + 4 + 1             # 53 convs + classifier
    # torchvision's figures for resnet50
    assert flops.resnet50_params() == 25_557_032
    assert flops.resnet50_forward_macs() == 4_089_184_256
    # 2 * (3 * 4,089,184,256 - 118,013,952): no input gradient for the image
    assert flops.resnet50_model_flops_per_image() == 24_299_077_632


def test_resnet_configuration_file_matches_the_counted_network():
    c = config("resnet50_v15")
    assert c["stage_sizes"] == [3, 4, 6, 3] and c["num_filters"] == 64
    assert (c["image_size"], c["num_classes"]) == (224, 1000)
