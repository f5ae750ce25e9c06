"""The SDAR cell's entries in `BENCHMARK.json` and its files: the
configuration against the catalog's published keys, the readers by name, the
builder's parameter count against the model's, and `--rehearse` of the whole
control flow on the CPU, which prints no metric."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL, CONFIG, TRAFFIC = ("sdar30b_1chip", "sdar30b_a3b_w2048",
                         "tokens_bd_b1x4096")
METRICS = ("flash_ms.sdar", "flash_roofline.sdar", "moe_ms.sdar",
           "moe_gmm_roofline.sdar", "bd_ms", "attn_ms.sdar",
           "flash_fwd_ms.sdar", "flash_dq_ms.sdar", "flash_dkv_ms.sdar",
           "moe_gmm_ms.sdar", "moe_shuffle_ms.sdar")
LAYERS = ["Pallas kernels", "Pallas kernels", "routed feed-forward",
          "routed feed-forward", "models", "models"] \
    + 3 * ["Pallas kernels"] + 2 * ["routed feed-forward"]
# https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json as
# the model-configs catalog holds it
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_the_manifest_takes_the_cell(manifest):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/JetLM/"
                               "SDAR-30B-A3B-Chat/blob/main/config.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert tuple(m["name"] for m in mine) == METRICS
    assert [m["layer"] for m in mine] == LAYERS
    for m in mine:
        assert m["moves"] == "throughput" and m["source"] == "device_trace"
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
    # the accepted cells' metrics do not list it, the unlisted ones read it
    assert sum(CELL in m.get("workloads", []) for m in
               manifest["per_layer"]) == len(METRICS)


def test_the_configuration_is_the_published_one_but_for_its_cut(config):
    changed = {k for k, v in PUBLISHED.items() if config.get(k, "absent") != v}
    assert changed == {"num_hidden_layers", "num_experts", "vocab_size"} \
        == set(config["reduced"])
    assert 4 <= config["num_hidden_layers"] <= 8
    assert config["num_experts"] == 16 and config["vocab_size"] == 19072
    # an eighth of the vocabulary, up to the next multiple of 128
    assert 0 <= config["vocab_size"] - PUBLISHED["vocab_size"] / 8 < 128
    assert config["vocab_size"] % 128 == 0
    assert config["published_num_experts"] == 128
    assert config["held_experts"] == [0, 16]
    for key in ("source", "deployment", "assumed", "departures", "job"):
        assert config[key], key
    # every size the config does not give is listed as assumed
    said = " ".join(config["assumed"])
    for what in ("Block length 4", "U(1e-3, 1)", "No shift", "mask id",
                 "0.001", "AdamW", "memory rule"):
        assert what in said, what
    for needle in ("hvd_flash_fwd", "hvd_moe_gmm", "hvd_moe_rows", "hvd_bd"):
        assert needle in config["program_must_contain"]
    # one backward kernel or two is the plan's choice, not a needle
    for backward in ("hvd_flash_dq", "hvd_flash_dkv", "hvd_flash_bwd"):
        assert backward not in config["program_must_contain"]
    assert config["builder"] == "sdar"
    # the timed step runs the model's own top-8: no routing switch, and
    # the seeded state that spreads its choice says why
    assert "routing" not in config["job"]
    assert not any("routing" in d for d in config["departures"])
    assert config["seeded_state"]["first_block_qk_norm_scale"] > 1
    assert "top-8" in config["seeded_state"]["why"]
    with open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["batch"], traffic["seq_len"], traffic["block_length"],
            traffic["item"]) == (1, 4096, 4, "token")


def test_the_builders_count_is_the_models(config):
    """`flops_sdar.params` against the parameter tree `models.Transformer`
    makes for the configuration (shapes only), and the plan the counts are
    made from."""
    import jax

    from benchmark.run import load_json, load_plugin
    from horovod_tpu import parallel

    traffic = load_json(os.path.join(BENCH, "traffic", TRAFFIC + ".json"))
    mesh = parallel.data_parallel_mesh(devices=jax.devices("cpu")[:1])
    built = load_plugin("builders", "sdar").build(config, traffic, mesh, 0,
                                                  abstract=True)
    held = sum(x.size for x in jax.tree_util.tree_leaves(built["state"][0]))
    layers = config["num_hidden_layers"]
    assert held == built["counts"]["params"] \
        == 78_120_960 + layers * 94_638_336
    assert built["items_per_step"] == 4096
    assert built["counts"]["flash_kernels"] == [
        "hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"]
    assert built["counts"]["flash_tiles"] == {
        k: [1280, 384, 2816] for k in built["counts"]["flash_kernels"]}
    # the batch carries one key a sequence beside its tokens
    batch = built["state"][2]
    assert batch["x"].shape == (1, 4096) and batch["key"].shape == (1, 2)


def test_rehearsal_runs_the_whole_control_flow_and_prints_no_metric(
        tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "1", "--trace", "2", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["metrics"] == {} and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    infos = [json.loads(ln[5:]) for ln in lines if ln.startswith("INFO ")]
    read = next(i for i in infos if "flipped_positions_share" in i)
    # two blocks at the rehearsal's depth, both halves of 128 tokens
    assert len(read["state_err_by_block"]) == 2
    assert all(0.0 < s < 1.0 for s in read["held_share_first_step"])
    assert 0.0 <= read["flipped_share_of_layers_x_positions"] \
        <= read["flipped_positions_share"] <= 1.0
    assert 0.0 <= read["flipped_margin"] < read["e4m3"]["margin"]
    assert read["grad_worst_leaf_err"] < min(
        read["grad_norm_and_leaf_against_causal_mask"][1],
        read["grad_norm_and_leaf_against_no_clean_half"][1])
    stats = read["block_diffusion_stats"]
    assert stats["masked"][0] + stats["kept"][0] == 128
    checks = {i["check"]: i["ok"] for i in infos if "check" in i}
    # the checks that hold at any size (the limits on the precision are set
    # at the published widths)
    for what in ("no assignment dropped", "the noise is the reference's",
                 "loss falls", "every loss is finite",
                 "references of another model are refused"):
        assert any(what in k and ok for k, ok in checks.items()), what
    assert read["against_causal_mask"] > 0.3
    assert read["against_no_clean_half"] > 0.3
    assert read["against_unit_weights"] > 0.1
