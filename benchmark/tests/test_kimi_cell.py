"""The Kimi-Linear cell's entries in `BENCHMARK.json` and its files: the
configuration against the catalog's published keys, the readers by name, the
builder's parameter count against the model's, its flash plan and its KDA
counts, and `--rehearse` of the whole control flow on the CPU, which prints
no metric. Everything is asserted of THE CELL, wherever later PRs leave it in
the lists."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL, CONFIG, TRAFFIC = ("kimilin48b_1chip", "kimilinear48b_a3b_w2304",
                         "tokens_b1x8192")
METRICS = {"kda_ms": "models", "kda_proj_ms": "models",
           "kda_gate_ms": "models", "kda_chunk_ms": "models",
           "kda_carry_ms": "models", "kda_roofline": "models",
           "kda_kernel_ms": "Pallas kernels",
           "kda_kernel_roofline": "Pallas kernels",
           "mla_ms.kimi": "models",
           "flash_ms.kimi": "Pallas kernels",
           "flash_roofline.kimi": "Pallas kernels",
           "moe_ms.kimi": "routed feed-forward",
           "moe_gmm_ms.kimi": "routed feed-forward",
           "moe_shuffle_ms.kimi": "routed feed-forward",
           "moe_gmm_roofline.kimi": "routed feed-forward"}
REDUCED = ["num_hidden_layers", "linear_attn_config", "num_experts",
           "vocab_size"]
SOURCE = ("https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
          "blob/main/config.json")
# the URL above as the model-configs catalog holds it
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}


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
    assert entry["reduced"] == REDUCED and entry["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    assert "1/32 of deployed" in cell["why"] and len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, layer in METRICS.items():
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["layer"] == layer
        assert m["moves"] == "throughput" and m["source"] == "device_trace"
        assert m["unit"] == ("%" if "roofline" in name else "ms")
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    # the cell reports the end-to-end metrics under the bounds they have
    assert all("workloads" not in m for m in manifest["end_to_end"])
    # eleven cells or more, and of them still one on four chips
    assert len(manifest["workloads"]) >= 11
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_the_configuration_is_the_published_one_but_for_its_cut(config):
    changed = {k for k, v in PUBLISHED.items() if config.get(k, "absent") != v}
    assert changed == set(REDUCED) == set(config["reduced"])
    assert set(PUBLISHED) <= set(config)
    # the nested group is cut in its two lists of layers and nowhere else
    group, whole = config["linear_attn_config"], \
        PUBLISHED["linear_attn_config"]
    assert {k for k in whole if group[k] != whole[k]} \
        == {"kda_layers", "full_attn_layers"}
    assert group["kda_layers"] == [1, 2, 3, 5, 6, 7]
    assert group["full_attn_layers"] == [4, 8]
    assert config["num_hidden_layers"] in (5, 8)   # rung 2 or rung 1
    assert config["num_experts"] == 8
    assert config["published_num_experts"] == 256
    assert config["held_experts"] == [0, 8]
    assert config["vocab_size"] == 20480 == PUBLISHED["vocab_size"] // 8
    assert config["vocab_size"] % 128 == 0
    for key in ("source", "deployment", "assumed", "departures", "job"):
        assert config[key], key
    assert "32 ways" in config["deployment"]
    assert "8192 rows" in config["deployment"]
    said = " ".join(config["assumed"])
    for what in ("8192", "memory rule", "block_remat", "AdamW", "1e-5",
                 "A_log", "dt_bias", "low ranks", "128^-1/2", "l2"):
        assert what in said, what
    departures = " ".join(config["departures"])
    for what in ("selection bias", "Weight decay", "closes the sequence"):
        assert what in departures, what
    job = config["job"]
    assert (job["learning_rate"], job["warmup_steps"], job["loss_chunk"],
            job["kda_chunk"]) == (1e-05, 2000, 512, 64)
    assert job["block_remat"] in (0, 2, 4, 6, 8)
    for needle in ("hvd_flash_fwd", "hvd_moe_gmm", "hvd_moe_rows",
                   "hvd_moe_shared", "hvd_attn_proj", "hvd_kda_chunk",
                   "hvd_kda_carry", "hvd_kda_scores", "hvd_kda_scores_bwd"):
        assert needle in config["program_must_contain"]
    # one backward kernel or two is the plan's choice, not a needle
    for backward in ("hvd_flash_dq", "hvd_flash_dkv", "hvd_flash_bwd"):
        assert backward not in config["program_must_contain"]
    assert config["builder"] == "kimi"
    assert 0 < config["seeded_state"]["mixer_out_gain"] < 1
    assert "top-8" in config["seeded_state"]["why"]
    with open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["batch"], traffic["seq_len"], traffic["item"]) \
        == (1, 8192, "token")
    assert traffic["seq_len"] <= PUBLISHED["model_max_length"]


def test_the_builders_count_is_the_models_and_its_plans_have_kernels(config):
    """`flops_kimi.params` against the parameter tree `models.Transformer`
    makes for the configuration (shapes only), the flash plan the counts are
    made from, and the chunked recurrence's counts."""
    import jax

    from benchmark import flops_kimi
    from benchmark.run import load_json, load_plugin, program_needles
    from horovod_tpu import parallel

    traffic = load_json(os.path.join(BENCH, "traffic", TRAFFIC + ".json"))
    mesh = parallel.data_parallel_mesh(devices=jax.devices("cpu")[:1])
    built = load_plugin("builders", "kimi").build(config, traffic, mesh, 0,
                                                  abstract=True)
    params = built["state"][0]
    held = sum(x.size for x in jax.tree_util.tree_leaves(params))
    counts = built["counts"]
    assert held == counts["params"]
    if config["num_hidden_layers"] == 8:
        assert held == 903_464_896
    # a KDA layer's mixer: the issue's 39.52 M
    mixer = sum(x.size for x in jax.tree_util.tree_leaves(
        params["block_0"]["attn"]))
    assert mixer == 39_514_272
    assert sorted(params["block_0"]["attn"]) == [
        "A_log", "conv_kernel", "dt_bias", "f_up", "g_up", "in_proj", "norm",
        "out_proj"]
    assert sorted(params["block_3"]["attn"]) == ["kv_a", "kv_b", "kv_norm",
                                                 "out", "q"]
    assert "mlp_gate" in params["block_0"] and "moe_mlp" in params["block_1"]
    assert built["items_per_step"] == 8192
    assert counts["flash_kernels"] == ["hvd_flash_bwd", "hvd_flash_fwd"]
    plan = counts["flash_plan"]
    assert (plan["hvd_flash_bwd"]["path"], plan["hvd_flash_bwd"]["held"]) \
        == ("resident", "q")
    needles = program_needles(config, 1, counts)
    assert "hvd_flash_bwd" in needles and "hvd_kda_scores" in needles
    assert built["state"][2]["x"].shape == (1, 8192)
    # the recurrence: six layers forward + twice backward, the recomputed
    # KDA blocks once more
    again = ["kda" if i + 1 in config["linear_attn_config"]["kda_layers"]
             else "full" for i in range(config["job"]["block_remat"])]
    one = flops_kimi.kda_chunk_forward_flops(1, 8192, 32, 128, 128, 64)
    assert counts["kda_executed_flops"] \
        == (3 * 6 + again.count("kda")) * one
    assert counts["kda_min_bytes"] > 0 < counts["flash_min_bytes"]


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
    read = next(i for i in infos if "mixer_branch_err_by_block" in i)
    # KKKF at the rehearsal's depth: one dense block and three routed ones
    assert len(read["state_err_by_block"]) == 4
    assert len(read["held_share_first_step"]) == 3
    assert all(0.0 < s < 1.0 for s in read["held_share_first_step"])
    assert read["dropped"] == 0
    assert 0.0 <= read["flipped_margin"] < read["e4m3"]["margin"]
    top = read["kda_state_max"]
    assert top["first_step"] == pytest.approx(top["reference"], rel=0.05)
    assert 0.0 < top["after_the_window"]
    assert max(read["kda_chunked_vs_sequential"].values()) < 3e-2
    grads = read["grad_err_by_leaf"]
    assert {k.split("/")[0] for k in grads} == {"kda", "latent", "dense",
                                                "routed"}
    assert all(grads[k] < read["e4m3"]["grad"][k] for k in grads)
    assert read["against_no_shared_expert"] > 0.25
    assert read["against_plain_delta_rule"] > 0.25
    checks = {i["check"]: i["ok"] for i in infos if "check" in i}
    for what in ("no assignment dropped", "every loss is finite",
                 "the chunked recurrence agrees",
                 "refused: no shared expert", "refused: alpha = 1",
                 "the same system on matrices rounded to e4m3 is refused"):
        assert any(what in k and ok for k, ok in checks.items()), what
