"""The Xing cell's entries in `BENCHMARK.json` and its files: the
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
CELL, CONFIG = "xing29b_1chip", "xing29b_a4b_w3584"
METRICS = ("hc_ms", "mla_ms", "flash_ms.xing", "flash_roofline.xing",
           "moe_ms.xing", "moe_gmm_roofline.xing", "mtp_ms")
LAYERS = ["hyper-connections", "models", "Pallas kernels", "Pallas kernels",
          "routed feed-forward", "routed feed-forward", "models"]
# https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json
# as the model-configs catalog holds it
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_the_entries_are_there_and_in_order(manifest):
    # (at the end when their PR added them; later cells came after)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, CONFIG, "tokens_b1x4096", 1)
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index(METRICS[0])
    tail = manifest["per_layer"][first:first + len(METRICS)]
    assert tuple(m["name"] for m in tail) == METRICS
    for m in tail:
        assert m["workloads"] == [CELL] and m["moves"] == "throughput"
        assert m["source"] == "device_trace"
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
    assert [m["layer"] for m in tail] == LAYERS


def test_the_configuration_is_the_published_one_but_for_its_cut(config):
    changed = {k for k, v in PUBLISHED.items() if config.get(k, "absent") != v}
    assert changed == {"num_hidden_layers", "n_routed_experts",
                       "vocab_size"} == set(config["reduced"])
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 16384)
    assert config["published_n_routed_experts"] == 64
    assert config["held_experts"] == [0, 8]
    assert config["dense_layers_held"] == 1
    for key in ("source", "deployment", "assumed", "departures", "job",
                "seeded_state"):
        assert config[key], key
    for needle in ("hvd_flash_fwd", "hvd_moe_gmm", "hvd_hc", "hvd_mtp"):
        assert needle in config["program_must_contain"]
    # one backward kernel or two is the plan's choice, not a needle
    assert "hvd_flash_bwd" not in config["program_must_contain"]
    assert config["builder"] == "xing"


def test_the_builders_count_is_the_models(config):
    """`flops_xing.params` against the parameter tree `models.Transformer`
    makes for the configuration (shapes only): 913.3 M, the issue's sum."""
    import jax

    from benchmark.run import load_json, load_plugin
    from horovod_tpu import parallel

    traffic = load_json(os.path.join(BENCH, "traffic",
                                     "tokens_b1x4096.json"))
    mesh = parallel.data_parallel_mesh(devices=jax.devices("cpu")[:1])
    built = load_plugin("builders", "xing").build(config, traffic, mesh, 0,
                                                  abstract=True)
    held = sum(x.size for x in jax.tree_util.tree_leaves(built["state"][0]))
    assert held == built["counts"]["params"] == 913_473_668
    assert built["counts"]["flash_kernels"] == ["hvd_flash_fwd",
                                                "hvd_flash_bwd"]


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
    read = next(i for i in infos if "flipped_tokens_share" in i)
    # 1 dense + 2 routed blocks and the module's at the rehearsal's depth
    assert len(read["state_err_by_block_agreeing_tokens"]) == 4
    assert len(read["held_share_first_step"]) == 3
    checks = {i["check"]: i["ok"] for i in infos if "check" in i}
    # the checks that hold at any size (the limits on the precision are set
    # at the published widths; at width 64 bf16 reads past some of them)
    for what in ("doubly stochastic", "no assignment dropped", "loss falls",
                 "every loss is finite"):
        assert any(what in k and ok for k, ok in checks.items()), what
    # the references of another model are seen at any size
    assert read["against_no_shared_expert"] > 0.3
    assert read["against_one_sinkhorn_iteration"]["hc_off_reference"] > 0.05
    assert read["against_no_module_loss"] > 0.1
