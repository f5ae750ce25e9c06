"""The Nemotron cell's entries in `BENCHMARK.json` and its files: the
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
CELL, CONFIG = "nemo3s120b_1chip", "nemo3s120b_a12b_w4096"
METRICS = ("ssm_ms", "ssd_ms", "ssd_roofline", "moe_ms.nemo3",
           "moe_gmm_ms.nemo3", "moe_shuffle_ms.nemo3",
           "moe_gmm_roofline.nemo3", "flash_ms.nemo3",
           "flash_roofline.nemo3")
LAYERS = ["models"] * 3 + ["routed feed-forward"] * 4 + ["Pallas kernels"] * 2
REDUCED = {"num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size", "num_nextn_predict_layers",
           "mamba_num_heads", "n_groups", "num_attention_heads",
           "num_key_value_heads"}
# https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/
# blob/main/config.json as the model-configs catalog holds it
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern":
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_the_entries_are_there_and_in_order(manifest):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == REDUCED
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
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
        assert (m["unit"], m["better"]) == (
            ("%", "higher") if "roofline" in m["name"] else ("ms", "lower"))
    assert [m["layer"] for m in tail] == LAYERS


def test_the_configuration_is_the_published_one_but_for_its_cut(config):
    changed = {k for k, v in PUBLISHED.items() if config.get(k, "absent") != v}
    assert changed == REDUCED == set(config["reduced"])
    assert (config["num_hidden_layers"], config["hybrid_override_pattern"],
            config["n_routed_experts"], config["vocab_size"],
            config["num_nextn_predict_layers"]) == (
        11, "MEMEMEMEM*E", 8, 16384, 0)
    # one whole period, as it stands in the published string
    assert PUBLISHED["hybrid_override_pattern"][27:38] == "MEMEMEMEM*E"
    assert [PUBLISHED["hybrid_override_pattern"].count(c) for c in "ME*"] \
        == [40, 40, 8]
    # the 2-way head share keeps every head's width and the head group
    assert (config["mamba_num_heads"], config["n_groups"],
            config["num_attention_heads"], config["num_key_value_heads"]) \
        == (64, 4, 16, 1)
    assert config["mamba_num_heads"] // config["n_groups"] == 128 // 8
    assert config["num_attention_heads"] // config["num_key_value_heads"] \
        == 32 // 2
    for key in ("n_routed_experts", "mamba_num_heads", "n_groups",
                "num_attention_heads", "num_key_value_heads", "vocab_size",
                "num_hidden_layers", "hybrid_override_pattern",
                "num_nextn_predict_layers"):
        assert config["published_" + key] == PUBLISHED[key], key
    assert config["held_experts"] == [0, 8]
    for key in ("source", "deployment", "assumed", "departures", "job"):
        assert config[key], key
    for needle in ("hvd_ssd", "hvd_ssm", "hvd_moe_gmm", "hvd_flash_fwd"):
        assert needle in config["program_must_contain"]
    assert any("tpu_custom_call" in n
               for n in config["program_must_contain"])
    assert "hvd_flash_bwd" not in config["program_must_contain"]
    assert config["builder"] == "nemo3"
    # the rehearse sizes name every size the builder reads
    assert config["rehearse"]["hybrid_override_pattern"] == "M*E"


def test_the_builders_count_is_the_models(config):
    """`flops_nemo3.params` against the parameter tree `models.Transformer`
    makes for the configuration (shapes only), and against the issue's sum
    of the 2-way head share: 919 M."""
    import jax

    from benchmark.run import load_json, load_plugin
    from horovod_tpu import parallel

    traffic = load_json(os.path.join(BENCH, "traffic",
                                     "tokens_b1x4096.json"))
    mesh = parallel.data_parallel_mesh(devices=jax.devices("cpu")[:1])
    built = load_plugin("builders", "nemo3").build(config, traffic, mesh, 0,
                                                   abstract=True)
    held = sum(x.size for x in jax.tree_util.tree_leaves(built["state"][0]))
    assert held == built["counts"]["params"] == 919_015_872
    # a head group of 16 at 4096 positions: q + dO + lse + delta of the
    # group do not fit the one backward kernel's budget beside its blocks,
    # so the plan names dQ and dK/dV apart
    assert built["counts"]["flash_kernels"] == [
        "hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"]


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
    # M, * and E at the rehearsal's depth
    assert len(read["state_err_by_layer_agreeing_tokens"]) == 3
    assert len(read["held_share_first_step"]) == 1
    assert read["ssd_state_max_first_step"] > 0
    assert any("ssm" in k for k in read["grad_err"])
    checks = {i["check"]: i["ok"] for i in infos if "check" in i}
    for what in ("no assignment dropped", "loss falls",
                 "every loss is finite", "carried state"):
        assert any(what in k and ok for k, ok in checks.items()), what
    # the reference of another model is seen at any size
    assert read["against_no_shared_expert"] > 0.3
