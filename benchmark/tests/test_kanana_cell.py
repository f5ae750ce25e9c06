"""The Kanana-2 cell's entries in `BENCHMARK.json` and its files: the
configuration against the catalog's published keys, the readers by name, the
builder's parameter count against the model's and its flash plan, and
`--rehearse` of the whole control flow on the CPU, which prints no metric.
Everything is asserted of THE CELL, wherever later PRs leave it in the lists
(PERF.md s7: two cells' tests counted the metrics that list them and went
stale with the next `benchmark` PR)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL, CONFIG, TRAFFIC = ("kanana30b_1chip", "kanana2_30b_a3b_w2048",
                         "tokens_b1x8192")
METRICS = {"flash_ms.kanana": "Pallas kernels",
           "flash_fwd_ms.kanana": "Pallas kernels",
           "flash_bwd_ms.kanana": "Pallas kernels",
           "flash_roofline.kanana": "Pallas kernels",
           "mla_ms.kanana": "models",
           "moe_ms.kanana": "routed feed-forward",
           "moe_gmm_ms.kanana": "routed feed-forward",
           "moe_shuffle_ms.kanana": "routed feed-forward",
           "moe_gmm_roofline.kanana": "routed feed-forward"}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
SOURCE = ("https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/"
          "blob/main/config.json")
# the URL above as the model-configs catalog holds it
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 128256}


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
    assert "1/8 of deployed" in cell["why"] and len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, layer in METRICS.items():
        m = by_name[name]
        assert CELL in m["workloads"] and m["layer"] == layer
        assert m["moves"] == "throughput" and m["source"] == "device_trace"
        assert m["unit"] == ("%" if "roofline" in name else "ms")
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    # the cell reports the end-to-end metrics under the bounds they have
    assert all("workloads" not in m for m in manifest["end_to_end"])
    # ten cells or more, and of them still one on four chips
    assert len(manifest["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_the_configuration_is_the_published_one_but_for_its_cut(config):
    changed = {k for k, v in PUBLISHED.items() if config.get(k, "absent") != v}
    assert changed == set(REDUCED) == set(config["reduced"])
    assert set(PUBLISHED) <= set(config)
    depth = config["num_hidden_layers"]
    assert depth in (5, 7, 9)                  # 1 dense + 4, 6 or 8 routed
    assert config["n_routed_experts"] == 16
    assert config["published_n_routed_experts"] == 128
    assert config["held_experts"] == [0, 16]
    assert config["vocab_size"] == 16128 and config["vocab_size"] % 128 == 0
    assert 0 <= config["vocab_size"] - PUBLISHED["vocab_size"] / 8 < 128
    for key in ("source", "deployment", "assumed", "departures", "job"):
        assert config[key], key
    assert "8-way group" in config["deployment"]
    assert "3072 rows" in config["deployment"]
    said = " ".join(config["assumed"])
    for what in ("8192", "memory rule", "block_remat", "AdamW", "1e-5"):
        assert what in said, what
    departures = " ".join(config["departures"])
    for what in ("rope_interleave", "selection bias", "Weight decay",
                 "closes the sequence"):
        assert what in departures, what
    job = config["job"]
    assert (job["learning_rate"], job["warmup_steps"], job["loss_chunk"]) \
        == (1e-05, 2000, 512)
    assert job["block_remat"] in (0, 3, 5, 7, 9)
    for needle in ("hvd_flash_fwd", "hvd_moe_gmm", "hvd_moe_rows",
                   "hvd_moe_shared", "hvd_attn_proj"):
        assert needle in config["program_must_contain"]
    # one backward kernel or two is the plan's choice, not a needle: the
    # builder's `counts["flash_kernels"]` names it (the next test)
    for backward in ("hvd_flash_dq", "hvd_flash_dkv", "hvd_flash_bwd"):
        assert backward not in config["program_must_contain"]
    assert config["builder"] == "kanana"
    assert 0 < config["seeded_state"]["attention_out_gain"] < 1
    assert "top-6" in config["seeded_state"]["why"]
    with open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["batch"], traffic["seq_len"], traffic["item"]) \
        == (1, 8192, "token")
    assert traffic["seq_len"] <= PUBLISHED["max_position_embeddings"]


def test_the_builders_count_is_the_models_and_its_plan_has_kernels(config):
    """`flops_kanana.params` against the parameter tree `models.Transformer`
    makes for the configuration (shapes only), and the plan the counts are
    made from: both kernels resident, the backward ONE kernel held by the q
    block."""
    import jax

    from benchmark.run import load_json, load_plugin, program_needles
    from horovod_tpu import parallel

    traffic = load_json(os.path.join(BENCH, "traffic", TRAFFIC + ".json"))
    mesh = parallel.data_parallel_mesh(devices=jax.devices("cpu")[:1])
    built = load_plugin("builders", "kanana").build(config, traffic, mesh, 0,
                                                    abstract=True)
    held = sum(x.size for x in jax.tree_util.tree_leaves(built["state"][0]))
    routed = config["num_hidden_layers"] - 1
    counts = built["counts"]
    # embedding + head + final norm; the dense layer; a routed layer:
    # attention 26.346 M, the shared pair 9.437 M, the router and its bias,
    # 16 experts of 4.719 M, three norms
    assert held == counts["params"] \
        == 66_062_336 + 64_098_816 + routed * 111_547_008
    assert built["items_per_step"] == 8192
    assert counts["flash_kernels"] == ["hvd_flash_bwd", "hvd_flash_fwd"]
    plan = counts["flash_plan"]
    assert (plan["hvd_flash_fwd"]["path"], plan["hvd_flash_fwd"]["held"]) \
        == ("resident", "q")
    assert (plan["hvd_flash_bwd"]["path"], plan["hvd_flash_bwd"]["held"],
            plan["hvd_flash_bwd"]["resident_bytes"]) \
        == ("resident", "q", 24 * 2 ** 20)
    needles = program_needles(config, 1, counts)
    assert "hvd_flash_bwd" in needles and "hvd_flash_fwd" in needles
    assert built["state"][2]["x"].shape == (1, 8192)
    assert counts["flash_executed_flops"] > 0 < counts["flash_min_bytes"]


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
    read = next(i for i in infos if "attn_branch_err_by_block" in i)
    # the dense block and two routed ones at the rehearsal's depth
    assert len(read["state_err_by_block"]) == 3
    assert len(read["held_share_first_step"]) == 2
    assert all(0.0 < s < 1.0 for s in read["held_share_first_step"])
    assert 0.0 <= read["flipped_margin"] < read["e4m3"]["margin"]
    grads = read["grad_err_by_slice_of_block_1"]
    assert sorted(grads) == ["kv_a/latent", "kv_a/rope", "q/nope", "q/rope"]
    assert all(grads[k] < read["e4m3"]["grad"][k] for k in grads)
    assert read["against_no_shared_pair"] > 0.25
    checks = {i["check"]: i["ok"] for i in infos if "check" in i}
    for what in ("no assignment dropped", "every loss is finite",
                 "a reference of another model is refused",
                 "the same system on matrices rounded to e4m3 is refused"):
        assert any(what in k and ok for k, ok in checks.items()), what
