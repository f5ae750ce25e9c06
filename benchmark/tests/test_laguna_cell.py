"""The Laguna cell's entries in `BENCHMARK.json` and its files: the
configuration against the catalog's published keys, the readers by name, the
builder's parameter count against the model's and its counts by kind of
layer, and `--rehearse` of the whole control flow on the CPU, which prints no
metric."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL, CONFIG, TRAFFIC = ("laguna33b_1chip", "laguna_xs2_33b_a3b_w2048",
                         "tokens_b1x8192")
METRICS = {
    "attn_full_ms.laguna": "models", "attn_window_ms.laguna": "models",
    "flash_full_ms.laguna": "Pallas kernels",
    "flash_window_ms.laguna": "Pallas kernels",
    "flash_full_roofline.laguna": "Pallas kernels",
    "flash_window_roofline.laguna": "Pallas kernels",
    "attn_gate_ms": "models", "attn_proj_ms.laguna": "models",
    "attn_rope_ms.laguna": "models",
    "moe_ms.laguna": "routed feed-forward",
    "moe_gmm_ms.laguna": "routed feed-forward",
    "moe_shuffle_ms.laguna": "routed feed-forward",
    "moe_gmm_roofline.laguna": "routed feed-forward"}
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "num_attention_heads_per_layer", "num_experts", "vocab_size"]
SOURCE = "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
PERIOD = ["full_attention"] + 3 * ["sliding_attention"]
# the URL above as the model-configs catalog holds it
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": 10 * PERIOD,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + 39 * ["sparse"],
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": 10 * [48, 64, 64, 64]}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_the_manifest_holds_the_cell(manifest):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED and entry["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    assert "256 rows an expert, 1/8 of 2048" in cell["why"]
    assert all(len(e["why"]) <= 200 for e in (entry, cell))
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]}
    assert {name: m["layer"] for name, m in mine.items()} == METRICS
    for name, m in mine.items():
        assert m["moves"] == "throughput" and m["source"] == "device_trace"
        assert m["unit"] == ("%" if "roofline" in name else "ms")
        assert m["better"] == ("higher" if "roofline" in name else "lower")
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    # no accepted cell's metric lists it; the unlisted ones read it too
    assert sum(CELL in m.get("workloads", []) for m in
               manifest["per_layer"]) == len(METRICS)
    # one cell of four chips in twelve
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_the_configuration_is_the_published_one_but_for_its_cut(config):
    assert set(PUBLISHED) <= set(config)
    changed = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert changed == set(REDUCED) == set(config["reduced"])
    depth = config["num_hidden_layers"]
    assert depth in (5, 8)             # the dense layer + 4, or two periods
    assert config["layer_types"] == PUBLISHED["layer_types"][:depth]
    assert config["mlp_layer_types"] == ["dense"] + (depth - 1) * ["sparse"]
    assert config["num_attention_heads_per_layer"] \
        == PUBLISHED["num_attention_heads_per_layer"][:depth]
    assert config["num_experts"] in (16, 32)
    assert config["held_experts"] == [0, config["num_experts"]]
    assert config["published_num_experts"] == 256
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["vocab_size"] % 128 == 0
    for key in ("source", "deployment", "assumed", "departures", "job",
                "seeded_state"):
        assert config[key], key
    assert "8-way group" in config["deployment"]
    assert "an eighth of its deployment load" in config["deployment"]
    # what the config does not say is listed as assumed, with its reason
    said = " ".join(config["assumed"])
    for what in ("per-head", "norm_topk_prob", "rotary_dim", "QK-norm",
                 "balancing term", "AdamW", "memory rule", "block_remat",
                 "no number of the cell hangs on"):
        assert what in said, what
    for needle in ("hvd_flash_fwd", "hvd_attn_window", "hvd_attn_full",
                   "hvd_attn_gate", "hvd_moe_shared", "hvd_moe_gmm",
                   "hvd_moe_gmm_dlhs", "hvd_moe_gmm_drhs", "hvd_moe_rows",
                   "hvd_moe_sum"):
        assert needle in config["program_must_contain"]
    # one backward kernel or two is the plan's choice, not a needle
    for backward in ("hvd_flash_dq", "hvd_flash_dkv", "hvd_flash_bwd"):
        assert backward not in config["program_must_contain"]
    assert config["builder"] == "laguna"
    with open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["batch"], traffic["seq_len"], traffic["item"]) \
        == (1, 8192, "token")
    # twice the context before YaRN's extension: the blend is live
    assert traffic["seq_len"] == 2 * PUBLISHED["rope_parameters"][
        "full_attention"]["original_max_position_embeddings"]
    # the rehearsal keeps two kinds, two head counts and the dense layer
    small = config["rehearse"]
    assert len(set(small["layer_types"])) == 2
    assert len(set(small["num_attention_heads_per_layer"])) == 2
    assert small["mlp_layer_types"][0] == "dense"


def test_the_builders_counts_are_the_models_by_kind(config):
    """`flops_laguna.params` against the parameter tree `models.Transformer`
    makes for the configuration (shapes only), and the plans the counts are
    made from, a kind of layer at a time at the kind's own heads."""
    import jax

    from benchmark.run import load_json, load_plugin
    from horovod_tpu import parallel

    traffic = load_json(os.path.join(BENCH, "traffic", TRAFFIC + ".json"))
    mesh = parallel.data_parallel_mesh(devices=jax.devices("cpu")[:1])
    built = load_plugin("builders", "laguna").build(config, traffic, mesh, 0,
                                                    abstract=True)
    params = built["state"][0]
    held = sum(x.size for x in jax.tree_util.tree_leaves(params))
    counts = built["counts"]
    assert held == counts["params"]
    if (config["num_hidden_layers"], config["num_experts"]) == (8, 32):
        assert held == 1_118_277_376  # ISSUE 62: 1118.2 M x 12 B = 12.50 GiB
    kinds = ["window" if t == "sliding_attention" else "full"
             for t in config["layer_types"]]
    for i, kind in enumerate(kinds):
        a = params["block_%d" % i]["attn"]
        heads = {"full": 48, "window": 64}[kind]
        assert a["query"]["kernel"].shape == (2048, heads, 128)
        assert a["key"]["kernel"].shape == (2048, 8, 128)
        assert a["gate"]["kernel"].shape == (2048, heads)
        assert a["out"]["kernel"].shape == (heads, 128, 2048)
    assert params["block_0"]["mlp_gate"]["kernel"].shape == (2048, 8192)
    moe = params["block_1"]["moe_mlp"]
    assert moe["router"].shape == (2048, 256)
    assert moe["w_gate"].shape == (config["num_experts"], 2048, 512)
    assert moe["shared_gate"]["kernel"].shape == (2048, 512)
    assert built["items_per_step"] == 8192
    assert counts["flash_kernels"] == ["hvd_flash_bwd", "hvd_flash_fwd"]
    by_kind = counts["flash_by_kind"]
    assert (by_kind["full"]["layers"], by_kind["window"]["layers"]) \
        == (kinds.count("full"), kinds.count("window"))
    plan = counts["flash_plan"]
    assert plan["full"]["hvd_flash_fwd"]["blocks"][0] % 6 == 0   # group 6
    assert plan["window"]["hvd_flash_fwd"]["blocks"][0] % 8 == 0  # group 8
    for kind in ("full", "window"):
        for name in counts["flash_kernels"]:
            p = plan[kind][name]
            assert (p["path"], p["held"]) == ("resident", "q")
            assert p["tiles_visited_masked_skipped"] \
                == counts["flash_tiles"][kind][name]
    # under a band of one k block every tile visited is cut
    for visited, masked, skipped in counts["flash_tiles"]["window"].values():
        assert visited == masked and skipped > 5 * visited
    # a window layer executes far less than a full one, a call
    per = {k: by_kind[k]["executed_flops"] / (
        by_kind[k]["layers"] + by_kind[k]["forward_again"] * 2 / 7)
        for k in by_kind}
    assert 0 < per["window"] < 0.5 * per["full"]
    assert 0 < counts["moe_gmm_executed_flops"]
    assert built["state"][2]["x"].shape == (1, 8192)


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
    # the dense layer and a period's three window layers, 256 tokens
    assert len(read["state_err_by_block"]) == 4
    assert set(read["attn_branch_err_by_kind"]) == {"full", "window"}
    assert len(read["held_share_first_step"]) == 3
    assert all(0.0 < s < 1.0 for s in read["held_share_first_step"])
    assert 0.0 <= read["flipped_margin"] < read["e4m3"]["margin"]
    assert len(read["grad_err_by_leaf"]) == 8
    assert len(read["against_other_models"]) == 8
    assert all(e > 0.2 for e in read["against_other_models"].values())
    plans = next(i for i in infos if "flash_plan_by_kind" in i)
    assert set(plans["flash_plan_by_kind"]) == {"full", "window"}
    checks = {i["check"]: i["ok"] for i in infos if "check" in i}
    # the checks that hold at any size (the limits on the precision are set
    # at the published widths; a second of steps at the start of the
    # warm-up does not move a loss in bf16)
    for what in ("no assignment dropped", "every loss is finite",
                 "references of another model are refused",
                 "flash_plan counts the tiles",
                 "rounded to e4m3 is refused"):
        assert any(what in k and ok for k, ok in checks.items()), what
