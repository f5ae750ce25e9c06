"""The LFM2 cell's entries in `BENCHMARK.json` and its files: the
configuration against the catalog's published keys, the readers by name, the
builder's parameter count against the model's and its counts, and
`--rehearse` of the whole control flow on the CPU, which prints no metric."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL, CONFIG, TRAFFIC = ("lfm2moe8b_1chip", "lfm2_8b_a1b_w2048",
                         "tokens_b2x8192")
METRICS = {
    "sconv_ms": "models", "sconv_proj_ms": "models",
    "sconv_gate_ms": "models", "sconv_gate_roofline": "models",
    "attn_full_ms.lfm2": "models", "flash_ms.lfm2": "Pallas kernels",
    "flash_roofline.lfm2": "Pallas kernels",
    "moe_ms.lfm2": "routed feed-forward",
    "moe_gmm_ms.lfm2": "routed feed-forward",
    "moe_shuffle_ms.lfm2": "routed feed-forward",
    "moe_gmm_roofline.lfm2": "routed feed-forward"}
REDUCED = ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
SOURCE = "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
# the URL above as the model-configs catalog holds it
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [{"c": "conv", "f": "full_attention"}[k]
                    for k in "ccfcccfcccfcccfcccfccfcc"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


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
    assert "2048 rows an expert" in cell["why"]
    assert "2x share" in cell["why"]
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
    # one cell of four chips in thirteen
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_the_configuration_is_the_published_one_but_for_its_cut(config):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
    assert row["config"] == PUBLISHED and row["source_url"] == SOURCE
    assert set(PUBLISHED) <= set(config)
    changed = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert changed == set(REDUCED) == set(config["reduced"])
    depth = config["num_hidden_layers"]
    assert depth in (6, 10)   # the two dense layers + 4, or two periods
    assert config["layer_types"] == PUBLISHED["layer_types"][:depth]
    assert config["num_experts"] == 8
    assert config["held_experts"] == [0, 8]
    assert config["published_num_experts"] == 32
    assert config["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    for key in ("source", "deployment", "assumed", "departures", "job"):
        assert config[key], key
    assert "4-way group" in config["deployment"]
    assert "2048 on each held expert" in config["deployment"]
    # what the config does not say is listed as assumed, with its reason
    said = " ".join(config["assumed"])
    for what in ("tied", "8.34 B", "B | G | z", "cross-correlation",
                 "per-head RMSNorm", "7168", "1e-6", "5e-7",
                 "balancing term", "AdamW", "memory rule", "block_remat"):
        assert what in said, what
    for needle in ("hvd_sconv", "hvd_sconv_gate", "hvd_attn_full",
                   "hvd_flash_fwd", "hvd_moe_gmm", "hvd_moe_gmm_dlhs",
                   "hvd_moe_gmm_drhs", "hvd_moe_rows", "hvd_moe_sum",
                   "hvd_moe_act"):
        assert needle in config["program_must_contain"]
    # one backward kernel or two is the plan's choice, not a needle
    for backward in ("hvd_flash_dq", "hvd_flash_dkv", "hvd_flash_bwd"):
        assert backward not in config["program_must_contain"]
    assert config["builder"] == "lfm2"
    with open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["batch"], traffic["seq_len"], traffic["item"]) \
        == (2, 8192, "token")
    # the rehearsal keeps both kinds, the two dense layers and a batch of 2
    small = config["rehearse"]
    assert set(small["layer_types"]) == {"conv", "full_attention"}
    assert small["layer_types"][:2] == ["conv", "conv"]
    assert traffic["rehearse"]["batch"] == 2


def test_the_builders_counts_are_the_models(config):
    """`flops_lfm2.params` against the parameter tree `models.Transformer`
    makes for the configuration (shapes only), and the plans the counts are
    made from."""
    import jax

    from benchmark import flops_lfm2
    from benchmark.run import load_json, load_plugin
    from horovod_tpu import parallel

    traffic = load_json(os.path.join(BENCH, "traffic", TRAFFIC + ".json"))
    mesh = parallel.data_parallel_mesh(devices=jax.devices("cpu")[:1])
    built = load_plugin("builders", "lfm2").build(config, traffic, mesh, 0,
                                                  abstract=True)
    params = built["state"][0]
    held = sum(x.size for x in jax.tree_util.tree_leaves(params))
    counts = built["counts"]
    assert held == counts["params"]
    if config["num_hidden_layers"] == 10:
        assert held == 982_084_096  # ISSUE 65: x 12 B = 10.98 GiB
    assert "lm_head" not in params
    assert params["embed"]["embedding"].shape == (16384, 2048)
    for i, kind in enumerate(config["layer_types"]):
        a = params["block_%d" % i]["attn"]
        if kind == "conv":
            assert a["in_proj"]["kernel"].shape == (2048, 6144)
            assert a["conv_kernel"].shape == (3, 2048)
            assert a["out_proj"]["kernel"].shape == (2048, 2048)
        else:
            assert a["query"]["kernel"].shape == (2048, 32, 64)
            assert a["key"]["kernel"].shape == (2048, 8, 64)
            assert a["q_norm"]["scale"].shape == (64,)
            assert a["out"]["kernel"].shape == (32, 64, 2048)
    for i in (0, 1):
        assert params["block_%d" % i]["mlp_gate"]["kernel"].shape \
            == (2048, 7168)
    moe = params["block_2"]["moe_mlp"]
    assert moe["router"].shape == (2048, 32)
    assert moe["w_gate"].shape == (8, 2048, 1792)
    assert "shared_gate" not in moe
    assert built["items_per_step"] == 2 * 8192
    assert built["state"][2]["x"].shape == (2, 8192)
    assert counts["flash_kernels"] == ["hvd_flash_bwd", "hvd_flash_fwd"]
    plan = counts["flash_plan"]
    for name in counts["flash_kernels"]:
        p = plan[name]
        assert (p["path"], p["held"], p["blocks"]) \
            == ("resident", "q", [2048, 512])
        assert p["tiles_visited_masked_skipped"] == [2176, 256, 1920] \
            == counts["flash_tiles"]["full"][name]
    assert plan["hvd_flash_bwd"]["resident_bytes"] == 24 << 20
    full = counts["flash_by_kind"]["full"]
    remat = config["job"]["block_remat"]
    kinds = config["layer_types"]
    assert full["layers"] == kinds.count("full_attention")
    assert full["forward_again"] == kinds[:remat].count("full_attention")
    assert counts["sconv_plan"]["path"] == "jnp"
    assert counts["sconv_gate_min_bytes"] == flops_lfm2.gate_step_min_bytes(
        2 * 8192, 2048, 3, kinds.count("conv"), kinds[:remat].count("conv"))
    # the grouped matmuls on the rows EXPECTED, until a run has counted
    assert counts["moe_gmm_rows_a_layer"] == 4 * 2 * 8192 * 8 / 32 == 16384
    assert 0 < counts["moe_gmm_executed_flops"]


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
    # two dense conv layers, an attention layer and three routed conv ones
    assert len(read["state_err_by_block"]) == 6
    assert set(read["mixer_branch_err_by_kind"]) == {"conv", "full"}
    assert len(read["held_share_first_step"]) == 4
    assert all(0.0 < s < 1.0 for s in read["held_share_first_step"])
    assert 0.0 <= read["flipped_margin"] < read["e4m3"]["margin"]
    assert len(read["grad_err_by_leaf"]) == 9
    assert len(read["against_other_models"]) == 5
    assert all(e > 0.15 for e in read["against_other_models"].values())
    # the grouped matmuls' counts follow the rows the run counted
    assert read["moe_gmm_rows_a_layer_counted_after_the_window"] \
        == pytest.approx(sum(read["held_share_after_the_window"]) / 4
                         * 4 * 2 * 256)
    plans = next(i for i in infos if "flash_plan" in i)
    assert set(plans["flash_plan"]) == {"hvd_flash_fwd", "hvd_flash_bwd"}
    assert plans["sconv_plan"]["path"] == "jnp"
    checks = {i["check"]: i["ok"] for i in infos if "check" in i}
    # the checks that hold at any size (the limits on the precision are set
    # at the published widths)
    for what in ("no assignment dropped", "every loss is finite",
                 "references of another model are refused",
                 "rounded to e4m3 is refused"):
        assert any(what in k and ok for k, ok in checks.items()), what
