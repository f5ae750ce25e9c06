"""The Mellum2 cell's entries in `BENCHMARK.json` and its files: the
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
CELL, CONFIG, TRAFFIC = ("mellum12b_1chip", "mellum12b_a2b5_w2304",
                         "tokens_b1x8192")
METRICS = ("attn_window_ms", "attn_full_ms", "flash_window_ms",
           "flash_full_ms", "flash_window_roofline", "flash_full_roofline",
           "moe_ms.mellum", "moe_gmm_ms.mellum", "moe_shuffle_ms.mellum",
           "moe_gmm_roofline.mellum")
LAYERS = 2 * ["models"] + 4 * ["Pallas kernels"] \
    + 4 * ["routed feed-forward"]
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "num_experts", "vocab_size"]
SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
          "blob/main/config.json")
PERIOD = 3 * ["sliding_attention"] + ["full_attention"]
# the URL above as the model-configs catalog holds it
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": 7 * PERIOD, "mlp_layer_types": 28 * ["sparse"],
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}


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
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    assert "1/4 of deployed" in cell["why"]
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert tuple(m["name"] for m in mine) == METRICS
    assert [m["layer"] for m in mine] == LAYERS
    for m in mine:
        assert m["moves"] == "throughput" and m["source"] == "device_trace"
        assert m["unit"] == ("%" if "roofline" in m["name"] else "ms")
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
    # the accepted cells' metrics do not list it, the unlisted ones read it
    assert sum(CELL in m.get("workloads", []) for m in
               manifest["per_layer"]) == len(METRICS)
    # and the new entries stand at the end of their lists
    assert manifest["configs"][-1] is entry
    assert manifest["workloads"][-1] is cell
    assert manifest["per_layer"][-len(METRICS):] == mine


def test_the_configuration_is_the_published_one_but_for_its_cut(config):
    changed = {k for k, v in PUBLISHED.items() if config.get(k, "absent") != v}
    assert changed == set(REDUCED) == set(config["reduced"])
    depth = config["num_hidden_layers"]
    assert depth in (4, 8)                     # whole periods
    assert config["layer_types"] == (depth // 4) * PERIOD
    assert config["mlp_layer_types"] == depth * ["sparse"]
    assert config["num_experts"] == 16 and config["vocab_size"] == 24576
    assert config["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert config["vocab_size"] % 128 == 0
    assert config["published_num_experts"] == 64
    assert config["held_experts"] == [0, 16]
    for key in ("source", "deployment", "assumed", "departures", "job"):
        assert config[key], key
    assert "4-way group" in config["deployment"]
    assert "a fourth of its deployment load" in config["deployment"]
    # every size the config does not give is listed as assumed
    said = " ".join(config["assumed"])
    for what in ("RMSNorm over each HEAD", "0.001", "AdamW", "memory rule",
                 "block_remat"):
        assert what in said, what
    assert any("prediction module" in d for d in config["departures"])
    for needle in ("hvd_flash_fwd", "hvd_moe_gmm", "hvd_moe_rows",
                   "hvd_moe_act", "hvd_attn_window", "hvd_attn_full"):
        assert needle in config["program_must_contain"]
    # one backward kernel or two is the plan's choice, not a needle
    for backward in ("hvd_flash_dq", "hvd_flash_dkv", "hvd_flash_bwd"):
        assert backward not in config["program_must_contain"]
    assert config["builder"] == "mellum"
    assert config["seeded_state"]["first_block_qk_norm_scale"] > 1
    assert "top-8" in config["seeded_state"]["why"]
    with open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["batch"], traffic["seq_len"], traffic["item"]) \
        == (1, 8192, "token")
    assert traffic["seq_len"] == PUBLISHED["rope_parameters"][
        "full_attention"]["original_max_position_embeddings"]


def test_the_builders_count_is_the_models(config):
    """`flops_mellum.params` against the parameter tree `models.Transformer`
    makes for the configuration (shapes only), and the plans the counts are
    made from, a kind of layer at a time."""
    import jax

    from benchmark.run import load_json, load_plugin
    from horovod_tpu import parallel

    traffic = load_json(os.path.join(BENCH, "traffic", TRAFFIC + ".json"))
    mesh = parallel.data_parallel_mesh(devices=jax.devices("cpu")[:1])
    built = load_plugin("builders", "mellum").build(config, traffic, mesh, 0,
                                                    abstract=True)
    held = sum(x.size for x in jax.tree_util.tree_leaves(built["state"][0]))
    layers = config["num_hidden_layers"]
    counts = built["counts"]
    # embedding + head + final norm; a layer: attention 21.234 M, the
    # router, 16 experts of 6.193 M, two norms, two per-head scales
    assert held == counts["params"] == 113_248_512 + layers * 120_476_416
    assert built["items_per_step"] == 8192
    assert counts["flash_kernels"] == ["hvd_flash_bwd", "hvd_flash_fwd"]
    by_kind = counts["flash_by_kind"]
    assert (by_kind["window"]["layers"], by_kind["full"]["layers"]) \
        == (3 * layers // 4, layers // 4)
    tiles = counts["flash_tiles"]
    for name in counts["flash_kernels"]:
        visited, masked, skipped = tiles["window"][name]
        full = tiles["full"][name]
        assert visited + skipped == full[0] + full[2]
        assert 0 < masked <= visited < 0.4 * full[0]
    assert 0 < by_kind["window"]["executed_flops"] \
        < by_kind["full"]["executed_flops"] * 3 * 0.4
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
    # one period at the rehearsal's depth, 256 tokens
    assert len(read["state_err_by_block"]) == 4
    assert all(0.0 < s < 1.0 for s in read["held_share_first_step"])
    assert 0.0 <= read["flipped_margin"] < read["e4m3"]["margin"]
    assert not any(sum(n) for n in read["band_edges_rows_wrong"].values())
    checks = {i["check"]: i["ok"] for i in infos if "check" in i}
    # the checks that hold at any size (the limits on the precision are set
    # at the published widths; a second of steps at the start of the
    # warm-up does not move a loss in bf16)
    for what in ("no assignment dropped",
                 "every loss is finite", "window_matters", "yarn_matters",
                 "the band's edges", "flash_plan counts the tiles"):
        assert any(what in k and ok for k, ok in checks.items()), what
    assert all(e > 0.15 for e in
               read["against_other_stacks_attn_branch"].values())
