"""The Ouro cell's entries in `BENCHMARK.json` and its files: the
configuration against the catalog's published keys, the readers by name,
and `--rehearse` of the whole control flow on the CPU, which prints no
metric."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL, CONFIG = "ouro2b6_1chip", "ouro2b6_w2048"
METRICS = ("loop_ms", "exit_ms", "flash_ms.ouro", "flash_roofline.ouro")
# https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json as the
# model-configs catalog holds it: every number and flag that shapes the model
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "max_position_embeddings": 65536,
    "max_window_layers": 48, "model_type": "ouro",
    "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4,
    "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152, "layer_types": ["full_attention"] * 48}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_entries_are_there_and_in_order(manifest):
    # (at the end when their PR added them; later cells came after)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
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
    assert [m["layer"] for m in tail] == ["looped stack"] * 2 + \
        ["Pallas kernels"] * 2


def test_the_configuration_is_the_published_one_but_for_its_depth():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    changed = {k for k, v in PUBLISHED.items() if config.get(k, "absent") != v}
    assert changed == {"num_hidden_layers"} == set(config["reduced"])
    assert 4 <= config["num_hidden_layers"] <= 48
    assert config["total_ut_steps"] == 4
    assert config["hidden_size"] == \
        config["num_attention_heads"] * config["head_dim"]
    for key in ("source", "deployment", "assumed", "departures", "job"):
        assert config[key], key
    assert "hvd_loop" in config["program_must_contain"]
    assert config["builder"] == "ouro"


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
    exits = next(i for i in infos if "exit_first_step" in i)
    for when in ("exit_first_step", "exit_after_the_window"):
        assert len(exits[when]["p_mean"]) == 4
        assert sum(exits[when]["p_mean"]) == pytest.approx(1.0, abs=1e-5)
    assert len(exits["hidden_err_by_pass"]) == 4
    checks = {i["check"]: i["ok"] for i in infos if "check" in i}
    # the checks that hold at any size (the limits on the precision are set
    # at the published widths; at width 64 bf16 reads past some of them)
    for what in ("sums to 1", "runs the stack once is refused",
                 "loss falls", "every loss is finite"):
        assert any(what in k and ok for k, ok in checks.items()), what
