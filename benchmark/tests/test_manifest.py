"""`BENCHMARK.json` against the contract's rules on names and units, and
every cell's files resolve."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_limits(manifest):
    assert set(manifest) - {"trace_in_run"} == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
    assert manifest.get("trace_in_run", True) is True
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    assert manifest["command"][1].startswith(manifest["paths"][0] + "/")
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def test_every_name_and_unit_is_well_formed(manifest):
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        names += [w["name"], w["config"], w["traffic"]]
        assert w["chips"] in (1, 4)
    for group, keys in (("end_to_end", {"name", "unit", "better", "bound",
                                        "source"}),
                        ("per_layer", {"name", "unit", "better", "source",
                                       "layer", "moves"})):
        for m in manifest[group]:
            assert set(m) - {"workloads"} == keys, m
            names.append(m["name"])
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
    for n in names:
        assert NAME.match(n), n
    for entry in manifest["configs"] + manifest["workloads"]:
        for key in ("why", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200, entry[key]
                assert "\n" not in entry[key] and "\t" not in entry[key]
    for group in ("configs", "workloads"):
        ns = [e["name"] for e in manifest[group]]
        assert len(ns) == len(set(ns))
    metric_names = [m["name"] for m in
                    manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_metrics_are_consistent(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        assert set(m.get("workloads", cells)) <= cells
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= \
            set(moved.get("workloads", cells))
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for cell in cells:
        reported = [m for m in manifest["per_layer"]
                    if cell in m.get("workloads", cells)]
        assert reported, cell


def test_every_cell_resolves_to_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    for w in manifest["workloads"]:
        entry = configs[w["config"]]
        used.add(w["config"])
        path = os.path.join(ROOT, entry["file"])
        assert entry["file"].startswith(tuple(p + "/" for p in
                                              manifest["paths"]))
        with open(path) as f:
            config = json.load(f)
        assert os.path.isfile(os.path.join(
            BENCH, "builders", config["builder"] + ".py"))
        for key in entry["reduced"]:
            assert key in config and key in config["reduced"]
        with open(os.path.join(BENCH, "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert traffic["batch"] >= w["chips"]
        assert traffic["batch"] % w["chips"] == 0
    assert used == set(configs)
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    # every per-layer metric has its reader, and every reader its metric
    readers = {fn[:-3] for fn in os.listdir(
        os.path.join(BENCH, "layer_metrics")) if fn.endswith(".py")}
    assert {m["name"] for m in manifest["per_layer"]} == readers
    for dirpath, _, filenames in os.walk(BENCH):
        if "__pycache__" in dirpath:
            continue
        for fn in filenames:
            rel = os.path.relpath(os.path.join(dirpath, fn), ROOT)
            assert PATH.match(rel), rel


def test_the_flash_readers_name_kernels_the_program_runs(manifest):
    """Since PR 33 every causal cell's backward is one kernel,
    `hvd_flash_bwd`: the readers by the two older names are gone from the
    cells that never run them (`sdar30b_1chip` runs the two kernels and
    keeps its own), and the window's own account is reported in every
    cell."""
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for gone in ("flash_dq_ms", "flash_dkv_ms", "flash_dq_ms.olmoe",
                 "flash_dkv_ms.olmoe"):
        assert gone not in per_layer
        assert not os.path.exists(os.path.join(
            BENCH, "layer_metrics", gone + ".py"))
    assert per_layer["flash_bwd_ms"]["workloads"] == \
        per_layer["flash_fwd_ms"]["workloads"] == \
        per_layer["flash_ms"]["workloads"]
    assert per_layer["flash_bwd_ms.olmoe"]["workloads"] == \
        per_layer["flash_fwd_ms.olmoe"]["workloads"]
    assert {"flash_dq_ms.sdar", "flash_dkv_ms.sdar"} <= set(per_layer)
    for name in ("step_ms_median", "window_lost_ms", "step_ms_max"):
        m = per_layer[name]
        assert "workloads" not in m and m["moves"] == "throughput"
        assert (m["source"], m["layer"]) == ("host_clock", "entry points")


def test_needles_name_a_mechanism_and_follow_the_plan(manifest):
    """A configuration's `program_must_contain` holds the forward's kernel
    (every plan's forward is its own kernel) and never a backward's: one
    kernel or two is `flash_plan`'s choice from the shapes. The backward
    is still held: `run.program_needles` adds the kernels the plan names
    (the builder's `counts["flash_kernels"]`), so the program's text has
    to hold whichever backward the plan chose."""
    import sys

    sys.path.insert(0, ROOT)
    from benchmark.run import program_needles

    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        needles = config.get("program_must_contain", [])
        for backward in ("hvd_flash_bwd", "hvd_flash_dq", "hvd_flash_dkv"):
            assert backward not in needles, (c["name"], backward)
        one = program_needles(config, 1, {"flash_kernels": [
            "hvd_flash_fwd", "hvd_flash_bwd"]})
        two = program_needles(config, 4, {"flash_kernels": [
            "hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"]})
        assert one[:len(needles)] == two[:len(needles)] == needles
        assert "hvd_flash_bwd" in one and "hvd_flash_dq" not in one
        assert {"hvd_flash_dq", "hvd_flash_dkv"} <= set(two)
        assert "hvd_flash_bwd" not in two and two[-1] == "all-reduce"
        assert one.count("hvd_flash_fwd") == two.count("hvd_flash_fwd") == 1
        # a builder that counts no flash kernel (ResNet) adds none
        assert program_needles(config, 1, {}) == needles == \
            program_needles(config, 1)
