#!/usr/bin/env python3
"""Compile every cell's step program at its real size for a described
`v5e:2x2`, with no chip attached, and print what the compiler says:

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py [cell ...]

One line per cell: per-device bytes from `memory_analysis()` and how often
the program text holds each string it must (`run.program_needles`). A compile
that passes is not a chip run: it gives no time and no result, only "this
fits and these kernels and collectives are in the program". Run it before a
chip call after changing a configuration, a traffic file or a builder.
"""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def main(names):
    import jax
    from jax.experimental import topologies

    from benchmark.run import (find_cell, load_json, load_plugin, memory_gib,
                               program_needles)
    from horovod_tpu import parallel

    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # The kernel dispatchers ask for the default backend; nothing else here
    # does. The persistent cache cannot hold a program for a described chip.
    jax.default_backend = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)
    for name in names or [w["name"] for w in manifest["workloads"]]:
        cell, entry = find_cell(manifest, name)
        config = load_json(os.path.join(ROOT, entry["file"]))
        traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                         cell["traffic"] + ".json"))
        mesh = parallel.data_parallel_mesh(
            devices=topo.devices[:int(cell["chips"])])
        built = load_plugin("builders", config["builder"]).build(
            config, traffic, mesh, 0, abstract=True)
        t0 = time.perf_counter()
        compiled = built["step"].lower(*built["state"]).compile()
        secs = time.perf_counter() - t0
        text = compiled.as_text()
        needles = program_needles(config, int(cell["chips"]),
                                  built["counts"])
        print(json.dumps({
            "cell": name, "chips": cell["chips"], "compile_s": round(secs, 1),
            "per_device_gib": memory_gib(compiled.memory_analysis()),
            "program_text_counts": {n: text.count(n) for n in needles},
            "instructions": {op: text.count(" %s(" % op)
                             for op in ("all-reduce", "all-reduce-start",
                                        "while")}}), flush=True)

if __name__ == "__main__":
    main(sys.argv[1:])
