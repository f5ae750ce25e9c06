#!/usr/bin/env python3
"""Whether a change left a cell's compiled step the program it was: compile
the named cells as `rehearse_compile.py` does (real sizes, a described
`v5e:2x2`, no chip) and print, per cell, the sha256 of the program's text
with every SOURCE LOCATION taken out:

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_text.py [cell ...]

Run it in a checkout of the parent and in the change and compare the lines.
`compiled.as_text()` itself differs as soon as a line of Python moves: it
opens with tables of file names, function names and stack frames, every
instruction carries `metadata={op_name source_file source_line ...}`, and a
Pallas kernel's body (serialized MLIR in its `backend_config`) holds the
call sites it was traced from. None of that reaches the chip. Taken out
here: the tables, each instruction's metadata, and each kernel's debug
locations (the body is parsed and printed without them). What is left is
every instruction, shape, layout, schedule and kernel body; equal hashes
mean the same program.
"""

import base64
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

_METADATA = re.compile(r", metadata=\{[^{}]*\}")
_KERNEL_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def without_locations(text):
    """`compiled.as_text()` with the source locations taken out (module
    text above); also the number of kernel bodies that were parsed."""
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    first_line, _, rest = text.partition("\n")
    if "StackFrames" in rest:
        rest = rest[rest.index("\n\n", rest.index("StackFrames")):]
    rest = _METADATA.sub("", rest)
    bodies = []

    def plain(match):
        module = ir.Module.parse(base64.b64decode(match.group(1)))
        bodies.append(1)
        return '"body":%s' % json.dumps(
            module.operation.get_asm(enable_debug_info=False))

    with jax_mlir.make_ir_context() as context:
        # The serialized form's own dialect (`stable_mosaic`) has no
        # Python registration; it parses and prints unregistered.
        context.allow_unregistered_dialects = True
        rest = _KERNEL_BODY.sub(plain, rest)
    return first_line + rest, len(bodies)


def main(names):
    import jax
    from jax.experimental import topologies

    from benchmark.run import find_cell, load_json, load_plugin, memory_gib
    from horovod_tpu import parallel

    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
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
        compiled = built["step"].lower(*built["state"]).compile()
        text, kernels = without_locations(compiled.as_text())
        print(json.dumps({
            "cell": name,
            "sha256_without_locations": hashlib.sha256(
                text.encode()).hexdigest()[:16],
            "kernel_bodies_parsed": kernels, "characters": len(text),
            "step_gib": memory_gib(compiled.memory_analysis())["step"]}),
            flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
