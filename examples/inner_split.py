"""How far the split of a benchmark cell's attention and Mamba-2 time by the
program's inner scopes can be trusted: join the per-instruction times of a
kept trace (`benchmark/inner_reduce.py`) to what the fusions of the cell's
compiled step hold (`hvd.profile.fused_scopes`), by instruction name, and
print per area and part how many ms a step lie in fusions that hold another
part's work, the optimizer's, or the block's own outside every part
(docs/TRACING.md, "The in-`jit` step").

Usage, on the machine with the chip and after the run that leaves the trace
(`--trace 1` keeps it under `.bench_trace/<cell>`; `--trace 2` removes it):

    python3 benchmark/run.py --workload <cell> --seed 1 --seconds 35 --trace 1
    PYTHONPATH=. python3 examples/inner_split.py <cell> > split.json

The step is compiled here a second time for its text (a load from the
compile cache where the run above left it there). The line printed also
holds every instruction of the two areas with its part and ms
(`instructions`); given as a second argument on a machine with no chip,

    JAX_PLATFORMS=cpu python3 examples/inner_split.py <cell> split.json

it stands in for the trace, and the text is that of a compile for a
described `v5e:2x2`, as `benchmark/rehearse_text.py` makes it: `in_text_ms`
says how much of the trace's time found its instruction in that text.
"""

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from horovod_tpu.run.util import use_compile_cache  # noqa: E402

use_compile_cache()

import jax  # noqa: E402

from benchmark import inner_reduce  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.run import find_cell, load_json, load_plugin  # noqa: E402
from horovod_tpu import parallel, profile  # noqa: E402

# The parts, and beside them what XLA fuses with them: the optimizer (a
# weight's update rides behind the last term of its gradient) and the work
# of a block or a mixer that lies under none of the parts (the norm before a
# mixer, a residual add, a slice): `fused_scopes` takes the innermost.
PARTS = profile.ATTN_PARTS + profile.SSM_SCOPES[1:]
OTHER = (profile.BLOCK, profile.SSM)
SCOPES = PARTS + OTHER + (profile.OPTIMIZER,)
SHOWN = 8  # mixed fusions printed an area, longest first
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.-]+) = ", re.M)


def compiled_text(name):
    """`compiled.as_text()` of the cell's step, for the chips at hand or,
    with none, for a described `v5e:2x2`."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, entry = find_cell(manifest, name)
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(ROOT, "benchmark", "traffic",
                                     cell["traffic"] + ".json"))
    if jax.default_backend() == "tpu":
        devices = jax.devices()
    else:
        from jax.experimental import topologies

        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
        jax.default_backend = lambda: "tpu"  # the kernels' dispatchers ask
        jax.config.update("jax_enable_compilation_cache", False)
    mesh = parallel.data_parallel_mesh(devices=devices[:int(cell["chips"])])
    built = load_plugin("builders", config["builder"]).build(
        config, traffic, mesh, 0, abstract=True)
    return built["step"].lower(*built["state"]).compile().as_text()


def joined(instructions, fused, in_text):
    """Per area: {"parts": {part: {"ms"; "in_text_ms": of instructions the
    text holds by name (all of it, where trace and text are one program's);
    "with_parts_ms": in fusions that hold more than one of `PARTS`;
    "with_optimizer_ms": in fusions that hold the optimizer's work;
    "with_other_ms": in fusions that hold work of the block or the mixer
    outside every part}}, "mixed_fusions": the longest fusions of any of
    the three kinds [[instruction, the part it counts for, ms, what it holds
    inside]]}."""
    out = {}
    for area, rows in instructions.items():
        parts, mixed = {}, []
        for name, (part, ms) in rows.items():
            row = parts.setdefault(part, dict.fromkeys(
                ("ms", "in_text_ms", "with_parts_ms", "with_optimizer_ms",
                 "with_other_ms"), 0.0))
            row["ms"] += ms
            row["in_text_ms"] += ms * (name in in_text)
            inner = fused.get(name, {}).get("inner", {})
            kinds = (len([s for s in inner if s in PARTS]) > 1,
                     profile.OPTIMIZER in inner,
                     bool(inner) and any(s in inner for s in OTHER))
            for key, held in zip(("with_parts_ms", "with_optimizer_ms",
                                  "with_other_ms"), kinds):
                row[key] += ms * held
            if any(kinds):
                mixed.append([name, part, ms,
                              {str(k): n for k, n in inner.items()}])
        if parts:
            out[area] = {"parts": parts, "mixed_fusions": sorted(
                mixed, key=lambda row: -row[2])[:SHOWN]}
    return out


def main(name, table=None):
    if table is None:
        path = tr.find_xplane(os.path.join(ROOT, ".bench_trace", name))
        trace = tr.load(path)
        reduced = inner_reduce.reduce_file(
            path, trace, trace.modules[min(trace.modules)])
        if reduced is None:
            raise SystemExit("the trace at %s names none of the parts" % path)
        instructions = {area: reduced[area]["instructions"]
                        for area in ("attn", "ssm")}
    else:
        with open(table) as f:
            instructions = json.load(f)["instructions"]
    text = compiled_text(name)
    fused = profile.fused_scopes(text, SCOPES)
    print(json.dumps({
        "cell": name, "fusions_holding_a_scope": len(fused),
        "of_them_mixed": sum(f["mixed"] for f in fused.values()),
        "ms_a_step": joined(instructions, fused,
                            set(_INSTRUCTION.findall(text))),
        "instructions": instructions}), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
