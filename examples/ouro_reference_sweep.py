"""The readings behind the limits of `benchmark/builders/ouro.py` and of
`benchmark/builders/xing.py` (`--workload xing29b_1chip`) and of
`benchmark/builders/sdar.py` (`--workload sdar30b_1chip`; any cell whose
builder returns `readings`): how far the bf16 system is from the float32
reference at the benchmark's own sizes, over several seeds, three ways:

- `bf16`: the system as the cell runs it;
- `fp8`: the same system with every matrix of its parameters rounded to
  fp8's precision (e4m3) and back, against the reference on the unrounded
  parameters: the nearest precision below the configuration's, which the
  limits must refuse;
- `one_pass` (the looped LM): the reference of the stack run once, which
  they must refuse as well (the comparison sees the loop). The Xing
  builder's `readings` hold their own references of another model (no
  shared expert, one Sinkhorn iteration, no module's loss) in every call.

Chip only (the reference at L=4096 wants the device's memory), about two
minutes for eight seeds.

Usage: python examples/ouro_reference_sweep.py [--seeds 8] [--first 1]
           [--workload ouro2b6_1chip] [--rehearse]
"""

import argparse
import inspect
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--first", type=int, default=2147483000)
    ap.add_argument("--workload", default="ouro2b6_1chip")
    ap.add_argument("--rehearse", action="store_true",
                    help="the files' tiny `rehearse` sizes, on any device")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmark.run import (BENCH_DIR, find_cell, load_json, load_plugin,
                               with_rehearsal)
    from horovod_tpu import parallel

    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, entry = find_cell(manifest, args.workload)
    config = with_rehearsal(load_json(os.path.join(ROOT, entry["file"])),
                            args.rehearse)
    traffic = with_rehearsal(load_json(os.path.join(
        BENCH_DIR, "traffic", cell["traffic"] + ".json")), args.rehearse)
    mesh = parallel.data_parallel_mesh(devices=jax.devices()[:1])
    print("device:", jax.devices()[0].device_kind, flush=True)

    def fp8(params):
        """Every matrix rounded to e4m3's precision (four significant
        bits, round to nearest even) on the bits of its f32: a convert to
        `float8_e4m3fn` and back is folded away by the TPU's compiler."""
        def rounded(x):
            bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
            keep = jnp.uint32(0xFFF00000)             # 3 of 23 mantissa bits
            bits = (bits + jnp.uint32((1 << 19) - 1)
                    + ((bits >> 20) & jnp.uint32(1))) & keep
            return jax.lax.bitcast_convert_type(bits, x.dtype)

        return jax.tree_util.tree_map(
            lambda x: rounded(x) if x.ndim >= 2 else x, params)

    # Built once: the seed only picks the keys of weights and tokens.
    built = load_plugin("builders", config["builder"]).build(
        config, traffic, mesh, 0, abstract=True)
    init_params = jax.jit(built["init_params"])
    make_tokens = jax.jit(built["make_tokens"])
    fp8 = jax.jit(fp8)
    for seed in range(args.first, args.first + args.seeds):
        k_param, k_tok = jax.random.split(jax.random.PRNGKey(seed))
        params = init_params(k_param)
        seq = make_tokens(k_tok)[0]
        # A batch that carries a noise key beside its tokens (block
        # diffusion: `sdar30b_1chip`) is read under the seed's own.
        kw = {"key": built["make_noise_key"](k_tok)} \
            if "make_noise_key" in built else {}
        takes = inspect.signature(built["readings"]).parameters
        # Where `readings` also holds the first gradient (`others`), two
        # sets of parameters and a gradient do not fit a chip: the fp8
        # system's gradient is the builder's `verify`'s to read.
        low = {"others": False} if "others" in takes else {}
        out = {"seed": seed,
               "bf16": built["readings"](params, params, seq, **kw),
               "fp8": built["readings"](fp8(params), params, seq, **kw,
                                        **low)}
        if "ref_passes" in takes:
            out["one_pass"] = built["readings"](params, params, seq,
                                                ref_passes=1)
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
