"""Bytes-accessed regression guard for the traffic-lean BN (tier-1).

Golden JSON of the resnet50 train step's ``cost_analysis()`` bytes
under stock flax BN vs lean BN vs the norm-free floor, at a
CPU-compilable shape. The sensitive invariant is the BN-TAX reduction
(step bytes minus the norm-free floor): a future change that silently
re-materializes an activation pass — a saved x_hat, a stored ReLU mask,
a layout-copying view through the custom-VJP boundary (each measured
during round 10, on XLA:CPU's cost model) — adds a full per-site
activation pass, which moves the tax by ~30% while moving whole-step
bytes by only ~1%.

Regenerate the golden after an INTENTIONAL change with the command in
its `regenerate` field (it prints `bn_traffic_step_stats` of this file
for the three variants).
"""

import json
import os

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(REPO_ROOT, "tests", "golden",
                      "bn_traffic_resnet50.json")

# Absolute-bytes drift allowed for jax/XLA version changes before the
# golden must be regenerated; the tax assertion below is the tight one.
ABS_TOLERANCE = 0.20
# Allowed tax-reduction slack: a single re-materialized activation pass
# at the golden shape moves the tax reduction by ~0.3, far outside.
TAX_TOLERANCE = 0.08


def bn_traffic_step_stats(norm, batch=32, image_size=64, dtype="bfloat16"):
    """Compiles the REAL resnet50 train step (make_train_step over a
    1-device mesh) for the given norm variant and returns XLA's own
    accounting of it: ``{"bytes_accessed", "flops", "temp_bytes"}``.

    Abstract lowering only (eval_shape params, ShapeDtypeStruct batch):
    no training compute, no chip — reproducible under
    ``JAX_PLATFORMS=cpu``. A count of XLA:CPU's cost model, never a
    device metric."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models.resnet import ResNet, BottleneckBlock
    from horovod_tpu.parallel import data_parallel_mesh, make_train_step
    from horovod_tpu.parallel.train import cross_entropy_loss

    model = ResNet(stage_sizes=[3, 4, 6, 3], block_cls=BottleneckBlock,
                   norm=norm, num_classes=1000, dtype=getattr(jnp, dtype))
    rng = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(
        lambda: model.init(rng, jnp.zeros((1, image_size, image_size, 3)),
                           train=False))
    params = jax.tree_util.tree_map(
        lambda sd: jax.ShapeDtypeStruct(sd.shape, sd.dtype),
        shapes["params"])
    # Running-stat VALUES are irrelevant to the lowering; zeros of the
    # right shape avoid paying a real model init.
    batch_stats = jax.tree_util.tree_map(
        lambda sd: jnp.zeros(sd.shape, sd.dtype),
        shapes.get("batch_stats", {}))
    mutable = ["batch_stats"] if batch_stats else []

    def loss_fn(p, b):
        state = {"params": p}
        if batch_stats:
            state["batch_stats"] = batch_stats
            logits, _ = model.apply(state, b["x"], train=True,
                                    mutable=mutable)
        else:
            logits = model.apply(state, b["x"], train=True)
        return cross_entropy_loss(logits, b["y"])

    mesh = data_parallel_mesh(devices=jax.devices("cpu")[:1])
    opt = optax.sgd(0.01, momentum=0.9)
    step = make_train_step(loss_fn, opt, mesh, donate=False)
    opt_state = jax.eval_shape(opt.init, params)
    x = jax.ShapeDtypeStruct((batch, image_size, image_size, 3),
                             jnp.float32)
    y = jax.ShapeDtypeStruct((batch,), jnp.int32)
    compiled = step.lower(params, opt_state, {"x": x, "y": y}).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    mem = compiled.memory_analysis()
    return {
        "bytes_accessed": float(cost["bytes accessed"]),
        "flops": float(cost.get("flops", 0.0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
    }


@pytest.fixture(scope="module")
def measured():
    cfg = json.load(open(GOLDEN))["config"]
    return {norm: bn_traffic_step_stats(
        norm, batch=cfg["batch"], image_size=cfg["image_size"],
        dtype=cfg["dtype"])
        for norm in ("batch", "lean", "none")}


def test_lean_bn_tax_reduction_holds(measured):
    golden = json.load(open(GOLDEN))
    stock = measured["batch"]["bytes_accessed"]
    lean = measured["lean"]["bytes_accessed"]
    floor = measured["none"]["bytes_accessed"]
    assert lean < stock, (lean, stock)
    tax_reduction = 1.0 - (lean - floor) / (stock - floor)
    assert tax_reduction >= golden["bn_tax_reduction"] - TAX_TOLERANCE, (
        "lean BN's bytes-accessed advantage over stock flax BN "
        "regressed: tax reduction %.4f vs golden %.4f (+/-%.2f). A "
        "change re-materialized an activation pass the lean path "
        "exists to eliminate (stored x_hat / stored ReLU mask / "
        "layout-copying view). If intentional, regenerate %s with the "
        "command in its `regenerate` field."
        % (tax_reduction, golden["bn_tax_reduction"], TAX_TOLERANCE,
           GOLDEN))


def test_absolute_bytes_near_golden(measured):
    """Coarse drift alarm: jax/XLA upgrades legitimately move absolute
    bytes; past +/-20% the golden no longer describes this toolchain
    and must be regenerated so the tax assertion stays meaningful."""
    golden = json.load(open(GOLDEN))
    for norm, key in (("batch", "stock_bytes_accessed"),
                      ("lean", "lean_bytes_accessed"),
                      ("none", "normfree_floor_bytes_accessed")):
        got = measured[norm]["bytes_accessed"]
        ref = golden[key]
        assert abs(got - ref) <= ABS_TOLERANCE * ref, (
            "%s train-step bytes drifted beyond %d%% of the golden "
            "(%.4g vs %.4g): regenerate %s (see its `regenerate` "
            "field) so the BN-tax guard keeps a meaningful baseline"
            % (norm, 100 * ABS_TOLERANCE, got, ref, GOLDEN))
