"""Getting going, in a process of its own (tests/test_profile.py): what a
job does before its first step, a repeated call, a second batch shape and
`hvd.shutdown()`; then the record as one JSON line. The caller gives the
compile cache's directory in the environment, and runs this twice on one."""

import json
import sys

import horovod_tpu as hvd

assert "jax" not in sys.modules
hvd.init()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from horovod_tpu.parallel import (data_parallel_mesh,  # noqa: E402
                                  make_train_step)


def loss_fn(params, batch):
    return jnp.mean((batch["x"] @ params["w"]) ** 2)


params = {"w": jnp.ones((8, 8))}
opt = optax.sgd(0.1)
mesh = data_parallel_mesh(devices=jax.devices("cpu")[:1])
step = make_train_step(loss_fn, opt, mesh, donate=False)
state = step.place(params, opt.init(params), {"x": jnp.ones((8, 8))})
for _ in range(2):  # the second call compiles nothing
    jax.block_until_ready(step(*state))
before_second_shape = len(hvd.profile.phases())
wider = step.place(params, opt.init(params), {"x": jnp.ones((16, 8))})
jax.block_until_ready(step(*wider))
before_shutdown = hvd.profile.phases()
hvd.shutdown()
print(json.dumps({
    "phases": hvd.profile.phases(), "compiles": hvd.profile.compiles(),
    "dropped": hvd.profile.dropped(),
    "before_second_shape": before_second_shape,
    "survived_shutdown": hvd.profile.phases() == before_shutdown}))
