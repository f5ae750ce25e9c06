"""Native TF graph ops for horovod_tpu collectives + gradient registration.

Loads (building on first use) the custom-op kernel library
``native/libhorovod_tpu_tf.so`` so allreduce/allgather/broadcast are real
graph nodes — differentiable, tf.function-composable, SavedModel-
exportable. Capability parity with the reference op loader + gradient
registrations (/root/reference horovod/tensorflow/mpi_ops.py:50-180);
fresh implementation over our handle-based C API.

Gradients (matching the reference's semantics):
  * allreduce: the gradient is itself allreduced (same scaling attrs) —
    each rank holds a different upstream grad, the true Jacobian-vector
    product sums them.
  * allgather: upstream grad covers the full gathered dim; allreduce it,
    then every rank slices out its own segment (segment boundaries come
    from an allgather of first-dim sizes, so unequal slices work).
  * broadcast: the root receives the summed grads of every rank's output;
    non-roots contribute zero to their (unused) input.
"""

import fcntl
import os
import subprocess
import threading

import tensorflow as tf
from tensorflow.python.framework import ops as tf_framework_ops

from horovod_tpu.common.basics import get_basics

_MOD_DIR = os.path.dirname(os.path.abspath(__file__))
_NATIVE_DIR = os.path.abspath(os.path.join(_MOD_DIR, "..", "native"))
_TF_LIB_PATH = os.path.join(_NATIVE_DIR, "libhorovod_tpu_tf.so")

_load_lock = threading.Lock()
_lib = None
_load_error = None


def _build_tf_ops():
    env = dict(os.environ)
    env["TF_CFLAGS"] = " ".join(tf.sysconfig.get_compile_flags())
    env["TF_LDFLAGS"] = " ".join(tf.sysconfig.get_link_flags())
    lock_path = os.path.join(_NATIVE_DIR, ".build_tf.lock")
    with open(lock_path, "w") as lock_file:
        fcntl.flock(lock_file, fcntl.LOCK_EX)
        try:
            # Always through make (a no-op when fresh): a library that
            # merely exists may be older than tf_ops.cc beside it.
            subprocess.run(["make", "tf"], cwd=_NATIVE_DIR, env=env,
                           check=True, stdout=subprocess.PIPE,
                           stderr=subprocess.STDOUT)
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                "failed to build libhorovod_tpu_tf.so:\n" +
                e.stdout.decode("utf-8", "replace")) from e
        finally:
            fcntl.flock(lock_file, fcntl.LOCK_UN)


def _load():
    """Builds + loads the kernel library once; returns the op module or
    None (with the failure remembered) when native ops are unavailable."""
    global _lib, _load_error
    if _lib is not None or _load_error is not None:
        return _lib
    with _load_lock:
        if _lib is not None or _load_error is not None:
            return _lib
        if os.environ.get("HVD_TPU_TF_NATIVE", "1") == "0":
            _load_error = "disabled via HVD_TPU_TF_NATIVE=0"
            return None
        try:
            # The kernels resolve core symbols from libhorovod_tpu.so,
            # which basics loads RTLD_GLOBAL — load it first.
            get_basics()
            _build_tf_ops()
            _lib = tf.load_op_library(_TF_LIB_PATH)
        except Exception as e:  # noqa: BLE001 — remember and fall back
            _load_error = str(e)
            return None
    return _lib


def native_ops_available():
    return _load() is not None


def load_error():
    _load()
    return _load_error


def allreduce(tensor, op_name, average=False, prescale=1.0, postscale=1.0):
    lib = _load()
    return lib.horovod_tpu_allreduce(tensor=tensor, op_name=op_name,
                                     average=average, prescale=prescale,
                                     postscale=postscale)


def allgather(tensor, op_name):
    lib = _load()
    squeeze = tensor.shape.rank == 0
    if squeeze:
        tensor = tf.reshape(tensor, [1])
    return lib.horovod_tpu_allgather(tensor=tensor, op_name=op_name)


def broadcast(tensor, root_rank, op_name):
    lib = _load()
    return lib.horovod_tpu_broadcast(tensor=tensor, op_name=op_name,
                                     root_rank=root_rank)


@tf_framework_ops.RegisterGradient("HorovodTpuAllreduce")
def _allreduce_grad(op, grad):
    # Reference semantics (horovod/tensorflow/mpi_ops.py:89-105): the
    # gradient of an allreduce is the allreduce of the gradient with the
    # same scaling.
    return allreduce(grad, op.get_attr("op_name").decode() + ".grad",
                     average=op.get_attr("average"),
                     prescale=op.get_attr("prescale"),
                     postscale=op.get_attr("postscale"))


@tf_framework_ops.RegisterGradient("HorovodTpuAllgather")
def _allgather_grad(op, grad):
    # Reference semantics (mpi_ops.py:107-141): sum the upstream grads,
    # then slice out this rank's segment (segment table via an allgather
    # of first-dim sizes, so unequal gathers differentiate correctly).
    import horovod_tpu as hvd

    op_name = op.get_attr("op_name").decode()
    grad = allreduce(grad, op_name + ".grad")
    my_dim = tf.shape(op.inputs[0], out_type=tf.int64)[:1]
    sizes = allgather(my_dim, op_name + ".grad_sizes")
    offset = tf.reduce_sum(sizes[:hvd.rank()])
    return tf.slice(grad, tf.concat(
        [[offset], tf.zeros([tf.rank(grad) - 1], tf.int64)], axis=0),
        tf.concat([sizes[hvd.rank():hvd.rank() + 1],
                   tf.fill([tf.rank(grad) - 1], tf.constant(-1, tf.int64))],
                  axis=0))


@tf_framework_ops.RegisterGradient("HorovodTpuBroadcast")
def _broadcast_grad(op, grad):
    # Reference semantics (mpi_ops.py:166-180): every rank's output grad
    # flows back to the root's input; non-root inputs are unused -> zero.
    import horovod_tpu as hvd

    op_name = op.get_attr("op_name").decode()
    reduced = allreduce(grad, op_name + ".grad")
    if hvd.rank() == op.get_attr("root_rank"):
        return reduced
    return tf.zeros_like(reduced)
