// TensorFlow custom-op kernels for horovod_tpu collectives.
//
// Makes allreduce/allgather/broadcast real graph nodes: they compose with
// tf.function, tf.gradients (gradients are registered on the Python side,
// horovod_tpu/tensorflow/mpi_ops.py) and SavedModel export, instead of
// detouring through tf.py_function. Capability parity with the reference
// async CPU kernels (/root/reference horovod/tensorflow/mpi_ops.cc:276-463);
// fresh implementation: kernels call the framework-agnostic handle-based
// C API of libhorovod_tpu.so (native/operations.cc), whose symbols are
// already in the process (loaded RTLD_GLOBAL by common/basics.py), and
// AsyncOpKernel completion rides a scheduled closure that blocks on the
// handle — no TF thread ever enters the core's background loop.
//
// Build: `make libhorovod_tpu_tf.so TF_CFLAGS=... TF_LDFLAGS=...` with the
// flags from tf.sysconfig (driven lazily by horovod_tpu/tensorflow).

#include <cstdint>
#include <string>
#include <vector>

#include "tensorflow/core/framework/common_shape_fns.h"
#include "tensorflow/core/framework/op.h"
#include "tensorflow/core/framework/op_kernel.h"
#include "tensorflow/core/framework/shape_inference.h"

extern "C" {
int horovod_tpu_enqueue_allreduce(const char* name, const void* data,
                                  void* output, int ndim, const int64_t* shape,
                                  int dtype, double prescale, double postscale,
                                  int compression);
int horovod_tpu_default_compression();
int horovod_tpu_enqueue_allgather(const char* name, const void* data, int ndim,
                                  const int64_t* shape, int dtype);
int horovod_tpu_enqueue_broadcast(const char* name, const void* data,
                                  void* output, int ndim, const int64_t* shape,
                                  int dtype, int root_rank);
int horovod_tpu_wait(int handle);
const char* horovod_tpu_error_string(int handle);
int64_t horovod_tpu_allgather_bytes(int handle);
int64_t horovod_tpu_allgather_rank_dim(int handle, int rank);
int horovod_tpu_allgather_copy(int handle, void* out);
void horovod_tpu_release(int handle);
int horovod_tpu_size();
int horovod_tpu_initialized();
}

namespace {

using namespace tensorflow;  // NOLINT

// Values must match native/message.h DataType (same table as
// common/basics.py _NUMPY_TO_DTYPE).
int HvdDtype(DataType dt) {
  switch (dt) {
    case DT_UINT8: return 0;
    case DT_INT8: return 1;
    case DT_UINT16: return 2;
    case DT_INT16: return 3;
    case DT_INT32: return 4;
    case DT_INT64: return 5;
    case DT_HALF: return 6;
    case DT_FLOAT: return 7;
    case DT_DOUBLE: return 8;
    case DT_BOOL: return 9;
    case DT_BFLOAT16: return 10;
    default: return -1;
  }
}

std::vector<int64_t> ShapeVec(const Tensor& t) {
  std::vector<int64_t> dims(t.dims());
  for (int i = 0; i < t.dims(); ++i) dims[i] = t.dim_size(i);
  if (dims.empty()) dims.push_back(1);  // 0-d rides as shape (1,)
  return dims;
}

const void* DataPtr(const Tensor& t) {
  return static_cast<const void*>(t.tensor_data().data());
}

void* MutableDataPtr(Tensor* t) {
  return const_cast<char*>(t->tensor_data().data());
}

Status CheckReady(DataType dt, int* hvd_dtype) {
  if (!horovod_tpu_initialized()) {
    return errors::FailedPrecondition(
        "horovod_tpu is not initialized; call hvd.init() before running "
        "collectives");
  }
  *hvd_dtype = HvdDtype(dt);
  if (*hvd_dtype < 0) {
    return errors::InvalidArgument("unsupported dtype for horovod_tpu: ",
                                   DataTypeString(dt));
  }
  return Status();
}

// Completes `handle` off the TF executor thread, sets the op status and
// fires `done`. The captured tensors keep their buffers alive until the
// core's background thread has consumed them.
void FinishAsync(OpKernelContext* ctx, AsyncOpKernel::DoneCallback done,
                 int handle, Tensor input_ref) {
  Env::Default()->SchedClosure([ctx, done, handle, input_ref]() {
    if (horovod_tpu_wait(handle) != 0) {
      ctx->SetStatus(errors::Internal("horovod_tpu collective failed: ",
                                      horovod_tpu_error_string(handle)));
    }
    horovod_tpu_release(handle);
    done();
  });
}

class HorovodTpuAllreduceOp : public AsyncOpKernel {
 public:
  explicit HorovodTpuAllreduceOp(OpKernelConstruction* c) : AsyncOpKernel(c) {
    OP_REQUIRES_OK(c, c->GetAttr("op_name", &op_name_));
    OP_REQUIRES_OK(c, c->GetAttr("average", &average_));
    OP_REQUIRES_OK(c, c->GetAttr("prescale", &prescale_));
    OP_REQUIRES_OK(c, c->GetAttr("postscale", &postscale_));
  }

  void ComputeAsync(OpKernelContext* ctx, DoneCallback done) override {
    const Tensor& input = ctx->input(0);
    int hvd_dtype;
    OP_REQUIRES_OK_ASYNC(ctx, CheckReady(input.dtype(), &hvd_dtype), done);
    Tensor* output = nullptr;
    OP_REQUIRES_OK_ASYNC(
        ctx, ctx->allocate_output(0, input.shape(), &output), done);
    std::vector<int64_t> dims = ShapeVec(input);
    // `average` divides by the communicator size at run (not trace) time.
    double post = average_ ? postscale_ / horovod_tpu_size() : postscale_;
    // Wire compression rides the job-wide env default here (the TF
    // binding's Compression codecs stay tensor-level); negotiation
    // validates the mode cross-rank like any other param.
    int handle = horovod_tpu_enqueue_allreduce(
        op_name_.c_str(), DataPtr(input), MutableDataPtr(output),
        static_cast<int>(dims.size()), dims.data(), hvd_dtype, prescale_,
        post, horovod_tpu_default_compression());
    FinishAsync(ctx, done, handle, input);
  }

 private:
  std::string op_name_;
  bool average_;
  float prescale_, postscale_;
};

class HorovodTpuAllgatherOp : public AsyncOpKernel {
 public:
  explicit HorovodTpuAllgatherOp(OpKernelConstruction* c) : AsyncOpKernel(c) {
    OP_REQUIRES_OK(c, c->GetAttr("op_name", &op_name_));
  }

  void ComputeAsync(OpKernelContext* ctx, DoneCallback done) override {
    const Tensor input = ctx->input(0);
    int hvd_dtype;
    OP_REQUIRES_OK_ASYNC(ctx, CheckReady(input.dtype(), &hvd_dtype), done);
    std::vector<int64_t> dims = ShapeVec(input);
    int handle = horovod_tpu_enqueue_allgather(
        op_name_.c_str(), DataPtr(input), static_cast<int>(dims.size()),
        dims.data(), hvd_dtype);
    // Output first-dim is only known at completion (ranks may gather
    // unequal slices): allocate inside the completion closure.
    Env::Default()->SchedClosure([ctx, done, handle, input]() {
      if (horovod_tpu_wait(handle) != 0) {
        ctx->SetStatus(errors::Internal("horovod_tpu allgather failed: ",
                                        horovod_tpu_error_string(handle)));
        horovod_tpu_release(handle);
        done();
        return;
      }
      int64_t first_dim = 0;
      for (int r = 0; r < horovod_tpu_size(); ++r) {
        int64_t d = horovod_tpu_allgather_rank_dim(handle, r);
        if (d < 0) {
          ctx->SetStatus(errors::Internal("allgather rank sizes missing"));
          horovod_tpu_release(handle);
          done();
          return;
        }
        first_dim += d;
      }
      TensorShape out_shape = input.shape();
      if (out_shape.dims() == 0) out_shape.AddDim(1);
      out_shape.set_dim(0, first_dim);
      Tensor* output = nullptr;
      Status s = ctx->allocate_output(0, out_shape, &output);
      if (s.ok()) {
        int64_t nbytes = horovod_tpu_allgather_bytes(handle);
        if (nbytes != static_cast<int64_t>(output->tensor_data().size())) {
          s = errors::Internal("allgather size mismatch");
        } else {
          horovod_tpu_allgather_copy(handle, MutableDataPtr(output));
        }
      }
      if (!s.ok()) ctx->SetStatus(s);
      horovod_tpu_release(handle);
      done();
    });
  }

 private:
  std::string op_name_;
};

class HorovodTpuBroadcastOp : public AsyncOpKernel {
 public:
  explicit HorovodTpuBroadcastOp(OpKernelConstruction* c) : AsyncOpKernel(c) {
    OP_REQUIRES_OK(c, c->GetAttr("op_name", &op_name_));
    OP_REQUIRES_OK(c, c->GetAttr("root_rank", &root_rank_));
  }

  void ComputeAsync(OpKernelContext* ctx, DoneCallback done) override {
    const Tensor& input = ctx->input(0);
    int hvd_dtype;
    OP_REQUIRES_OK_ASYNC(ctx, CheckReady(input.dtype(), &hvd_dtype), done);
    Tensor* output = nullptr;
    OP_REQUIRES_OK_ASYNC(
        ctx, ctx->allocate_output(0, input.shape(), &output), done);
    std::vector<int64_t> dims = ShapeVec(input);
    int handle = horovod_tpu_enqueue_broadcast(
        op_name_.c_str(), DataPtr(input), MutableDataPtr(output),
        static_cast<int>(dims.size()), dims.data(), hvd_dtype, root_rank_);
    FinishAsync(ctx, done, handle, input);
  }

 private:
  std::string op_name_;
  int root_rank_;
};

REGISTER_OP("HorovodTpuAllreduce")
    .Attr("T: {uint8, int8, uint16, int16, int32, int64, float16, float32, "
          "float64, bfloat16}")
    .Attr("op_name: string")
    .Attr("average: bool = false")
    .SetIsStateful()
    .Attr("prescale: float = 1.0")
    .Attr("postscale: float = 1.0")
    .Input("tensor: T")
    .Output("reduced: T")
    .SetShapeFn(shape_inference::UnchangedShape);

REGISTER_OP("HorovodTpuAllgather")
    .Attr("T: {uint8, int8, uint16, int16, int32, int64, float16, float32, "
          "float64, bool, bfloat16}")
    .Attr("op_name: string")
    .SetIsStateful()
    .Input("tensor: T")
    .Output("gathered: T")
    .SetShapeFn([](shape_inference::InferenceContext* c) {
      shape_inference::ShapeHandle in = c->input(0);
      if (!c->RankKnown(in)) {
        c->set_output(0, c->UnknownShape());
        return Status();
      }
      shape_inference::ShapeHandle out;
      // First dim becomes the (unknown until run time) gathered length.
      TF_RETURN_IF_ERROR(c->ReplaceDim(in, 0, c->UnknownDim(), &out));
      c->set_output(0, out);
      return Status();
    });

REGISTER_OP("HorovodTpuBroadcast")
    .Attr("T: {uint8, int8, uint16, int16, int32, int64, float16, float32, "
          "float64, bool, bfloat16}")
    .Attr("op_name: string")
    .Attr("root_rank: int")
    .SetIsStateful()
    .Input("tensor: T")
    .Output("broadcast: T")
    .SetShapeFn(shape_inference::UnchangedShape);

REGISTER_KERNEL_BUILDER(Name("HorovodTpuAllreduce").Device(DEVICE_CPU),
                        HorovodTpuAllreduceOp);
REGISTER_KERNEL_BUILDER(Name("HorovodTpuAllgather").Device(DEVICE_CPU),
                        HorovodTpuAllgatherOp);
REGISTER_KERNEL_BUILDER(Name("HorovodTpuBroadcast").Device(DEVICE_CPU),
                        HorovodTpuBroadcastOp);

}  // namespace
