"""Analytic operation and byte counts — the benchmark's own yardstick.

Nothing here reads the program or the compiler: every count follows from
the sizes in a configuration file, so a PR that changes the program cannot
change what `mfu` or a roofline share is measured against. One
multiply-accumulate is two operations. "Model" counts are what the forward
and backward passes require (recomputation not counted); "executed" counts
are what a kernel's algorithm runs, recomputation included.
"""


# --------------------------------------------------------------------------
# Decoder-only transformer (the shape `models.Transformer` builds: four
# square attention projections, an ungated two-matrix MLP, an untied head).
# --------------------------------------------------------------------------

def transformer_matmul_params(hidden, mlp, vocab, layers):
    """Parameters that sit in a matrix multiplication: q, k, v, out
    (4·hidden²) and the MLP (2·hidden·mlp) per layer, plus the output head
    (hidden·vocab). The embedding is a gather and the norms are
    elementwise; neither is counted."""
    return layers * (4 * hidden * hidden + 2 * hidden * mlp) + hidden * vocab


def transformer_params(hidden, mlp, vocab, layers):
    """Every parameter held: the matmul parameters, the embedding table,
    two norm scales a layer and the final norm."""
    return (transformer_matmul_params(hidden, mlp, vocab, layers)
            + vocab * hidden + (2 * layers + 1) * hidden)


def attention_matmul_flops(batch, heads, length, head_dim, causal=True):
    """Operations of ONE of attention's matrix products (QK^T or PV) over
    a batch: 2·B·H·L²·D, halved when causal (L²/2 pairs; the L/2 diagonal
    surplus is left out, as is usual)."""
    flops = 2.0 * batch * heads * length * length * head_dim
    return flops / 2.0 if causal else flops


def transformer_model_flops_per_token(hidden, mlp, vocab, layers, heads,
                                      head_dim, length):
    """Forward + backward operations one token requires: 6 per matmul
    parameter (2 forward, 4 backward) plus attention's 2 products forward
    and 4 backward (6 matmul-equivalents; the kernels' recomputation of
    the scores is NOT counted here)."""
    dense = 6.0 * transformer_matmul_params(hidden, mlp, vocab, layers)
    attn = layers * 6.0 * attention_matmul_flops(1, heads, length, head_dim)
    return dense + attn / length


# Matmul-equivalents a flash kernel executes per block pair, by the name the
# program gives the kernel (`hvd.profile.flash_plan` names the ones a call
# runs: the builder asks it and hands the names here, so the count follows
# the path the program takes and not a form it once had): the forward forms
# QK^T and PV (2); the one-kernel backward recomputes the scores and forms
# dP, dV, dK and dQ (5); as two kernels dQ recomputes the scores and forms
# dP and dQ (3), dK/dV recomputes them and forms dP, dV and dK (4).
FLASH_EXECUTED_MATMULS = {"hvd_flash_fwd": 2, "hvd_flash_bwd": 5,
                          "hvd_flash_dq": 3, "hvd_flash_dkv": 4}


def flash_executed_flops(kernels, batch, heads, length, head_dim,
                         causal=True):
    """Operations the flash kernels named `kernels` (the keys of
    `flash_plan`'s forward and backward answers) execute for one attention
    layer in a train step: 7 matmul-equivalents with the one-kernel
    backward, 9 with dQ and dK/dV apart."""
    return (sum(FLASH_EXECUTED_MATMULS[k] for k in kernels)
            * attention_matmul_flops(batch, heads, length, head_dim, causal))


def flash_min_bytes(kernels, batch, heads, kv_heads, length, head_dim,
                    itemsize=2):
    """Least bytes those kernels move to and from device memory for one
    layer in a train step, each tensor once per kernel that needs it:
    forward reads q, k, v and writes o and the row logsumexp; the
    one-kernel backward reads q, k, v, dO and the two f32 row statistics
    (logsumexp, delta) and writes dq, dk, dv; as two kernels dQ reads the
    same and writes dq, dK/dV reads the same and writes dk, dv. A row
    statistic is counted at 4 bytes a row, whatever padding the kernel
    keeps it in."""
    q_like = batch * heads * length * head_dim * itemsize
    kv_like = batch * kv_heads * length * head_dim * itemsize
    rows = batch * heads * length * 4
    cost = {"hvd_flash_fwd": 2 * q_like + 2 * kv_like + rows,
            "hvd_flash_bwd": 3 * q_like + 4 * kv_like + 2 * rows,
            "hvd_flash_dq": 3 * q_like + 2 * kv_like + 2 * rows,
            "hvd_flash_dkv": 2 * q_like + 4 * kv_like + 2 * rows}
    return sum(cost[k] for k in kernels)


# --------------------------------------------------------------------------
# ResNet-50 v1.5 (He et al. Table 1, 50-layer; the 3x3 convolution of a
# bottleneck carries the stride, as in torchvision's `resnet50`).
# --------------------------------------------------------------------------

def _conv_out(size, kernel, stride, pad):
    return (size + 2 * pad - kernel) // stride + 1


def resnet50_convs(image=224, classes=1000, stage_sizes=(3, 4, 6, 3),
                   width=64):
    """Every convolution and the classifier as (name, out_h, kernel,
    c_in, c_out): the multiply-accumulates of one are
    out_h²·kernel²·c_in·c_out."""
    layers = []
    size = _conv_out(image, 7, 2, 3)
    layers.append(("conv_init", size, 7, 3, width))
    size = _conv_out(size, 3, 2, 1)  # max pool
    c_in = width
    for stage, blocks in enumerate(stage_sizes):
        f = width * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            out = _conv_out(size, 3, stride, 1)
            tag = "s%d.b%d." % (stage, block)
            layers.append((tag + "conv1", size, 1, c_in, f))
            layers.append((tag + "conv2", out, 3, f, f))
            layers.append((tag + "conv3", out, 1, f, 4 * f))
            if c_in != 4 * f or stride != 1:
                layers.append((tag + "proj", out, 1, c_in, 4 * f))
            size, c_in = out, 4 * f
    layers.append(("classifier", 1, 1, c_in, classes))
    return layers


def resnet50_forward_macs(image=224, classes=1000):
    return sum(h * h * k * k * ci * co
               for _, h, k, ci, co in resnet50_convs(image, classes))


def resnet50_model_flops_per_image(image=224, classes=1000):
    """Forward + backward operations one image requires: every layer's
    forward product, its weight gradient and its input gradient — except
    the first convolution's input gradient, which nothing needs (the image
    is not trained). BatchNorm, ReLU and pooling are elementwise and are
    not counted."""
    convs = resnet50_convs(image, classes)
    macs = sum(h * h * k * k * ci * co for _, h, k, ci, co in convs)
    _, h, k, ci, co = convs[0]
    return 2.0 * (3 * macs - h * h * k * k * ci * co)


def resnet50_params(image=224, classes=1000):
    """Trainable parameters: convolution kernels, the classifier with its
    bias, and a scale and a bias per BatchNorm (one after every
    convolution)."""
    convs = resnet50_convs(image, classes)
    total = 0
    for name, _, k, ci, co in convs:
        total += k * k * ci * co
        total += co if name == "classifier" else 2 * co
    return total
