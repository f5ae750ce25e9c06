"""Model zoo backing the reference's benchmark/example configs
(`BASELINE.json` `configs`; reference examples/ directory):

* :mod:`.resnet`   — ResNet-18/34/50/101/152 (flax), the flagship
  benchmark model (reference `examples/tensorflow2_synthetic_benchmark.py`,
  `examples/pytorch_imagenet_resnet50.py`).
* :mod:`.mnist`    — 2-layer CNN (reference `examples/tensorflow2_mnist.py`).
* :mod:`.word2vec` — skip-gram with negative sampling; sparse embedding
  gradients exercise the allgather path (reference
  `examples/tensorflow_word2vec.py`).
* :mod:`.transformer` — decoder-only transformer with optional ring
  attention for long-context sequence parallelism (TPU-first extension).
* :mod:`.block_diffusion` — block-diffusion training of the decoder (a
  noisy and a clean copy of every sequence under one block mask, a 1/t
  weighted masked loss): the batch, the noisy half, the noise's counters.
* :mod:`.imagenet_extras` — VGG-16 and Inception V3, the other models in
  the reference's published 512-GPU scaling table
  (`docs/benchmarks.rst:13-14`).

All models are written TPU-first: NHWC conv layouts, bfloat16 compute with
float32 parameters, static shapes, no data-dependent Python control flow.
"""

from .resnet import (ResNet, ResNet18, ResNet34, ResNet50, ResNet50GN,  # noqa: F401
                     ResNet50Lean, ResNet50NF, ResNet101, ResNet101NF,
                     ResNet152)
from .mnist import MnistCNN  # noqa: F401
from .word2vec import SkipGram  # noqa: F401
from .transformer import (AttentionShape, Layer, Transformer,  # noqa: F401
                          TransformerConfig,
                          Yarn, hc_stats, kda_stats, ssd_stats)
from .block_diffusion import (block_diffusion_batch,  # noqa: F401
                              block_diffusion_noisy_half,
                              block_diffusion_stats)
from .imagenet_extras import VGG16, InceptionV3  # noqa: F401
