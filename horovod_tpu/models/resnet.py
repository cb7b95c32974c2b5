"""ResNet v1.5 family in flax.linen, laid out for TPU.

The reference benchmarks data-parallel training of ResNet-50/101 with
its synthetic benchmark scripts
(``examples/pytorch/pytorch_synthetic_benchmark.py:24`` uses
``models.resnet50``; ``docs/benchmarks.rst:15-43`` records the
tf_cnn_benchmarks numbers).  This is the model of the benchmark's
control cell (``chipbench/adapters/cnn_train.py``).

TPU-first choices:

* NHWC layout (XLA:TPU's native convolution layout).
* bfloat16 activations / float32 parameters and batch stats — the MXU
  consumes bf16 directly; master weights stay f32 for optimizer math.
* The stride-2 3x3 conv sits in the middle of the bottleneck
  (ResNet v1.5 — the variant torchvision's resnet50 implements, so the
  per-image FLOPs match the reference benchmark model).
"""

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

ModuleDef = Any


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3(stride) -> 1x1 bottleneck with projection shortcut."""
    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1),
                                 self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class ResNet(nn.Module):
    """ResNet v1.5.  ``stage_sizes``: blocks per stage."""
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype,
                       param_dtype=jnp.float32)
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                       param_dtype=jnp.float32, axis_name=None)
        x = x.astype(self.dtype)
        x = conv(self.num_filters, (7, 7), (2, 2),
                 padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = BottleneckBlock(
                    self.num_filters * 2 ** i, strides=strides,
                    conv=conv, norm=norm, act=nn.relu)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32,
                     param_dtype=jnp.float32, name="head")(x)
        return x


ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3])
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3])
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3])
