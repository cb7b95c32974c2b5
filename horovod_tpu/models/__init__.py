"""Model zoo for benchmarks and parallelism flagships."""

from .resnet import ResNet, ResNet50, ResNet101, ResNet152  # noqa: F401
from .vgg import VGG, VGG16, VGG19  # noqa: F401
from .inception import InceptionV3  # noqa: F401
from .transformer import (  # noqa: F401
    TransformerConfig, TransformerLM, DecoderBlock, RMSNorm, MLAAttention,
    dense_causal_attention, lm_loss, chunked_lm_loss, make_fused_lm_loss,
    make_generate_fn,
)
from .kda import KDAMixer, kda_chunked  # noqa: F401
from .vit import ViT, ViTConfig, ViT_B16, ViT_S16  # noqa: F401
