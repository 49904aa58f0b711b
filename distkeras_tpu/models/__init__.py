"""Model layer: functional adapters (Keras-3 / flax) and the in-tree zoo."""

from distkeras_tpu.models.adapter import (
    FlaxModel,
    FunctionalModel,
    ModelAdapter,
    TrainedModel,
    as_adapter,
)
from distkeras_tpu.models.moe import (
    MoEEncoderBlock,
    MoEFeedForward,
    MoETransformerClassifier,
    expert_partition,
)
from distkeras_tpu.models.hf import HuggingFaceModel
from distkeras_tpu.models.hf_staged import PretrainedStagedLM, gpt2_to_staged
from distkeras_tpu.models.generate import greedy_generate
from distkeras_tpu.models.latent_moe import LatentMoELM
from distkeras_tpu.models.scmoe import ShortcutMoELM
from distkeras_tpu.models.staged import StagedLM, StagedTransformer
from distkeras_tpu.models.transformer import (
    TransformerClassifier,
    TransformerEncoderBlock,
    TransformerLM,
)
from distkeras_tpu.models.zoo import CIFARCNN, MLP, MNISTCNN, ResNet20, TextCNN

__all__ = [
    "ModelAdapter",
    "FlaxModel",
    "FunctionalModel",
    "TrainedModel",
    "as_adapter",
    "MLP",
    "MNISTCNN",
    "CIFARCNN",
    "ResNet20",
    "TextCNN",
    "TransformerClassifier",
    "TransformerEncoderBlock",
    "TransformerLM",
    "LatentMoELM",
    "ShortcutMoELM",
    "StagedTransformer",
    "StagedLM",
    "greedy_generate",
    "MoEFeedForward",
    "MoEEncoderBlock",
    "MoETransformerClassifier",
    "expert_partition",
    "HuggingFaceModel",
    "PretrainedStagedLM",
    "gpt2_to_staged",
]
