"""Flax model zoo.

Replaces the neural-network surface the reference reaches through
``tensorflow.keras`` — both user-defined keras models shipped as JSON
(reference: microservices/binary_executor_image/binary_execution.py:248-251)
and pre-trained ``keras.applications`` classes instantiated by the model
service (model_image/model.py:92-162).  Every zoo entry is a Flax module
wrapped in a :class:`~learningorchestra_tpu.train.neural.NeuralEstimator`,
which provides the keras-like ``fit/evaluate/predict`` methods the executor
layer drives by reflection.
"""

from learningorchestra_tpu.models.mlp import MLPClassifier, MLPRegressor
from learningorchestra_tpu.models.vision import (
    MnistCNN,
    MobileNet,
    ResNet18,
    ResNet50,
    VGG16,
)
from learningorchestra_tpu.models.text import (
    DecoderLM,
    LSTMClassifier,
    TransformerClassifier,
    BertModel,
)
from learningorchestra_tpu.models.longcontext import LongContextTransformer
from learningorchestra_tpu.models.moe import (
    BlockDiffusionMoELM,
    MoEDecoderLM,
    MoETransformerClassifier,
)
from learningorchestra_tpu.models.retention import RetentionLM
from learningorchestra_tpu.models.hybrid import HybridLM

__all__ = [
    "MLPClassifier",
    "MLPRegressor",
    "MnistCNN",
    "ResNet18",
    "ResNet50",
    "VGG16",
    "MobileNet",
    "LSTMClassifier",
    "TransformerClassifier",
    "BertModel",
    "DecoderLM",
    "LongContextTransformer",
    "MoEDecoderLM",
    "BlockDiffusionMoELM",
    "MoETransformerClassifier",
    "RetentionLM",
    "HybridLM",
]
