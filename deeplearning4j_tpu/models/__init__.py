"""Model zoo (reference: deeplearning4j-zoo)."""

from deeplearning4j_tpu.models.zoo import (
    AlexNet,
    DeepSeekV2LM,
    FaceNetNN4Small2,
    FalconH1LM,
    GoogLeNet,
    GraniteMoeHybridLM,
    InceptionResNetV1,
    LeNet,
    ResNet50,
    SimpleCNN,
    TextGenerationLSTM,
    TransformerLM,
    TrinityLM,
    VGG16,
    VGG19,
    ZooModel,
    greedy_generate,
    sample_generate,
    zoo_models,
)

__all__ = [
    "AlexNet", "DeepSeekV2LM", "FaceNetNN4Small2", "FalconH1LM", "GoogLeNet", "GraniteMoeHybridLM", "InceptionResNetV1", "LeNet",
    "ResNet50", "SimpleCNN", "TextGenerationLSTM", "TransformerLM", "TrinityLM", "VGG16", "VGG19",
    "ZooModel", "greedy_generate", "sample_generate", "zoo_models",
]
