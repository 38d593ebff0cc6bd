"""Model zoo — canonical architectures (DL4J deeplearning4j-zoo parity).

Reference: /root/reference/deeplearning4j-zoo/src/main/java/org/deeplearning4j/zoo/
(`ZooModel.java`, `model/*.java`). Architectures are re-expressed TPU-first:
NHWC layouts, bf16-friendly compute, builders produce jit-compiled networks.
"""
from deeplearning4j_tpu.models.zoo import (
    ZooModel,
    LeNet,
    SimpleCNN,
    AlexNet,
    VGG16,
    VGG19,
    ResNet50,
    GoogLeNet,
    Darknet19,
    TinyYOLO,
    YOLO2,
    TextGenerationLSTM,
    InceptionResNetV1,
    FaceNetNN4Small2,
    UNet,
    model_by_name,
    zoo_models,
)
from deeplearning4j_tpu.models.transformer import (
    Glm4MoeLiteLM, KeyeVL2LM, KimiLinearLM, Lfm2MoeLM, NemotronHLM,
    SdarMoeLM,
    TransformerLM, TransformerLMMoE, Xing4LM,
)

__all__ = [
    "ZooModel", "LeNet", "SimpleCNN", "AlexNet", "VGG16", "VGG19",
    "ResNet50", "GoogLeNet", "Darknet19", "TinyYOLO", "YOLO2",
    "TextGenerationLSTM", "InceptionResNetV1", "FaceNetNN4Small2", "UNet",
    "TransformerLM", "TransformerLMMoE", "KimiLinearLM", "Glm4MoeLiteLM",
    "Lfm2MoeLM",
    "KeyeVL2LM", "SdarMoeLM", "NemotronHLM", "Xing4LM",
    "model_by_name", "zoo_models",
]
