"""Model zoo.

Parity surface: reference deeplearning4j-zoo/ — 11 instantiable
architectures (zoo/model/*.java) + ZooModel.initPretrained weight loading
(ZooModel.java:40).
"""

from deeplearning4j_tpu.zoo.zoo_model import ZooModel
from deeplearning4j_tpu.zoo.simple import (
    LeNet, SimpleCNN, AlexNet, VGG16, VGG19, Darknet19, TextGenerationLSTM,
    TinyTransformer,
)
from deeplearning4j_tpu.zoo.resnet import ResNet50, ResNet50Cifar
from deeplearning4j_tpu.zoo.inception import (
    GoogLeNet, InceptionResNetV1, FaceNetNN4Small2,
)
from deeplearning4j_tpu.zoo.decoder import SparseDecoder

__all__ = ["ZooModel", "LeNet", "SimpleCNN", "AlexNet", "VGG16", "VGG19",
           "Darknet19", "TextGenerationLSTM", "TinyTransformer", "ResNet50", "ResNet50Cifar", "GoogLeNet",
           "InceptionResNetV1", "FaceNetNN4Small2", "SparseDecoder"]
