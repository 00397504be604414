"""Model zoo (ref deeplearning4j-zoo): instantiable architectures + ModelSelector."""
from deeplearning4j_tpu.models.alexnet import AlexNet
from deeplearning4j_tpu.models.facenet_nn4_small2 import FaceNetNN4Small2
from deeplearning4j_tpu.models.googlenet import GoogLeNet
from deeplearning4j_tpu.models.inception_resnet_v1 import InceptionResNetV1
from deeplearning4j_tpu.models.lenet import LeNet
from deeplearning4j_tpu.models.olmo_hybrid import OlmoHybrid
from deeplearning4j_tpu.models.qwen3_next import Qwen3Next
from deeplearning4j_tpu.models.resnet50 import ResNet50
from deeplearning4j_tpu.models.simple_cnn import SimpleCNN, TextGenerationLSTM
from deeplearning4j_tpu.models.vgg import VGG16, VGG19
from deeplearning4j_tpu.models.xing4 import Xing4
from deeplearning4j_tpu.models.zoo_model import PretrainedType, ZooModel

ZOO = {
    "lenet": LeNet,
    "alexnet": AlexNet,
    "vgg16": VGG16,
    "vgg19": VGG19,
    "resnet50": ResNet50,
    "simplecnn": SimpleCNN,
    "textgenlstm": TextGenerationLSTM,
}


class ModelSelector:
    """(ref zoo/ModelSelector.java) — select zoo models by name."""

    @staticmethod
    def select(name: str, num_labels: int = 1000, seed: int = 123, **kw) -> ZooModel:
        key = name.lower()
        if key not in ZOO:
            raise ValueError(f"Unknown zoo model '{name}'; available: {sorted(ZOO)}")
        return ZOO[key](num_labels, seed=seed, **kw)
