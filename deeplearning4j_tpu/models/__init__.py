"""Model zoo (ref deeplearning4j-zoo): instantiable architectures + ModelSelector.

Beside the reference's zoo, decoders built from the keys of a published
`config.json`, each trained through `ComputationGraph.fit_on_device`: `Xing4`
(latent attention, routed experts, hyper-connections, an MTP module),
`Qwen3Next` (Gated DeltaNet and gated attention, routed experts),
`OlmoHybrid` (Gated DeltaNet and attention, dense SwiGLU) and `Ouro` (one
stack of layers run several times with shared weights, an exit gate scoring
every pass). They are not in `ZOO`: they take a config, not a label count.
"""
from deeplearning4j_tpu.models.alexnet import AlexNet
from deeplearning4j_tpu.models.facenet_nn4_small2 import FaceNetNN4Small2
from deeplearning4j_tpu.models.googlenet import GoogLeNet
from deeplearning4j_tpu.models.inception_resnet_v1 import InceptionResNetV1
from deeplearning4j_tpu.models.lenet import LeNet
from deeplearning4j_tpu.models.olmo_hybrid import OlmoHybrid
from deeplearning4j_tpu.models.ouro import Ouro
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
