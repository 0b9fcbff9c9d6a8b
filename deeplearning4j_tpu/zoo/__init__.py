"""Model zoo.

TPU-native equivalent of deeplearning4j-zoo (SURVEY §2.8): each model is a
config-builder factory (ref: InstantiableModel iface / ZooModel.java:28-81)
producing a MultiLayerNetwork or ComputationGraph. The model set mirrors
zoo/model/*: LeNet, AlexNet, VGG16, VGG19, ResNet50, GoogLeNet,
InceptionResNetV1, FaceNetNN4Small2, SimpleCNN, TextGenerationLSTM, plus
TinyYOLO-style Darknet (ref objdetect).
"""

from deeplearning4j_tpu.zoo.base import ZooModel, MODEL_REGISTRY, get_model  # noqa: F401
from deeplearning4j_tpu.zoo.lenet import LeNet  # noqa: F401
from deeplearning4j_tpu.zoo.alexnet import AlexNet  # noqa: F401
from deeplearning4j_tpu.zoo.simple_cnn import SimpleCNN  # noqa: F401
from deeplearning4j_tpu.zoo.vgg import VGG16, VGG19  # noqa: F401
from deeplearning4j_tpu.zoo.resnet import ResNet50  # noqa: F401
from deeplearning4j_tpu.zoo.googlenet import GoogLeNet  # noqa: F401
from deeplearning4j_tpu.zoo.inception_resnet import InceptionResNetV1, FaceNetNN4Small2  # noqa: F401
from deeplearning4j_tpu.zoo.text_lstm import TextGenerationLSTM
from deeplearning4j_tpu.zoo.transformer import TextGenerationTransformer  # noqa: F401
from deeplearning4j_tpu.zoo.sparse_latent_moe import SparseLatentMoETransformer  # noqa: F401
from deeplearning4j_tpu.zoo.hybrid_linear import HybridLinearTransformer  # noqa: F401
from deeplearning4j_tpu.zoo.sparse_gqa_moe import SparseGQAMoETransformer  # noqa: F401
from deeplearning4j_tpu.zoo.imagenet import ImageNetLabels  # noqa: F401
