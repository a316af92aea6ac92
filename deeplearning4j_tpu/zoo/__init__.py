"""Model zoo (reference: deeplearning4j-zoo zoo/model/*.java).

Architecture definitions only — the reference's pretrained-weight download
machinery (ZooModel.initPretrained) is replaced by Keras/TF import and
checkpoint loading. Each model exposes ``build() -> network`` (initialized,
ready for fit/output), mirroring ZooModel.init().
"""
from deeplearning4j_tpu.zoo.models import (
    AlexNet, LeNet, ResNet50, SimpleCNN, TextGenLSTM, TransformerEncoder,
    VGG16)
from deeplearning4j_tpu.zoo.models_ext import (
    Darknet19, SqueezeNet, TinyYOLO, UNet, Xception)
from deeplearning4j_tpu.zoo.models_wave3 import (
    FaceNet, InceptionResNetV1, NASNet, VGG19, YOLO2)
from deeplearning4j_tpu.zoo.bert import BERT_BASE, BERT_TINY, BertConfig, bert_base
from deeplearning4j_tpu.zoo.gpt import GPT_MEDIUM, GPT_TINY, GPTConfig, build_gpt
from deeplearning4j_tpu.zoo.smallthinker import SmallThinkerConfig
from deeplearning4j_tpu.zoo.glm_moe_lite import GlmMoeLiteConfig
from deeplearning4j_tpu.zoo.cohere2_moe import Cohere2MoeConfig

__all__ = ["LeNet", "SimpleCNN", "AlexNet", "VGG16", "ResNet50",
           "TextGenLSTM", "TransformerEncoder", "SqueezeNet", "UNet",
           "Xception", "Darknet19", "TinyYOLO", "VGG19", "InceptionResNetV1",
           "FaceNet", "NASNet", "YOLO2", "BertConfig", "BERT_BASE",
           "BERT_TINY", "bert_base", "GPTConfig", "GPT_MEDIUM", "GPT_TINY",
           "build_gpt", "SmallThinkerConfig",
           "GlmMoeLiteConfig", "Cohere2MoeConfig"]
