from .base import describe, param_count
from .bert import BertClassifier, BertEncoder, BertMLM
from .lenet import LeNet
from .resnet import ResNet, ResNet18, ResNet34, ResNet50, ResNet101, ResNet152
from .moe import MoeMlp, moe_lm, tiny_moe_lm
from .pipelined import PipelinedLM, pipelined_lm, tiny_pipe_lm
from .llama import LlamaLM, llama, tiny_llama
from .nemotron_h import NemotronHLM, nemotron_h, tiny_nemotron_h
from .granite_hybrid import (
    GraniteHybridLM, granite_hybrid, tiny_granite_hybrid,
)
from .solar_open2 import SolarOpen2LM, solar_open2, tiny_solar_open2
from .transformer import TransformerLM, gpt2, tiny_lm
from .vit import ViT, vit
