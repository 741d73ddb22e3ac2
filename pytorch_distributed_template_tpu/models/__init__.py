from .base import describe, param_count
from .bert import BertClassifier, BertEncoder, BertMLM
from .lenet import LeNet
from .resnet import ResNet, ResNet18, ResNet34, ResNet50, ResNet101, ResNet152
from .moe import MoeMlp, moe_lm, tiny_moe_lm
from .pipelined import PipelinedLM, pipelined_lm, tiny_pipe_lm
from .llama import LlamaLM, llama, tiny_llama
from .hybrid import (
    HybridLM, granite_hybrid, lfm2_moe, nemotron_h, solar_open2,
    tiny_granite_hybrid, tiny_lfm2_moe, tiny_nemotron_h, tiny_solar_open2,
)
from .transformer import TransformerLM, gpt2, tiny_lm
from .vit import ViT, vit
