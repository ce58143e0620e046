"""singa_tpu.models — the model zoo (reference parity: examples/mlp,
examples/cnn model definitions + the ONNX-zoo transformer families,
BASELINE.json:7-11).

Families:
  * mlp          — MLP for MNIST-class data (BASELINE.json:7)
  * cnn          — simple CNN / LeNet-5 / AlexNet (BASELINE.json:7-8)
  * resnet       — ResNet-18/34/50/101/152, CIFAR + ImageNet stems
                   (BASELINE.json:8,10)
  * vgg          — VGG-11/13/16/19 (+BN) (BASELINE.json:8)
  * transformer  — GPT-2 and BERT (BASELINE.json:9)
  * llama        — Llama-3 family, the flagship stretch config
                   (BASELINE.json:11): RMSNorm, RoPE, SwiGLU, GQA
  * zaya         — ZAYA1: attention in a compressed latent with
                   convolutional mixing (CCA), top-1 experts behind an
                   MLP router, tied head; inference only
  * granite_hybrid — Granite 4.0-H: Mamba-2 state-space layers to one
                   attention layer without positional embedding, a
                   held share of the experts beside a shared MLP, tied
                   head; inference only
  * glm_mla      — GLM-4.7-Flash: multi-head latent attention over a
                   cache of one compressed row a token, a dense layer,
                   then sigmoid-routed experts with a correction bias
                   beside a shared expert, untied head; inference only
  * ling         — Ling-3.0-flash: Kimi delta attention (a per-channel
                   gated delta rule with an f32 state) in five layers of
                   six, MLA with a head-wise gate in the sixth, experts
                   routed within the best groups beside a shared expert,
                   untied head; inference only

Every model is a singa_tpu.model.Model: imperative forward, trains
eagerly or as one compiled XLA module, shards over a mesh via the
sharding rules each module exports (see singa_tpu.parallel).
"""

from . import mlp
from . import cnn
from . import resnet
from . import vgg
from . import transformer
from . import llama
from . import zaya
from . import granite_hybrid
from . import glm_mla
from . import ling

from .mlp import MLP
from .cnn import CNN, LeNet5, AlexNet
from .resnet import (ResNet, resnet18, resnet34, resnet50, resnet101,
                     resnet152)
from .vgg import VGG, vgg11, vgg13, vgg16, vgg19
from .transformer import GPT2, BERT, GPT2Config, BERTConfig
from .llama import Llama, LlamaConfig
from .zaya import Zaya, ZayaConfig
from .granite_hybrid import GraniteHybrid, GraniteHybridConfig
from .glm_mla import GlmMla, GlmMlaConfig
from .ling import Ling, LingConfig
from .convert import (from_hf, from_hf_bert, from_hf_gpt2,
                      from_hf_llama, from_hf_mistral,
                      from_hf_mixtral, to_hf)

__all__ = [
    "mlp", "cnn", "resnet", "vgg", "transformer", "llama", "zaya",
    "granite_hybrid", "glm_mla", "ling",
    "MLP", "CNN", "LeNet5", "AlexNet",
    "ResNet", "resnet18", "resnet34", "resnet50", "resnet101", "resnet152",
    "VGG", "vgg11", "vgg13", "vgg16", "vgg19",
    "GPT2", "BERT", "GPT2Config", "BERTConfig",
    "Llama", "LlamaConfig", "Zaya", "ZayaConfig",
    "GraniteHybrid", "GraniteHybridConfig", "GlmMla", "GlmMlaConfig",
    "Ling", "LingConfig",
    "from_hf", "from_hf_bert", "from_hf_gpt2", "from_hf_llama",
    "from_hf_mistral", "from_hf_mixtral",
    "to_hf",
]
