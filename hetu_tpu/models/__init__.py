from hetu_tpu.models.afmoe import Afmoe, AfmoeBlock, AfmoeConfig
from hetu_tpu.models.bert import (
    BertConfig,
    BertForMaskedLM,
    BertForNextSentencePrediction,
    BertForPreTraining,
    BertForSequenceClassification,
    BertModel,
    BertMoEForPreTraining,
    BertMoEModel,
    bert_base,
    bert_large,
)
from hetu_tpu.models.ctr import DCN, CTRConfig, DeepCrossing, DeepFM, WideDeep
from hetu_tpu.models.deepseek_v2 import (DeepseekV2, DeepseekV2Block,
                                         DeepseekV2Config)
from hetu_tpu.models.gpt import GPT, GPTConfig, gpt2_large, gpt2_medium, gpt2_small
from hetu_tpu.models.kimi_linear import (KimiLinear, KimiLinearBlock,
                                         KimiLinearConfig)
from hetu_tpu.models.moe_lm import MoEBlock, MoELM, MoELMConfig
from hetu_tpu.models.ncf import GMF, MF, MLPRec, NeuMF
from hetu_tpu.models.resnet import BasicBlock, ResNet, resnet18, resnet34
from hetu_tpu.models.rnn import (
    GRUCell,
    LSTMCell,
    RNN,
    RNNCell,
    RNNClassifier,
)
from hetu_tpu.models.simple import MLP, LeNet, LogReg, alexnet, vgg16
from hetu_tpu.models.swin import Swin, SwinConfig, swin_base, swin_large, swin_tiny
from hetu_tpu.models.t5 import (
    T5Config,
    T5ForConditionalGeneration,
    T5Model,
    t5_base,
    t5_large,
    t5_small,
)
from hetu_tpu.models.vit import ViT, ViTConfig, vit_base, vit_huge, vit_large
