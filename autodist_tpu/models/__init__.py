"""Model zoo: the reference's benchmark families, rebuilt TPU-native
(``/root/reference/examples/benchmark/``: imagenet.py VGG16/ResNet101/
DenseNet121/InceptionV3, bert.py, ncf.py; ``examples/lm1b/`` LSTM LM)."""
from autodist_tpu.models.resnet import (  # noqa: F401
    ResNet18, ResNet34, ResNet50, ResNet101, ResNet152,
)
from autodist_tpu.models.norm import (  # noqa: F401
    FusedBatchNorm, FusedGroupNorm,
)
from autodist_tpu.models.vgg import VGG16  # noqa: F401
from autodist_tpu.models.densenet import DenseNet121, DenseNet169  # noqa: F401
from autodist_tpu.models.inception import InceptionV3  # noqa: F401
from autodist_tpu.models.bert import (  # noqa: F401
    BERT_BASE, BERT_LARGE, BERT_TINY, Bert, BertConfig, BertForPreTraining,
)
from autodist_tpu.models.gpt import (  # noqa: F401
    GPT, GPT_SMALL, GPT_TINY, GPTConfig,
)
from autodist_tpu.models.llama import (  # noqa: F401
    LLAMA_TINY, Llama, LlamaConfig,
)
from autodist_tpu.models.qwen3_next import (  # noqa: F401
    QWEN3_NEXT_TINY, Qwen3Next, Qwen3NextConfig,
)
from autodist_tpu.models.lm import LMConfig, LSTMBody, LSTMLM  # noqa: F401
from autodist_tpu.models.ncf import NCFConfig, NeuMF  # noqa: F401
