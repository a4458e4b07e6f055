"""Flagship LLM model families (TPU-first).

The reference keeps its LLM zoo in the PaddleNLP ecosystem on top of the
core framework; this package ships the framework-native equivalents used
by the acceptance configs (BASELINE.json #3-#5): a Llama-family decoder
(RMSNorm/rope/flash-attention/SwiGLU) and a BERT encoder family
(fused post-LN attention/FFN blocks, tied MLM decoder, pretraining
criterion), both built on the fused-op API, sized by config, single-chip
or hybrid-parallel via fleet. Beside them the expert decoders the
serving benchmark runs, a file a family: ``xing4`` (latent attention,
dropless sigmoid experts, mHC), ``solar_open2`` (KDA state rows beside
NoPE-GQA pages, a held share of experts) and ``kimi_linear`` (KDA state
rows beside NoPE-MLA latent pages, a leading dense layer, a held share
of experts: assembled from the other two's parts, layer by layer from
the config).
"""
from .bert import (  # noqa: F401
    BertConfig,
    BertForPretraining,
    BertModel,
    BertPretrainingCriterion,
)
from .kimi_linear import (  # noqa: F401
    KimiLinearConfig,
    KimiLinearForCausalLM,
)
from .gpt_moe import GPTMoEConfig, GPTMoEForCausalLM  # noqa: F401
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaForCausalLM,
    LlamaModel,
    causal_lm_loss,
)
from .llama_pipe import (  # noqa: F401
    LlamaDecoderLayerTP,
    LlamaForCausalLMPipe,
)
from .solar_open2 import (  # noqa: F401
    SolarOpen2Config,
    SolarOpen2ForCausalLM,
)
from .xing4 import Xing4Config, Xing4ForCausalLM  # noqa: F401
