"""Flagship LLM model families (TPU-first).

The reference keeps its LLM zoo in the PaddleNLP ecosystem on top of the
core framework; this package ships the framework-native equivalents used
by the acceptance configs (BASELINE.json #3-#5): a Llama-family decoder
(RMSNorm/rope/flash-attention/SwiGLU) and a BERT encoder family
(fused post-LN attention/FFN blocks, tied MLM decoder, pretraining
criterion), both built on the fused-op API, sized by config, single-chip
or hybrid-parallel via fleet.
"""
from .bert import (  # noqa: F401
    BertConfig,
    BertForPretraining,
    BertModel,
    BertPretrainingCriterion,
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
