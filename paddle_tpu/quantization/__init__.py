"""paddle.quantization parity (python/paddle/quantization/ — unverified):
QuantConfig + QAT/PTQ over fake-quant simulation.

TPU design: TRAINING-time quantization is *simulated* (fake-quant) —
scales are learned/observed and quant/dequant round-trips run in the
graph with a straight-through estimator, exactly the reference's
QAT/PTQ training semantics; ``convert`` bakes the final scales into
ObservedLayers. SERVING-time quantization is REAL narrow-dtype
execution: ``quantize_for_serving`` converts the weights to
(int8, per-channel scale) pairs executed as dequant -> matmul
(``serving.int8_matmul_composed``), and ``kv.QuantizedKV`` stores the
serving KV caches as int8 values + per-token scales (the paged pools'
``cache_dtype="int8"``), halving weight and KV HBM again under bf16.
"""
from .config import QuantConfig  # noqa: F401
from .observers import (  # noqa: F401
    AbsmaxObserver,
    PerChannelAbsmaxObserver,
)
from .qat import QAT  # noqa: F401
from .ptq import PTQ  # noqa: F401
from .quanters import FakeQuanterWithAbsMaxObserver  # noqa: F401
from .serving import (  # noqa: F401
    QuantizedLinear,
    quantize_for_serving,
)
from . import kv  # noqa: F401
