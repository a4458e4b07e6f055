"""Fused TPU kernels (Pallas) behind the reference's fused-op API names.

Reference parity: paddle/phi/kernels/fusion/gpu/* + flash_attn third-party
lib (unverified, mount empty). Each module provides a Pallas TPU kernel and
a composed-jnp fallback (CPU/CI); call sites pick automatically.

Selection is measurement-driven: ``autotune`` holds the block-size
autotuner (measured search + persistent per-device result cache, see
``tools/kernel_tune.py``); flash attention resolves its block config
through it and publishes its selection, and every kernel its
fallbacks, as ``paddle_kernels_*`` registry metrics.
"""
from . import autotune  # noqa: F401
from . import flash_attention  # noqa: F401
from . import fused_adam  # noqa: F401
from . import rms_norm  # noqa: F401
from . import rope  # noqa: F401

# The ONE home of the 2 GiB fp32-score-matrix threshold that decides
# composed-vs-flash attention (BENCH_NOTES "Where the r3->r4 time went"
# and the selection logic both refer here).
from .flash_attention import SCORE_BYTES_THRESHOLD  # noqa: F401
