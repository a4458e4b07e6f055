"""paddle.jit — the step compiler.

Reference parity: python/paddle/jit/ (dy2static AST translator + SOT
bytecode capture + CINN offload — unverified, mount empty). TPU-first
redesign per SURVEY.md §3.5: there is no source translation at all — JAX
tracing IS the dynamic-to-static bridge, and XLA is the compiler CINN was
retargeting. ``to_static`` wraps a Layer/function into a traced, cached,
whole-program-compiled callable; ``save``/``load`` export/import StableHLO
via jax.export (the deployment format replacing ProgramDesc+params).
"""
from .api import TranslatedLayer, ignore_module, load, not_to_static, save, to_static  # noqa: F401
from .compile_cache import place_compile_cache  # noqa: F401
from .trainer import CompiledTrainStep  # noqa: F401
from . import dy2static  # noqa: F401
