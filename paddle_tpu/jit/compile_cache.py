"""Where XLA's persistent compilation cache lives.

Entry scripts (``chip_smoke.py``, ``bench.py``, ``tools/serve_bench.py``,
``tools/kernel_tune.py``, ``serving.fleet.launch``) call
:func:`place_compile_cache` once, before their first compile; importing
``paddle_tpu`` places nothing. The directory is part of every cache
key, so it is either the one the environment names
(``JAX_COMPILATION_CACHE_DIR`` — JAX reads that itself, nothing is set
here) or one fixed path inside the checkout: never a temporary
directory, a pid or a timestamp, which would never hit twice.

The serving AOT cache (:mod:`paddle_tpu.jit.aot_cache`) is a different
thing — serialized executables in a directory its caller names.
"""
from __future__ import annotations

import os

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env_dir = os.environ.get(ENV_DIR)
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
