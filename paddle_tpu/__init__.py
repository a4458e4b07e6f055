"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capability surface, built on JAX/XLA/Pallas.

Not a port: the reference's C++ kernel library, CUDA kernels, executors and
CINN compiler are all *absorbed by XLA* (see SURVEY.md §7 design stance);
this package is the framework shell — imperative tensor/autograd UX, nn/
optimizer/data APIs, the Fleet distributed stack mapped onto jax.sharding
meshes, and Pallas kernels for the fused hot ops.
"""
from __future__ import annotations

__version__ = "0.5.0"  # keep in sync with pyproject.toml

from . import ops as _ops_ns
from .core import dtypes as _dtypes
from .core import tensor as _tensor_mod
from .core.device import (
    CPUPlace,
    CUDAPinnedPlace,
    CUDAPlace,
    CustomPlace,
    Place,
    TPUPlace,
    XPUPlace,
    device_count,
    get_device,
    is_compiled_with_cinn,
    is_compiled_with_cuda,
    is_compiled_with_distribute,
    is_compiled_with_rocm,
    is_compiled_with_tpu,
    is_compiled_with_xpu,
    set_device,
)
from .core.dtypes import (
    bfloat16,
    bool_,
    complex64,
    complex128,
    finfo,
    float16,
    float32,
    float64,
    get_default_dtype,
    iinfo,
    int8,
    int16,
    int32,
    int64,
    set_default_dtype,
    uint8,
)
from .core.lazy import LazyGuard
from .core.random import get_rng_state, seed, set_rng_state
from .core.tape import is_grad_enabled, no_grad, set_grad_enabled
from .core.tensor import Parameter, Tensor, is_tensor

# wire the ops namespace into Tensor dunders
_tensor_mod._bind_ops(_ops_ns)

# lift every op to the top-level namespace (paddle.add, paddle.reshape, ...)
from .ops import *  # noqa: F401,F403

from . import autograd  # noqa: E402
from .autograd import grad  # noqa: E402
from .autograd.backward import backward as _backward_multi  # noqa: E402,F401

# ---------------------------------------------------------------------------
# Tensor method binding: every op whose first arg is a tensor becomes a method
_TENSOR_METHODS = (
    "add subtract multiply divide floor_divide mod remainder pow maximum "
    "minimum fmax fmin atan2 sqrt rsqrt exp expm1 log log2 log10 log1p abs "
    "neg sign sin cos tan asin acos atan sinh cosh tanh asinh acosh atanh "
    "erf erfinv floor ceil round trunc frac reciprocal square sigmoid "
    "isfinite isinf isnan scale clip lerp nan_to_num matmul mm bmm dot inner "
    "outer addmm kron cross cumsum cumprod logsumexp logcumsumexp logaddexp "
    "trace diff sum mean prod max min amax amin all any nanmean nansum "
    "median nanmedian std var count_nonzero quantile cast reshape reshape_ "
    "transpose t swapaxes moveaxis flatten squeeze squeeze_ unsqueeze "
    "unsqueeze_ split chunk unbind tile expand broadcast_to expand_as flip "
    "roll repeat_interleave tril triu diag diagonal gather gather_nd "
    "index_select index_sample take_along_axis put_along_axis scatter "
    "scatter_nd_add masked_fill masked_select where unique argmax argmin "
    "argsort sort topk kthvalue mode nonzero searchsorted equal not_equal "
    "less_than less_equal greater_than greater_equal logical_and logical_or "
    "logical_xor logical_not bitwise_and bitwise_or bitwise_xor bitwise_not "
    "isclose allclose equal_all norm det inv pinv cholesky matrix_power "
    "slice pad index_put copysign gammaln gammainc gammaincc positive "
    "negative vecdot reduce_as view view_as as_strided select_scatter "
    "diagonal_scatter tensor_split hsplit vsplit dsplit isreal crop "
    "matrix_exp lu_unpack "
    # inplace-suffix family + misc tail
    "exp_ sqrt_ rsqrt_ ceil_ floor_ round_ reciprocal_ tanh_ sigmoid_ "
    "clip_ scale_ tril_ triu_ cumsum_ flatten_ t_ add_ subtract_ "
    "multiply_ remainder_ copysign_ lerp_ masked_fill_ renorm_ "
    "index_add_ index_put_ put_along_axis_ scatter_ relu_ softmax_ "
    "fill_ zero_ fill_diagonal_ fill_diagonal_tensor "
    "fill_diagonal_tensor_ normal_ uniform_ exponential_ geometric_ "
    "cauchy_ log_normal_ where_ rank increment shard_index multiplex "
    "addbmm baddbmm histogram_bin_edges is_complex is_floating_point "
    "is_integer "
    # audit-closure tail (tools/api_audit.py): reference Tensor methods
    # whose functions already existed top-level
    "angle as_complex as_real bernoulli bincount bucketize conj cummax "
    "cummin deg2rad diag_embed diagflat digamma dist floor_mod frexp gcd "
    "heaviside histogram hypot i0 i0e i1 i1e imag index_add index_fill "
    "inverse is_empty lcm ldexp lgamma logit masked_scatter multinomial "
    "mv nanquantile nextafter rad2deg real renorm rot90 scatter_nd sgn "
    "signbit sinc stanh strided_slice take tensordot unflatten unfold "
    "unique_consecutive unstack vander add_n complex"
).split()

for _name in _TENSOR_METHODS:
    _fn = getattr(_ops_ns, _name, None)
    if _fn is not None and not hasattr(Tensor, _name):
        setattr(Tensor, _name, _fn)

# paddle.dtype: the type of Tensor.dtype values. Tensor.dtype yields numpy
# dtype objects; the literals (paddle.float32, ...) are the jnp scalar
# types. In the reference the literals ARE instances of paddle.dtype, so
# scripts write ``isinstance(paddle.float32, paddle.dtype)`` — honoured
# here via __instancecheck__ accepting both forms. Calling paddle.dtype(x)
# constructs a numpy dtype, like the alias it replaces.
import numpy as _np  # noqa: E402


class _DTypeMeta(type):
    _literals = frozenset(
        map(id, (bfloat16, bool_, complex64, complex128, float16, float32,
                 float64, int8, int16, int32, int64, uint8))
    )

    def __instancecheck__(cls, obj):
        return isinstance(obj, _np.dtype) or id(obj) in cls._literals

    def __call__(cls, obj):
        # no default: np.dtype() raises too — paddle.dtype(None) silently
        # meaning float64 would be a wrong-dtype trap on a fp32 framework
        return _np.dtype(obj)


class dtype(metaclass=_DTypeMeta):
    """The type of dtype values: ``isinstance`` accepts numpy dtypes and
    the paddle dtype literals; calling it coerces to a numpy dtype."""

# paddle-compat static-mode switches (static graph == jax.jit here; these are
# retained as no-ops so reference scripts run unmodified)


def enable_static():
    raise NotImplementedError(
        "paddle_tpu is dygraph-first; use paddle_tpu.jit.to_static for the "
        "compiled path (whole-step jax.jit)."
    )


def disable_static():
    return None


def in_dynamic_mode():
    return True


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Tensor repr formatting (maps onto numpy print options, which
    Tensor.__repr__ uses)."""
    import numpy as _np

    kw = {}
    if precision is not None:
        kw["precision"] = int(precision)
    if threshold is not None:
        kw["threshold"] = int(threshold)
    if edgeitems is not None:
        kw["edgeitems"] = int(edgeitems)
    if linewidth is not None:
        kw["linewidth"] = int(linewidth)
    if sci_mode is not None:
        kw["suppress"] = not bool(sci_mode)
    _np.set_printoptions(**kw)


def disable_signal_handler():
    return None


# subsystem namespaces — extended as subsystems land (build order: SURVEY §7)
from . import linalg  # noqa: E402
from . import regularizer  # noqa: E402
from .regularizer import L1Decay, L2Decay  # noqa: E402
from . import framework  # noqa: E402
from .framework.io import load, save  # noqa: E402
from . import nn  # noqa: E402
from . import optimizer  # noqa: E402
from . import io  # noqa: E402
from . import distributed  # noqa: E402
from .nn.layer.layers import ParamAttr  # noqa: E402
from . import amp  # noqa: E402
from . import metric  # noqa: E402
from . import vision  # noqa: E402
from . import hapi  # noqa: E402
from .hapi.model import Model  # noqa: E402
from .hapi import callbacks  # noqa: E402
from . import static  # noqa: E402
from . import jit  # noqa: E402
from . import profiler  # noqa: E402
from . import observability  # noqa: E402
from . import checkpoint  # noqa: E402
from . import utils  # noqa: E402
from .utils.flags import get_flags, set_flags  # noqa: E402
from . import audio  # noqa: E402
from . import distribution  # noqa: E402
from . import geometric  # noqa: E402
from . import quantization  # noqa: E402
from . import fft  # noqa: E402
from . import signal  # noqa: E402
from . import text  # noqa: E402
from . import version  # noqa: E402
from .hapi.summary import flops, summary  # noqa: E402
from . import incubate  # noqa: E402
from . import inference  # noqa: E402
from . import models  # noqa: E402
from . import serving  # noqa: E402
from . import sparse  # noqa: E402
from . import analysis  # noqa: E402
