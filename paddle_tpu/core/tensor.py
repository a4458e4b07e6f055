"""The paddle_tpu Tensor.

Reference parity: phi::DenseTensor + the Python Tensor facade
(reference: paddle/phi/core/dense_tensor.cc, python/paddle/tensor/ —
unverified, mount empty). TPU-first redesign: a Tensor is a thin mutable
handle around an immutable ``jax.Array``. "In-place" mutation (optimizer
updates, __setitem__, set_value) swaps the underlying array — the jax way —
while autograd metadata (``_node``/``_out_idx``/``grad``) gives the
imperative ``.backward()`` UX on top of jax VJPs. Storage, layout, strides,
and allocator concerns from the reference all collapse into jax.Array/XLA
(device memory is managed by the runtime's BFC allocator; there is nothing
idiomatic to reimplement there — see SURVEY.md §7 design stance).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import device as device_mod
from . import dtypes as dtypes_mod
from . import tape as tape_mod

# Populated by paddle_tpu/__init__.py after the ops namespace exists; dunder
# methods dispatch through it so Tensor math records autograd nodes.
_ops = None


def _bind_ops(ops_namespace):
    global _ops
    _ops = ops_namespace


class Tensor:
    __slots__ = (
        "value",
        "stop_gradient",
        "grad",
        "name",
        "persistable",
        "_node",
        "_out_idx",
        "_hooks",
        "_retain_grad",
        "__weakref__",
    )

    def __init__(self, value, stop_gradient=True, name=None):
        self.value = value  # jax.Array (or tracer inside jit)
        self.stop_gradient = stop_gradient
        self.grad = None
        self.name = name
        self.persistable = False
        self._node = None  # GradNode that produced this tensor
        self._out_idx = 0
        self._hooks = None
        self._retain_grad = False

    # ---------------------------------------------------------------- meta
    @property
    def shape(self):
        return list(self.value.shape)

    @property
    def ndim(self):
        return self.value.ndim

    def dim(self):
        return self.value.ndim

    def rank(self):
        return self.value.ndim

    @property
    def size(self):
        return int(np.prod(self.value.shape)) if self.value.shape else 1

    @property
    def dtype(self):
        return jnp.dtype(self.value.dtype)

    @property
    def place(self):
        return device_mod.current_place()

    @property
    def is_leaf(self):
        return self._node is None

    @property
    def T(self):
        return _ops.t(self)

    @property
    def mT(self):
        return _ops.matrix_transpose(self)

    def numel(self):
        return self.size

    def element_size(self):
        return np.dtype(self.dtype).itemsize

    def is_floating_point(self):
        return dtypes_mod.is_floating_point_dtype(self.dtype)

    # ------------------------------------------------------------- convert
    def numpy(self):
        return np.asarray(self.value)

    def item(self, *args):
        self._guard_concrete(".item()")
        arr = np.asarray(self.value)
        return arr.item(*args)

    def tolist(self):
        return np.asarray(self.value).tolist()

    def astype(self, dtype):
        return _ops.cast(self, dtype)

    def cast(self, dtype):
        return _ops.cast(self, dtype)

    def cpu(self):
        cpu_dev = device_mod.jax_device(device_mod.Place("cpu", 0))
        return Tensor(jax.device_put(self.value, cpu_dev), self.stop_gradient)

    def cuda(self, device_id=None, blocking=True):
        """Move to the accelerator (reference Tensor.cuda; here: the
        default non-CPU device — TPU)."""
        dev = device_mod.jax_device(device_mod.TPUPlace(device_id or 0))
        return Tensor(jax.device_put(self.value, dev), self.stop_gradient)

    def ndimension(self):
        return self.ndim

    def to(self, *args, **kwargs):
        out = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, str) and (
                a.startswith(("cpu", "tpu", "gpu")) or ":" in a
            ):
                dev = device_mod.jax_device(_parse_place(a))
                out = Tensor(jax.device_put(out.value, dev), out.stop_gradient)
            elif a is not None:
                out = out.astype(a)
        return out

    def pin_memory(self):  # host-staging is XLA-managed; API parity no-op
        return self

    def contiguous(self):  # jax arrays are always logically contiguous
        return self

    def is_contiguous(self):
        return True

    # ------------------------------------------------------------ autograd
    def backward(self, grad_tensor=None, retain_graph=False):
        from ..autograd.backward import run_backward

        run_backward(self, grad_tensor, retain_graph)

    def detach(self):
        t = Tensor(self.value, stop_gradient=True, name=self.name)
        return t

    def detach_(self):
        self._node = None
        self.stop_gradient = True
        return self

    def clone(self):
        return _ops.assign(self)

    def register_hook(self, hook):
        """Run ``hook(grad)`` when this tensor's cotangent is computed.

        If the hook returns a value it replaces the gradient (paddle parity).
        """
        if self._hooks is None:
            self._hooks = []
        self._hooks.append(hook)

        class _Handle:
            def __init__(h, hooks, fn):
                h._hooks, h._fn = hooks, fn

            def remove(h):
                if h._fn in h._hooks:
                    h._hooks.remove(h._fn)

        return _Handle(self._hooks, hook)

    def retain_grads(self):
        self._retain_grad = True

    def clear_grad(self):
        self.grad = None

    def clear_gradient(self, set_to_zero=False):
        if set_to_zero and self.grad is not None:
            self.grad = Tensor(jnp.zeros_like(self.grad.value))
        else:
            self.grad = None

    @property
    def gradient(self):
        return None if self.grad is None else self.grad.numpy()

    # ------------------------------------------------------------- mutation
    def set_value(self, value):
        """In-place value replacement (paddle Tensor.set_value parity)."""
        if isinstance(value, Tensor):
            value = value.value
        arr = jnp.asarray(value)
        if tuple(arr.shape) != tuple(self.value.shape):
            raise ValueError(
                f"set_value shape mismatch: {arr.shape} vs {self.value.shape}"
            )
        self.value = arr.astype(self.value.dtype)
        return self

    def copy_(self, other):
        return self.set_value(other)

    def fill_(self, v):
        self.value = jnp.full_like(self.value, v)
        return self

    def zero_(self):
        self.value = jnp.zeros_like(self.value)
        return self

    def _replace_with(self, other: "Tensor"):
        """Adopt another tensor's value + autograd identity (inplace ops)."""
        import weakref

        self.value = other.value
        self._node = other._node
        self._out_idx = other._out_idx
        self.stop_gradient = other.stop_gradient
        if self._node is not None:
            # the graph's output edge must track *this* object now
            self._node.out_refs[self._out_idx] = weakref.ref(self)
        return self

    def _alias_for_inplace(self):
        """Snapshot this tensor's graph identity before an in-place op.

        The alias becomes the recorded *input* of the in-place op (and takes
        over as the producer node's tracked output), so pre-mutation history
        stays reachable while ``self`` moves on to the new node. Without
        this, x[i]=v would make x input and output of its own GradNode and
        sever the upstream graph.
        """
        import weakref

        a = Tensor(self.value, self.stop_gradient, name=self.name)
        a._node = self._node
        a._out_idx = self._out_idx
        if a._node is not None:
            a._node.out_refs[a._out_idx] = weakref.ref(a)
        return a

    def _inplace(self, op, *args, **kw):
        alias = self._alias_for_inplace()
        return self._replace_with(op(alias, *args, **kw))

    # ------------------------------------------------------------- dunders
    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self.value.shape[0]

    def _guard_concrete(self, what):
        import jax as _jax

        if isinstance(self.value, _jax.core.Tracer):
            raise TypeError(
                f"{what} of a traced Tensor: inside to_static/jit the "
                "value is not available, so data-dependent Python control "
                "flow cannot be compiled. to_static auto-converts "
                "`if`/`elif`/`while`/`for i in range(...)` on Tensor "
                "conditions, including early return/break/continue "
                "inside them — but only when the function's source is "
                "importable (defined in a file, not a REPL) and the "
                "exit does not escape a try/except or a generator. "
                "Otherwise use paddle.static.nn.cond / while_loop / "
                "switch_case, or express the branch as a select with "
                "paddle.where. (reference: dy2static unsupported-syntax "
                "errors)"
            )

    def __bool__(self):
        self._guard_concrete("bool()")
        return bool(np.asarray(self.value))

    def __int__(self):
        self._guard_concrete("int()")
        return int(np.asarray(self.value))

    def __float__(self):
        self._guard_concrete("float()")
        return float(np.asarray(self.value))

    def __index__(self):
        self._guard_concrete("index()")
        return int(np.asarray(self.value))

    def __format__(self, spec):
        if self.ndim == 0:
            return format(self.item(), spec)
        return format(str(self), spec)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __hash__(self):
        return id(self)

    def __repr__(self):
        try:
            data = np.asarray(self.value)
            body = np.array2string(data, precision=6, separator=", ")
        except Exception:  # inside a jit trace
            body = f"<traced {self.value}>"
        return (
            f"Tensor(shape={self.shape}, dtype={np.dtype(self.dtype).name}, "
            f"place={self.place}, stop_gradient={self.stop_gradient},\n"
            f"       {body})"
        )

    # math dunders dispatch through the ops namespace (autograd-aware)
    def __add__(self, o):
        return _ops.add(self, o)

    def __radd__(self, o):
        return _ops.add(o, self)

    def __sub__(self, o):
        return _ops.subtract(self, o)

    def __rsub__(self, o):
        return _ops.subtract(o, self)

    def __mul__(self, o):
        return _ops.multiply(self, o)

    def __rmul__(self, o):
        return _ops.multiply(o, self)

    def __truediv__(self, o):
        return _ops.divide(self, o)

    def __rtruediv__(self, o):
        return _ops.divide(o, self)

    def __floordiv__(self, o):
        return _ops.floor_divide(self, o)

    def __mod__(self, o):
        return _ops.mod(self, o)

    def __pow__(self, o):
        return _ops.pow(self, o)

    def __rpow__(self, o):
        return _ops.pow(o, self)

    def __neg__(self):
        return _ops.neg(self)

    def __abs__(self):
        return _ops.abs(self)

    def __matmul__(self, o):
        return _ops.matmul(self, o)

    def __rmatmul__(self, o):
        return _ops.matmul(o, self)

    def __eq__(self, o):
        return _ops.equal(self, o)

    def __ne__(self, o):
        return _ops.not_equal(self, o)

    def __lt__(self, o):
        return _ops.less_than(self, o)

    def __le__(self, o):
        return _ops.less_equal(self, o)

    def __gt__(self, o):
        return _ops.greater_than(self, o)

    def __ge__(self, o):
        return _ops.greater_equal(self, o)

    def __invert__(self):
        return _ops.logical_not(self)

    def __and__(self, o):
        return _ops.logical_and(self, o)

    def __or__(self, o):
        return _ops.logical_or(self, o)

    def __xor__(self, o):
        return _ops.logical_xor(self, o)

    def __getitem__(self, idx):
        return _ops.getitem(self, idx)

    def __setitem__(self, idx, v):
        self._inplace(_ops.setitem, idx, v)

    # numpy protocol — lets np.asarray(tensor) work
    def __array__(self, dtype=None):
        arr = np.asarray(self.value)
        return arr.astype(dtype) if dtype is not None else arr


def _parse_place(s: str) -> device_mod.Place:
    s = s.lower()
    if s.startswith("gpu"):
        s = "tpu" + s[3:]
    if ":" in s:
        kind, _, idx = s.partition(":")
        return device_mod.Place(kind, int(idx))
    return device_mod.Place(s, 0)


class Parameter(Tensor):
    """Trainable tensor (reference: python/paddle/framework Parameter —
    unverified). stop_gradient defaults False; optimizers discover these via
    Layer.parameters()."""

    __slots__ = (
        "trainable",
        "optimize_attr",
        "regularizer",
        "is_distributed",
        "need_clip",
        "split_axis",
        "sequence_parallel",
        "_lazy_initializer",  # set under LazyGuard; see Layer.materialize
        "_lazy_seq",  # creation-order ticket for materialize() RNG replay
    )

    def __init__(self, value, trainable=True, name=None):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.is_distributed = False
        self.need_clip = True
        self.split_axis = None  # set by TP layers (mp partition axis)
        self.sequence_parallel = False  # set by SP's mark_as_... helper
        self.persistable = True


def is_tensor(obj) -> bool:
    return isinstance(obj, Tensor)


# jax pytree registration: Tensors flatten to their underlying array so whole
# models/state dicts can cross jit boundaries untouched.
jax.tree_util.register_pytree_node(
    Tensor,
    lambda t: ((t.value,), t.stop_gradient),
    lambda sg, vals: Tensor(vals[0], stop_gradient=sg),
)
jax.tree_util.register_pytree_node(
    Parameter,
    lambda t: ((t.value,), t.trainable),
    lambda tr, vals: Parameter(vals[0], trainable=tr),
)
