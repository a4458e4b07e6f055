"""The Layer base class (module system).

Reference parity: python/paddle/nn/layer/layers.py (unverified, mount
empty): parameters, buffers, sublayers, hooks, state_dict, train/eval,
apply/to, create_parameter with ParamAttr. TPU-specific addition:
``functional_state()``/``load_functional_state()`` snapshot the full
parameter+buffer pytree so whole layers can cross jax.jit boundaries — the
bridge between the imperative Layer API and functional transforms.
"""
from __future__ import annotations

import collections

import numpy as np

import jax
import jax.numpy as jnp

from ...core.dtypes import convert_dtype, get_default_dtype
from ...core.tensor import Parameter, Tensor
from .. import initializer as init_mod

_GLOBAL_INIT = [None, None]  # [weight_init, bias_init] via set_global_initializer


class ParamAttr:
    """Parameter attribute bundle (python/paddle/framework ParamAttr parity)."""

    def __init__(
        self,
        name=None,
        initializer=None,
        learning_rate=1.0,
        regularizer=None,
        trainable=True,
        do_model_average=True,
        need_clip=True,
    ):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip

    @staticmethod
    def _to_attr(attr):
        if attr is None:
            return ParamAttr()
        if isinstance(attr, ParamAttr):
            return attr
        if isinstance(attr, init_mod.Initializer):
            return ParamAttr(initializer=attr)
        if isinstance(attr, str):
            return ParamAttr(name=attr)
        if attr is False:
            return False
        raise TypeError(f"cannot convert {attr!r} to ParamAttr")


class _SubLayers(collections.OrderedDict):
    """A layer's ``_sub_layers``. Every way of registering a sublayer
    (attribute, ``add_sublayer``, a container's index) ends in a write
    here, which tells the sublayer the name it goes by:
    ``Layer.__call__`` opens that name as a ``jax.named_scope``, so the
    operations of a compiled program carry their module's path
    (``model/layers/0/self_attn/q_proj``) into the profiler trace."""

    def __setitem__(self, name, layer):
        if isinstance(layer, Layer):
            object.__setattr__(layer, "_scope_name", str(name))
        super().__setitem__(name, layer)


class Layer:
    _name_counters: dict = collections.defaultdict(int)

    def __init__(self, name_scope=None, dtype="float32"):
        cls = type(self).__name__.lower()
        idx = Layer._name_counters[cls]
        Layer._name_counters[cls] += 1
        object.__setattr__(self, "_full_name", name_scope or f"{cls}_{idx}")
        object.__setattr__(self, "_dtype", convert_dtype(dtype) or get_default_dtype())
        object.__setattr__(self, "_parameters", collections.OrderedDict())
        object.__setattr__(self, "_buffers", collections.OrderedDict())
        object.__setattr__(self, "_non_persistable_buffer_names", set())
        object.__setattr__(self, "_sub_layers", _SubLayers())
        object.__setattr__(self, "training", True)
        object.__setattr__(self, "_forward_pre_hooks", collections.OrderedDict())
        object.__setattr__(self, "_forward_post_hooks", collections.OrderedDict())
        object.__setattr__(self, "_casted_by_pure_fp16", False)

    # ------------------------------------------------------------ attribute
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning params")
            params[name] = value
            buffers.pop(name, None) if buffers else None
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            layers[name] = value
            self.__dict__.pop(name, None)
        elif params is not None and name in params:
            if value is None:
                del params[name]
                object.__setattr__(self, name, None)
            elif isinstance(value, Tensor):
                params[name].set_value(value)
            else:
                raise TypeError(f"cannot assign {type(value)} to parameter {name}")
        elif buffers is not None and name in buffers:
            if isinstance(value, Tensor):
                buffers[name] = value
            elif value is None:
                del buffers[name]
                object.__setattr__(self, name, None)
            else:
                object.__setattr__(self, name, value)
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'"
        )

    def __delattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        extra = []
        for store in ("_parameters", "_buffers", "_sub_layers"):
            extra += list(self.__dict__.get(store, ()))
        return list(super().__dir__()) + extra

    # ------------------------------------------------------------- creation
    def create_parameter(
        self,
        shape,
        attr=None,
        dtype=None,
        is_bias=False,
        default_initializer=None,
    ):
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        dtype = convert_dtype(dtype) or self._dtype
        initializer = attr.initializer or default_initializer
        if initializer is None:
            initializer = _GLOBAL_INIT[1 if is_bias else 0]
        if initializer is None:
            initializer = (
                init_mod.Constant(0.0) if is_bias else init_mod.XavierUniform()
            )
        from ...core import lazy as lazy_mod

        if lazy_mod.in_lazy_mode():
            # LazyGuard: abstract parameter — no allocation, no init
            # compute; materializable later, lowerable immediately
            value = lazy_mod.abstract_like(
                tuple(int(s) for s in shape), dtype
            )
            p = Parameter(value, trainable=attr.trainable, name=attr.name)
            p._lazy_initializer = initializer  # for materialize()
            # creation order, so materialize() replays the RNG stream in
            # the exact sequence eager init would have consumed it
            p._lazy_seq = lazy_mod.next_seq()
            p.optimize_attr = {"learning_rate": attr.learning_rate}
            p.regularizer = attr.regularizer
            p.need_clip = getattr(attr, "need_clip", True)
            self._maybe_lazy = True  # checked (then cleared) on __call__
            return p
        value = initializer(tuple(int(s) for s in shape), dtype)
        p = Parameter(value, trainable=attr.trainable, name=attr.name)
        p.optimize_attr = {"learning_rate": attr.learning_rate}
        p.regularizer = attr.regularizer
        p.need_clip = getattr(attr, "need_clip", True)
        return p

    def materialize(self):
        """Materialize every LazyGuard-created (abstract) parameter.

        Each parameter's recorded initializer is compiled with the
        parameter's sharding as ``out_shardings`` — on a device mesh the
        weight is initialized SHARD-LOCAL on its owning chips and a full
        host-resident copy never exists, which is the only way a
        LazyGuard-built 7B-class model can come up on real hardware.
        Initializers run in parameter CREATION order (not traversal
        order), so under the same seed materialize() reproduces eager
        init exactly. No-op for parameters that are already concrete.
        """
        import jax

        from ...core import lazy as lazy_mod

        todo = [
            p for _, p in self.named_parameters()
            if lazy_mod.is_abstract(p.value)
        ]
        todo.sort(key=lambda p: getattr(p, "_lazy_seq", 0))
        for p in todo:
            init = getattr(p, "_lazy_initializer", None)
            if init is None:
                init = init_mod.XavierUniform()
            shape = tuple(p.value.shape)
            dt = p.value.dtype
            sharding = getattr(p.value, "sharding", None)
            if sharding is not None:
                p.value = jax.jit(
                    lambda i=init, s=shape, d=dt: i(s, d),
                    out_shardings=sharding,
                )()
            else:
                p.value = init(shape, dt)
        for l in self.sublayers(include_self=True):
            l.__dict__.pop("_maybe_lazy", None)
        return self

    def create_tensor(self, name=None, dtype=None, default_initializer=None):
        dtype = convert_dtype(dtype) or self._dtype
        return Tensor(jnp.zeros([], dtype), name=name)

    def add_parameter(self, name, parameter):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError("add_parameter expects a Parameter")
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    # ------------------------------------------------------------ traversal
    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, sub, p in self._walk("_parameters", prefix, include_sublayers):
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield name, p

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        for name, sub, b in self._walk("_buffers", prefix, include_sublayers):
            if b is not None and id(b) not in seen:
                seen.add(id(b))
                yield name, b

    def _walk(self, store, prefix, include_sublayers):
        for name, obj in getattr(self, store).items():
            yield (prefix + name if not prefix else f"{prefix}.{name}"), self, obj
        if include_sublayers:
            for lname, sub in self._sub_layers.items():
                if sub is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                yield from sub._walk(store, sub_prefix, True)

    def children(self):
        yield from (l for _, l in self.named_children())

    def named_children(self):
        for name, sub in self._sub_layers.items():
            if sub is not None:
                yield name, sub

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix, self
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            p = f"{prefix}.{name}" if prefix else name
            yield from sub.named_sublayers(prefix=p, include_self=True)

    def apply(self, fn):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    # ------------------------------------------------------------ state
    def state_dict(
        self,
        destination=None,
        include_sublayers=True,
        structured_name_prefix="",
        use_hook=True,
    ):
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(
            prefix=structured_name_prefix.rstrip("."),
            include_sublayers=include_sublayers,
        ):
            dest[name] = p
        for name, b in self.named_buffers(
            prefix=structured_name_prefix.rstrip("."),
            include_sublayers=include_sublayers,
        ):
            short = name.rsplit(".", 1)[-1]
            # skip non-persistable buffers (paddle parity)
            owner = self._locate_owner(name)
            if owner is not None and short in owner._non_persistable_buffer_names:
                continue
            dest[name] = b
        return dest

    def _locate_owner(self, dotted):
        parts = dotted.split(".")
        layer = self
        for p in parts[:-1]:
            layer = layer._sub_layers.get(p)
            if layer is None:
                return None
        return layer

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing = [k for k in own if k not in state_dict]
        unexpected = [k for k in state_dict if k not in own]
        for k, t in own.items():
            if k not in state_dict:
                continue
            v = state_dict[k]
            arr = v.numpy() if isinstance(v, Tensor) else np.asarray(v)
            if tuple(arr.shape) != tuple(t.shape):
                raise ValueError(
                    f"shape mismatch for {k}: got {arr.shape}, expected {tuple(t.shape)}"
                )
            t.set_value(arr)
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    # ------------------------------------------------------ functional bridge
    def functional_state(self):
        """(params, buffers) pytrees of raw jax arrays, keyed by state name."""
        params = {k: p.value for k, p in self.named_parameters()}
        buffers = {k: b.value for k, b in self.named_buffers()}
        return params, buffers

    def load_functional_state(self, params=None, buffers=None):
        if params:
            lookup = dict(self.named_parameters())
            for k, v in params.items():
                lookup[k].value = v
        if buffers:
            lookup = dict(self.named_buffers())
            for k, v in buffers.items():
                lookup[k].value = v

    # ------------------------------------------------------------- modes
    def train(self):
        for l in self.sublayers(include_self=True):
            l.training = True
        return self

    def eval(self):
        for l in self.sublayers(include_self=True):
            l.training = False
        return self

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            d = convert_dtype(dtype)
            for p in self.parameters():
                p.value = p.value.astype(d)
            for b in self.buffers():
                if jnp.issubdtype(b.value.dtype, jnp.floating):
                    b.value = b.value.astype(d)
        if device is not None:
            import jax as _jax

            from ...core import device as device_mod
            from ...core.tensor import _parse_place

            dev = device_mod.jax_device(
                _parse_place(device) if isinstance(device, str) else device
            )
            for t in list(self.parameters()) + list(self.buffers()):
                t.value = _jax.device_put(t.value, dev)
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def half(self):
        return self.to(dtype="float16")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # ------------------------------------------------------------- hooks
    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle.id] = hook
        return handle

    def register_forward_post_hook(self, hook):
        handle = _HookHandle(self._forward_post_hooks)
        self._forward_post_hooks[handle.id] = hook
        return handle

    # ------------------------------------------------------------- call
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        if self.__dict__.get("_maybe_lazy"):
            self._check_lazy_executable()
        for hook in self._forward_pre_hooks.values():
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        scope = self.__dict__.get("_scope_name")
        if scope is None:  # a root: registered under no name
            outputs = self.forward(*inputs, **kwargs)
        else:
            # metadata of the traced operations only; eager it is inert
            with jax.named_scope(scope):
                outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            result = hook(self, inputs, outputs)
            if result is not None:
                outputs = result
        return outputs

    def _check_lazy_executable(self):
        """One-time (flag-gated) guard: a LazyGuard-built layer must be
        materialized — or have concrete values loaded — before it can
        execute; without this the failure is a raw jax TypeError deep in
        dispatch. Clears the flag once all parameters are concrete (e.g.
        after set_state_dict), so the walk never repeats."""
        from ...core import lazy as lazy_mod

        for k, p in self.named_parameters():
            if lazy_mod.is_abstract(p.value):
                raise RuntimeError(
                    f"parameter {k!r} is still abstract (built under "
                    "paddle.LazyGuard): call .materialize() or load a "
                    "checkpoint before running the layer. Abstract "
                    "networks can only be lowered (jit(...).lower), "
                    "not executed."
                )
        for l in self.sublayers(include_self=True):
            l.__dict__.pop("_maybe_lazy", None)

    def full_name(self):
        return self._full_name

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = [f"{type(self).__name__}({extra}"]
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).replace("\n", "\n  ")
            lines.append(f"  ({name}): {sub_repr}")
        return "\n".join(lines) + ")" if len(lines) > 1 else lines[0] + ")"

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()


class _HookHandle:
    _next_id = [0]

    def __init__(self, store):
        self.id = _HookHandle._next_id[0]
        _HookHandle._next_id[0] += 1
        self._store = store

    def remove(self):
        self._store.pop(self.id, None)
