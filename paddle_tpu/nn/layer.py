"""nn.Layer — module base class (reference: ``python/paddle/nn/layer/layers.py``
— SURVEY.md §2.2: sublayers, parameters, buffers, hooks, state_dict, to, apply)."""
from __future__ import annotations

import collections
from typing import Callable, Iterator

import numpy as np
import jax.numpy as jnp

from ..framework.core import Tensor, Parameter, _auto_name
from ..framework import dtype as dtypes


class HookRemoveHelper:
    def __init__(self, hooks, hook_id):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self):
        self._hooks.pop(self._hook_id, None)


class Layer:
    def __init__(self, name_scope=None, dtype=None):
        self.training = True
        # parameters are created in paddle.get_default_dtype() (float32
        # unless set_default_dtype changed it), as the reference's layers do
        self._dtype = dtype or dtypes.get_default_dtype()
        self._full_name = name_scope or _auto_name(type(self).__name__.lower())
        self._parameters: dict[str, Parameter] = collections.OrderedDict()
        self._sub_layers: dict[str, Layer] = collections.OrderedDict()
        self._buffers: dict[str, Tensor] = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._hook_id = [0]

    # -- forward ------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            o = hook(self, inputs, outputs)
            if o is not None:
                outputs = o
        return outputs

    # -- registration -------------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call super().__init__() first")
            for d in (layers, buffers):
                d.pop(name, None) if d else None
            params[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call super().__init__() first")
            for d in (params, buffers):
                d.pop(name, None) if d else None
            layers[name] = value
            self.__dict__.pop(name, None)
        else:
            if params is not None and name in params:
                if value is None:
                    params[name] = None
                    return
                del params[name]
            if layers is not None and name in layers:
                del layers[name]
            if buffers is not None and name in buffers:
                if value is None or isinstance(value, Tensor):
                    buffers[name] = value
                    return
                del buffers[name]
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for d in ("_parameters", "_sub_layers", "_buffers"):
            dd = self.__dict__.get(d)
            if dd is not None and name in dd:
                return dd[name]
        raise AttributeError(f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for d in (self._parameters, self._sub_layers, self._buffers):
            if name in d:
                del d[name]
                return
        object.__delattr__(self, name)

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

    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        from .initializer import Constant, XavierUniform
        from ..framework.param_attr import ParamAttr
        dtype = dtype or self._dtype or "float32"
        attr = ParamAttr._to_attr(attr)
        init = None
        if attr is not None and attr.initializer is not None:
            init = attr.initializer
        elif default_initializer is not None:
            init = default_initializer
        else:
            init = Constant(0.0) if is_bias else XavierUniform()
        data = init(shape, dtype)
        p = Parameter(data, dtype=dtype,
                      name=(attr.name if attr and attr.name else None))
        p.initializer = init
        if attr is not None:
            p.optimize_attr["learning_rate"] = attr.learning_rate
            p.regularizer = attr.regularizer
            p.trainable = attr.trainable
            p.stop_gradient = not attr.trainable
            p.need_clip = attr.need_clip
        return p

    def create_tensor(self, name=None, persistable=None, dtype=None):
        import jax.numpy as jnp
        return Tensor(jnp.zeros([], dtypes.convert_dtype(dtype or "float32")), name=name)

    # -- traversal ----------------------------------------------------------
    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        memo = set()
        for name, sub, pfx in self._walk(prefix, include_sublayers):
            for pname, p in sub._parameters.items():
                if p is not None and id(p) not in memo:
                    memo.add(id(p))
                    yield (f"{pfx}{pname}", p)

    def _walk(self, prefix="", include_sublayers=True):
        yield ("", self, prefix)
        if include_sublayers:
            for name, sub in self._sub_layers.items():
                if sub is None:
                    continue
                for n2, s2, p2 in sub._walk(f"{prefix}{name}.", True):
                    yield (n2, s2, p2)

    def children(self) -> Iterator["Layer"]:
        return iter([l for l in self._sub_layers.values() if l is not None])

    def named_children(self):
        return iter([(n, l) for n, l in self._sub_layers.items() if l is not None])

    def sublayers(self, include_self=False):
        out = [self] if include_self else []
        for sub in self.children():
            out.extend(sub.sublayers(include_self=True))
        return out

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield (prefix.rstrip("."), self)
        for name, sub in self.named_children():
            p = f"{prefix}{name}"
            yield (p, sub)
            yield from sub.named_sublayers(prefix=p + ".", include_self=False)

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        memo = set()
        for name, sub, pfx in self._walk(prefix, include_sublayers):
            for bname, b in sub._buffers.items():
                if b is not None and id(b) not in memo:
                    memo.add(id(b))
                    yield (f"{pfx}{bname}", b)

    def apply(self, fn: Callable):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    def full_name(self):
        return self._full_name

    # -- train / eval -------------------------------------------------------
    def train(self):
        for l in self.sublayers(include_self=True):
            l.training = True
        return self

    def eval(self):
        for l in self.sublayers(include_self=True):
            l.training = False
        return self

    # -- state dict ---------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix,
                                             include_sublayers=include_sublayers):
            dest[name] = p
        for _, sub, pfx in self._walk(structured_name_prefix, include_sublayers):
            for bname, b in sub._buffers.items():
                if b is not None and bname not in sub._non_persistable_buffer_names:
                    dest[f"{pfx}{bname}"] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        missing, unexpected = [], []
        own = self.state_dict()
        for k, v in state_dict.items():
            if k in own:
                val = v.numpy() if isinstance(v, Tensor) else np.asarray(v)
                own[k].set_value(val.astype(own[k].numpy().dtype)
                                 if val.dtype != own[k].numpy().dtype else val)
            else:
                unexpected.append(k)
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    # -- dtype / device movement -------------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            self._cast_params(dtype)
        return self

    def astype(self, dtype):
        self._cast_params(dtype)
        return self

    def _cast_params(self, dtype, only_floating=True):
        dt = dtypes.convert_dtype(dtype)
        for p in self.parameters():
            if not only_floating or jnp.issubdtype(p.dtype, jnp.floating) \
                    or p.dtype == jnp.bfloat16:
                p._data = p._data.astype(dt)
        for b in self.buffers():
            if jnp.issubdtype(b.dtype, jnp.floating) or b.dtype == jnp.bfloat16:
                b._data = b._data.astype(dt)
        self._dtype = dtypes.dtype_name(dt)

    def float(self):
        return self.astype("float32")

    def half(self):
        return self.astype("float16")

    def bfloat16(self):
        return self.astype("bfloat16")

    # -- hooks --------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        self._hook_id[0] += 1
        self._forward_pre_hooks[self._hook_id[0]] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id[0])

    def register_forward_post_hook(self, hook):
        self._hook_id[0] += 1
        self._forward_post_hooks[self._hook_id[0]] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id[0])

    # -- misc ---------------------------------------------------------------
    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).split("\n")
            sub_repr = "\n  ".join(sub_repr)
            lines.append(f"({name}): {sub_repr}")
        body = ("\n  " + "\n  ".join(lines) + "\n") if lines else extra
        return f"{type(self).__name__}({body})"

    def extra_repr(self):
        return ""


class Sequential(Layer):
    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], (list, tuple)) and \
                layers[0] and isinstance(layers[0][0], tuple):
            for name, layer in layers[0]:
                self.add_sublayer(name, layer)
        else:
            for i, layer in enumerate(layers):
                self.add_sublayer(str(i), layer)

    def forward(self, x):
        for layer in self._sub_layers.values():
            x = layer(x)
        return x

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return Sequential(*list(self._sub_layers.values())[idx])
        return list(self._sub_layers.values())[idx]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            for i, l in enumerate(sublayers):
                self.add_sublayer(str(i), l)

    def append(self, sublayer):
        self.add_sublayer(str(len(self._sub_layers)), sublayer)
        return self

    def insert(self, index, sublayer):
        layers = list(self._sub_layers.values())
        layers.insert(index, sublayer)
        self._sub_layers.clear()
        for i, l in enumerate(layers):
            self._sub_layers[str(i)] = l

    def extend(self, sublayers):
        for l in sublayers:
            self.append(l)
        return self

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return LayerList(list(self._sub_layers.values())[idx])
        return list(self._sub_layers.values())[idx]

    def __setitem__(self, idx, layer):
        self._sub_layers[str(idx)] = layer

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())


class LayerDict(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers:
            self.update(sublayers)

    def update(self, sublayers):
        items = sublayers.items() if isinstance(sublayers, dict) else sublayers
        for k, v in items:
            self.add_sublayer(k, v)

    def __getitem__(self, key):
        return self._sub_layers[key]

    def __setitem__(self, key, layer):
        self.add_sublayer(key, layer)

    def __delitem__(self, key):
        del self._sub_layers[key]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers)

    def keys(self):
        return self._sub_layers.keys()

    def values(self):
        return self._sub_layers.values()

    def items(self):
        return self._sub_layers.items()


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def append(self, parameter):
        self.add_parameter(str(len(self._parameters)), parameter)
        return self

    def __getitem__(self, idx):
        return self._parameters[str(idx)]

    def __len__(self):
        return len(self._parameters)

    def __iter__(self):
        return iter(self._parameters.values())
