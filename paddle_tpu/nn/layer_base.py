"""nn.Layer — the module/parameter container.

Reference analogue: python/paddle/fluid/dygraph/layers.py:83 (Layer,
__call__:920 with hooks, create_parameter, sublayers, state_dict) and
framework.ParamBase. Parameters are Tensors with stop_gradient=False plus
trainable metadata; buffers mirror register_buffer semantics.
"""
from __future__ import annotations

import collections
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

from ..core.dispatch import no_grad
from ..core.dtype import get_default_dtype
from ..core.tensor import Tensor
from ..profiler import trace as _trace


class HookRemoveHelper:
    def __init__(self, hooks, hook_id):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self):
        self._hooks.pop(self._hook_id, None)


_unique_id = [0]
_hook_id_counter = iter(range(1 << 62))


def _name(prefix):
    _unique_id[0] += 1
    return f"{prefix}_{_unique_id[0]}"


class Parameter(Tensor):
    """Trainable tensor (reference: framework.ParamBase / EagerParamBase)."""

    def __init__(self, value, trainable=True, name=None):
        super().__init__(value, stop_gradient=not trainable, name=name or _name("param"))
        self.is_parameter = True
        self.persistable = True

    @property
    def trainable(self):
        return not self.stop_gradient

    @trainable.setter
    def trainable(self, v):
        self.stop_gradient = not v

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


class Layer:
    """Base class for all network layers (reference: dygraph/layers.py:83)."""

    def __init__(self, name_scope=None, dtype=None):
        self.training = True
        self._dtype = dtype or get_default_dtype()
        self._parameters: "collections.OrderedDict[str, Parameter]" = collections.OrderedDict()
        self._sub_layers: "collections.OrderedDict[str, Layer]" = collections.OrderedDict()
        self._buffers: "collections.OrderedDict[str, Tensor]" = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._dtype_kept_buffer_names = set()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._casted_dtype = None  # set by amp O2 decorate / .to(dtype)
        self._full_name = name_scope or self.__class__.__name__.lower()
        # the attribute this layer is registered under in its parent (_adopt);
        # __call__ opens a jax.named_scope of that name
        self._scope_name = None

    # -- construction --------------------------------------------------------
    def create_parameter(
        self,
        shape,
        attr=None,
        dtype=None,
        is_bias=False,
        default_initializer=None,
    ) -> Parameter:
        """reference: layers.py create_parameter + LayerHelper; initializer
        defaults mirror fluid (Xavier for weights via layer classes, zeros
        for bias)."""
        from . import initializer as I

        dtype = dtype or self._dtype
        init = default_initializer
        trainable = True
        name = None
        if attr is not None and attr is not False:
            from .param_attr import ParamAttr

            if isinstance(attr, ParamAttr):
                init = attr.initializer or init
                trainable = attr.trainable
                name = attr.name
            elif isinstance(attr, I.Initializer):
                init = attr
        # set_global_initializer overrides layer defaults (but never an
        # explicit ParamAttr initializer) — reference fluid/initializer.py
        g = I._global_bias_init if is_bias else I._global_weight_init
        attr_init = init is not default_initializer or (
            attr is not None and getattr(attr, "initializer", None) is not None
        )
        if g is not None and not attr_init:
            init = g
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierNormal()
        shape = tuple(int(s) for s in shape)
        # a span a leaf: the programs its initializer builds are sited here
        with _trace.span("create_parameter", shape=shape, dtype=str(dtype)):
            return Parameter(init._generate(shape, dtype),
                             trainable=trainable, name=name)

    def add_parameter(self, name, parameter):
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        self._adopt(str(name), sublayer)
        return sublayer

    def _adopt(self, name, child):
        """Name the child's scope after the attribute it is registered
        under. A container with no forward of its own (LayerList,
        LayerDict) is never called, so its children carry its name too:
        `layers.3`."""
        if type(self).forward is Layer.forward and self._scope_name:
            name = f"{self._scope_name}.{name}"
        child._scope_name = name
        if type(child).forward is Layer.forward:
            for n, c in child._sub_layers.items():
                child._adopt(n, c)

    def register_buffer(self, name, tensor, persistable=True,
                        keep_dtype=False):
        """``keep_dtype``: ``to(dtype)`` (and so AMP-O2's ``decorate``)
        leaves the buffer in its own type."""
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        if keep_dtype:
            self._dtype_kept_buffer_names.add(name)
        return tensor

    # -- attribute magic -----------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning parameters")
            for d in (layers, buffers):
                if d is not None:
                    d.pop(name, None)
            params[name] = value
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ before assigning sublayers")
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            layers[name] = value
            self._adopt(name, value)
        elif isinstance(value, Tensor) and buffers is not None and name in buffers:
            buffers[name] = value
        else:
            for d in (params, layers, buffers):
                if d is not None:
                    d.pop(name, None)
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for dname in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(dname)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'"
        )

    def __delattr__(self, name):
        for dname in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(dname)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + list(
            self._sub_layers
        ) + list(self._buffers)

    # -- traversal -----------------------------------------------------------
    def parameters(self, include_sublayers=True) -> List[Parameter]:
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, layer_prefix, layer in self._walk(prefix, include_sublayers):
            for pname, p in layer._parameters.items():
                if p is not None and id(p) not in seen:
                    seen.add(id(p))
                    yield (f"{layer_prefix}{pname}", p)

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        for name, layer_prefix, layer in self._walk(prefix, include_sublayers):
            for bname, b in layer._buffers.items():
                if b is not None and id(b) not in seen:
                    seen.add(id(b))
                    yield (f"{layer_prefix}{bname}", b)

    def _walk(self, prefix="", include_sublayers=True):
        yield ("", prefix, self)
        if include_sublayers:
            for name, sub in self._sub_layers.items():
                if sub is None:
                    continue
                for item in sub._walk(f"{prefix}{name}.", True):
                    yield item

    def sublayers(self, include_self=False):
        out = [self] if include_self else []
        for _, sub in self.named_sublayers():
            out.append(sub)
        return out

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield (prefix.rstrip("."), self)
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            p = f"{prefix}{name}"
            yield (p, sub)
            for n2, s2 in sub.named_sublayers(prefix=p + "."):
                yield (n2, s2)

    def children(self):
        return [s for s in self._sub_layers.values() if s is not None]

    def named_children(self):
        return [(n, s) for n, s in self._sub_layers.items() if s is not None]

    def apply(self, fn):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    def full_name(self):
        return self._full_name

    # -- modes ---------------------------------------------------------------
    def train(self):
        self.training = True
        for sub in self.children():
            sub.train()
        return self

    def eval(self):
        self.training = False
        for sub in self.children():
            sub.eval()
        return self

    # -- hooks ---------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        hid = next(_hook_id_counter)
        self._forward_pre_hooks[hid] = hook
        return HookRemoveHelper(self._forward_pre_hooks, hid)

    def register_forward_post_hook(self, hook):
        hid = next(_hook_id_counter)
        self._forward_post_hooks[hid] = hook
        return HookRemoveHelper(self._forward_post_hooks, hid)

    # -- call ----------------------------------------------------------------
    def __call__(self, *inputs, **kwargs):
        """reference: layers.py:920 __call__ → _dygraph_call_func:887.

        Everything traced inside carries the layer's path in its op_name
        (`forward/gpt/layers.3/attn/...`): the name this layer is registered
        under in its parent, the class name for a root. The name stack is no
        part of any cache key, so an eager call pays only the context
        manager."""
        with jax.named_scope(self._scope_name or type(self).__name__):
            for hook in self._forward_pre_hooks.values():
                result = hook(self, inputs)
                if result is not None:
                    inputs = result if isinstance(result, tuple) else (result,)
            outputs = self.forward(*inputs, **kwargs)
            for hook in self._forward_post_hooks.values():
                result = hook(self, inputs, outputs)
                if result is not None:
                    outputs = result
            return outputs

    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    # -- state dict ----------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True, use_hook=True):
        # a compiled step may hold the authoritative (e.g. stage-stacked)
        # weights; let it materialize them into the live params first
        sync = getattr(self, "_lazy_param_sync", None)
        if sync is not None:
            sync()
        out = collections.OrderedDict() if destination is None else destination
        for name, p in self.named_parameters(include_sublayers=include_sublayers):
            out[name] = p
        seen = set()
        for _, layer_prefix, layer in self._walk("", include_sublayers):
            for bname, b in layer._buffers.items():
                if (
                    b is not None
                    and id(b) not in seen
                    # persistability is owned by the layer that registered it
                    and bname not in layer._non_persistable_buffer_names
                ):
                    seen.add(id(b))
                    out[f"{layer_prefix}{bname}"] = b
        return out

    def set_state_dict(self, state_dict, use_structured_name=True):
        """reference: layers.py set_state_dict — in-place set_value so
        optimizer references stay valid."""
        current = self.state_dict()
        missing, unexpected = [], []
        with no_grad():
            for name, tensor in current.items():
                if name in state_dict:
                    val = state_dict[name]
                    arr = val.numpy() if isinstance(val, Tensor) else np.asarray(val)
                    tensor.set_value(arr)
                else:
                    missing.append(name)
        for name in state_dict:
            if name not in current:
                unexpected.append(name)
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    # -- dtype movement ------------------------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            with no_grad():
                for p in self.parameters():
                    if p.dtype.is_floating_point:
                        p._value = p._value.astype(
                            __import__("paddle_tpu").core.dtype.to_np_dtype(dtype)
                        )
                kept = {id(b) for _, _, layer in self._walk()
                        for name, b in layer._buffers.items()
                        if name in layer._dtype_kept_buffer_names}
                for b in self.buffers():
                    if b.dtype.is_floating_point and id(b) not in kept:
                        b._value = b._value.astype(
                            __import__("paddle_tpu").core.dtype.to_np_dtype(dtype)
                        )
        return self

    def float(self):
        return self.to(dtype="float32")

    def half(self):
        return self.to(dtype="float16")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).split("\n")
            sub_repr = [sub_repr[0]] + ["  " + l for l in sub_repr[1:]]
            lines.append(f"  ({name}): " + "\n".join(sub_repr))
        main = f"{type(self).__name__}({extra}"
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"
