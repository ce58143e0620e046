"""singa_tpu.layer — the Layer zoo (capability parity: reference
``singa.layer``; BASELINE.json:5 names the singa.model API stack whose
layers these are).

Semantics kept from the reference surface:
  * layers initialize parameters lazily on first call (shape inference
    from the input), so user code never spells input dims twice;
  * ``get_params()/set_params()`` expose trainable tensors,
    ``get_states()/set_states()`` additionally expose non-trainable
    buffers (e.g. BatchNorm running stats);
  * layers discover sublayers by attribute traversal, in creation order.

TPU-first notes: conv/pool/norm default to NHWC (the layout XLA:TPU maps
onto the MXU); the NCHW entry point is kept for ONNX/reference-style
models and transposes once at the edge.  Parameters are created in f32
and cast per-step for bf16 compute (master weights stay f32 — standard
TPU mixed-precision recipe).
"""

from __future__ import annotations

import math
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd
from . import tensor as tensor_mod
from .tensor import Tensor
from .device import Device

__all__ = [
    "Layer", "Linear", "Conv2d", "SeparableConv2d", "BatchNorm2d",
    "MaxPool2d", "AvgPool2d", "GlobalAvgPool2d", "Flatten", "ReLU",
    "Sigmoid", "Tanh", "Gelu", "SiLU", "LeakyReLU", "Softmax", "Dropout",
    "Embedding", "LayerNorm", "RMSNorm", "RNN", "LSTM",
    "MultiHeadAttention", "MoE", "MLPRouter", "Remat", "PipelineStack",
    "Sequential", "CrossEntropyLoss", "MSELoss",
]

_name_counter: Dict[str, int] = {}


def _auto_name(prefix: str) -> str:
    n = _name_counter.get(prefix, 0)
    _name_counter[prefix] = n + 1
    return f"{prefix}_{n}" if n else prefix


class Layer:
    """Base layer: lazy init + param/state introspection."""

    def __init__(self, name: Optional[str] = None):
        self.name = name or _auto_name(type(self).__name__.lower())
        self._initialized = False
        self._sublayers: "OrderedDict[str, Layer]" = OrderedDict()
        self._params: "OrderedDict[str, Tensor]" = OrderedDict()
        self._states: "OrderedDict[str, Tensor]" = OrderedDict()  # non-trainable

    # attribute hooks register sublayers / params in declaration order
    def __setattr__(self, key, value):
        if isinstance(value, Layer) and key not in ("_sublayers",):
            self.__dict__.setdefault("_sublayers", OrderedDict())[key] = value
        elif isinstance(value, (list, tuple)) and value and all(
                isinstance(v, Layer) for v in value):
            subs = self.__dict__.setdefault("_sublayers", OrderedDict())
            for i, v in enumerate(value):
                subs[f"{key}.{i}"] = v
        object.__setattr__(self, key, value)

    # -- to implement --------------------------------------------------------
    def initialize(self, *xs):
        """Create parameters from input shapes. Called once lazily."""

    def forward(self, *xs):
        raise NotImplementedError

    def __call__(self, *xs):
        if not self._initialized:
            self.initialize(*xs)
            self._initialized = True
        return self.forward(*xs)

    # -- param/state plumbing -------------------------------------------------
    def register_param(self, name: str, t: Tensor) -> Tensor:
        t.requires_grad = True
        t.stores_grad = True
        t.name = f"{self.name}.{name}"
        self._params[name] = t
        return t

    def register_state(self, name: str, t: Tensor) -> Tensor:
        t.requires_grad = False
        t.stores_grad = False
        t.name = f"{self.name}.{name}"
        self._states[name] = t
        return t

    def get_params(self, prefix: str = "") -> Dict[str, Tensor]:
        """Trainable tensors keyed by *attribute path* (e.g. "fc1.W") —
        stable across instances/processes, so checkpoints round-trip."""
        out = dict()
        for n, p in self._params.items():
            p.name = prefix + n
            out[p.name] = p
        for key, sub in self._sublayers.items():
            out.update(sub.get_params(f"{prefix}{key}."))
        return out

    def set_params(self, params: Dict[str, Tensor], prefix: str = "") -> None:
        for n, p in self._params.items():
            full = prefix + n
            if full in params:
                src = params[full]
                p.copy_from(src if isinstance(src, Tensor) else np.asarray(src))
        for key, sub in self._sublayers.items():
            sub.set_params(params, f"{prefix}{key}.")

    def get_states(self, prefix: str = "") -> Dict[str, Tensor]:
        out = dict(self.get_params(prefix))
        out.update(self._get_buffers(prefix))
        return out

    def _get_buffers(self, prefix: str = "") -> Dict[str, Tensor]:
        out = dict()
        for n, s in self._states.items():
            s.name = prefix + n
            out[s.name] = s
        for key, sub in self._sublayers.items():
            out.update(sub._get_buffers(f"{prefix}{key}."))
        return out

    # name-PRESERVING traversals: get_params/_get_buffers rewrite each
    # tensor's .name from the prefix — callers that only need the
    # tensors (e.g. Remat's per-step param threading) must not clobber
    # the executor-assigned full paths that key optimizer state
    def _param_list(self) -> List[Tensor]:
        out = list(self._params.values())
        for sub in self._sublayers.values():
            out.extend(sub._param_list())
        return out

    def _buffer_list(self) -> List[Tensor]:
        out = list(self._states.values())
        for sub in self._sublayers.values():
            out.extend(sub._buffer_list())
        return out

    def set_states(self, states: Dict[str, Tensor], prefix: str = "") -> None:
        self.set_params(states, prefix)
        for n, s in self._states.items():
            full = prefix + n
            if full in states:
                src = states[full]
                s.copy_from(src if isinstance(src, Tensor) else np.asarray(src))
        for key, sub in self._sublayers.items():
            sub.set_states(states, f"{prefix}{key}.")

    def to_device(self, dev: Device) -> "Layer":
        for p in self._params.values():
            p.to_device(dev)
        for s in self._states.values():
            s.to_device(dev)
        for sub in self._sublayers.values():
            sub.to_device(dev)
        if hasattr(self, "device"):
            self.device = dev
        return self

    def sublayers(self) -> List["Layer"]:
        return list(self._sublayers.values())

    def __repr__(self):
        return f"<{type(self).__name__} {self.name}>"


# ---------------------------------------------------------------------------
# initializers (He / Xavier, f32 master weights)
# ---------------------------------------------------------------------------

def _he_normal(shape, fan_in, dev) -> Tensor:
    std = math.sqrt(2.0 / max(1, fan_in))
    t = Tensor(shape, dev, np.float32)
    return t.gaussian(0.0, std)


def _xavier_uniform(shape, fan_in, fan_out, dev) -> Tensor:
    a = math.sqrt(6.0 / max(1, fan_in + fan_out))
    t = Tensor(shape, dev, np.float32)
    return t.uniform(-a, a)


# ---------------------------------------------------------------------------
# core layers
# ---------------------------------------------------------------------------

class Linear(Layer):
    def __init__(self, out_features: int, in_features: Optional[int] = None,
                 bias: bool = True, name=None):
        super().__init__(name)
        # reference also allows Linear(in, out) positional style
        if in_features is not None and in_features > 0 and out_features > 0 \
                and isinstance(in_features, int):
            pass
        self.out_features = out_features
        self.in_features = in_features
        self.bias = bias

    def initialize(self, x: Tensor):
        in_f = self.in_features or x.shape[-1]
        self.in_features = in_f
        dev = x.device
        self.W = self.register_param(
            "W", _xavier_uniform((in_f, self.out_features), in_f,
                                 self.out_features, dev))
        if self.bias:
            self.b = self.register_param(
                "b", Tensor((self.out_features,), dev, np.float32))

    def forward(self, x: Tensor) -> Tensor:
        w = _maybe_cast(self.W, x)
        if self.bias:
            return autograd.linear(x, w, _maybe_cast(self.b, x))
        return autograd.linear(x, w)


def _maybe_cast(p: Tensor, x: Tensor) -> Tensor:
    """Cast f32 master param to the compute dtype of x (bf16 on TPU)."""
    if p.dtype == x.dtype:
        return p
    return autograd.cast(p, x.dtype)


class Conv2d(Layer):
    """Conv layer; data_format 'NHWC' (TPU-native) or 'NCHW' (reference/ONNX)."""

    def __init__(self, out_channels: int, kernel_size, in_channels=None,
                 stride=1, padding=0, bias=True, groups=1, dilation=1,
                 data_format="NHWC", name=None):
        super().__init__(name)
        self.out_channels = out_channels
        self.in_channels = in_channels
        self.kernel_size = (kernel_size, kernel_size) if isinstance(
            kernel_size, int) else tuple(kernel_size)
        self.stride = stride
        self.padding = padding
        self.use_bias = bias
        self.groups = groups
        self.dilation = dilation
        self.data_format = data_format

    def initialize(self, x: Tensor):
        c_axis = -1 if self.data_format == "NHWC" else 1
        in_c = self.in_channels or x.shape[c_axis]
        # layout tripwire: a (N, 3, H, W) image fed to an NHWC conv is
        # silently read as a 3-pixel-tall W-channel image — shapes stay
        # consistent, loss still falls, and the network is garbage
        # (exactly what the r1-r4 ResNet bench measured).  Warn loudly
        # when the other axis looks far more channel-like.
        if len(x.shape) == 4 and self.in_channels is None:
            other = x.shape[1 if self.data_format == "NHWC" else -1]
            # the spatial dim adjacent to the claimed channel axis: if
            # the input really is the OTHER layout, the claimed-channel
            # axis is a spatial dim and (for the common square-image
            # case) equals its neighbour
            neighbor = x.shape[-2 if self.data_format == "NHWC" else 2]
            # 1/3 = gray/RGB; deeper feature maps legitimately shrink to
            # tiny spatial dims, so 2/4 etc. stay silent.  Requiring the
            # suspect axis to LOOK spatial (== its neighbour) silences
            # the false positive on genuine NHWC inputs with spatial
            # height 1 or 3 and many channels, e.g. (N, 1, W, C)
            # spectrogram rows (ADVICE r5).
            if other in (1, 3) and in_c > 8 and in_c == neighbor:
                import warnings
                warnings.warn(
                    f"Conv2d(data_format={self.data_format!r}) sees input "
                    f"shape {tuple(x.shape)}: axis {c_axis} ({in_c} "
                    f"channels) looks spatial while the other layout's "
                    f"channel axis has {other} — is the input "
                    f"{'NCHW' if self.data_format == 'NHWC' else 'NHWC'}?",
                    stacklevel=2)
        self.in_channels = in_c
        kh, kw = self.kernel_size
        fan_in = in_c * kh * kw // self.groups
        dev = x.device
        # HWIO kernel layout (XLA native)
        self.W = self.register_param(
            "W", _he_normal((kh, kw, in_c // self.groups, self.out_channels),
                            fan_in, dev))
        if self.use_bias:
            self.b = self.register_param(
                "b", Tensor((self.out_channels,), dev, np.float32))

    def forward(self, x: Tensor) -> Tensor:
        if self.data_format == "NCHW":
            x = autograd.transpose(x, (0, 2, 3, 1))
        w = _maybe_cast(self.W, x)
        b = _maybe_cast(self.b, x) if self.use_bias else None
        y = autograd.conv2d(x, w, b, self.stride, self.padding,
                            self.groups, self.dilation)
        if self.data_format == "NCHW":
            y = autograd.transpose(y, (0, 3, 1, 2))
        return y


class SeparableConv2d(Layer):
    def __init__(self, out_channels, kernel_size, in_channels=None, stride=1,
                 padding=0, bias=False, data_format="NHWC", name=None):
        super().__init__(name)
        self.depthwise = Conv2d(0, kernel_size, stride=stride, padding=padding,
                                bias=bias, data_format=data_format)
        self.pointwise = Conv2d(out_channels, 1, bias=bias,
                                data_format=data_format)
        self.data_format = data_format

    def initialize(self, x: Tensor):
        c_axis = -1 if self.data_format == "NHWC" else 1
        in_c = x.shape[c_axis]
        self.depthwise.out_channels = in_c
        self.depthwise.groups = in_c

    def forward(self, x: Tensor) -> Tensor:
        return self.pointwise(self.depthwise(x))


class BatchNorm2d(Layer):
    """BatchNorm with running stats kept as layer *states* so the compiled
    training step threads them functionally (SURVEY.md §7.3 item 2)."""

    def __init__(self, num_features=None, momentum=0.9, eps=1e-5,
                 data_format="NHWC", name=None):
        super().__init__(name)
        self.num_features = num_features
        self.momentum = momentum
        self.eps = eps
        self.data_format = data_format

    def initialize(self, x: Tensor):
        c_axis = -1 if self.data_format == "NHWC" else 1
        c = self.num_features or x.shape[c_axis]
        self.num_features = c
        dev = x.device
        self.gamma = self.register_param("gamma", Tensor((c,), dev, np.float32).set_value(1.0))
        self.beta = self.register_param("beta", Tensor((c,), dev, np.float32))
        self.running_mean = self.register_state("running_mean", Tensor((c,), dev, np.float32))
        self.running_var = self.register_state("running_var", Tensor((c,), dev, np.float32).set_value(1.0))

    def forward(self, x: Tensor) -> Tensor:
        nchw = self.data_format == "NCHW"
        if nchw:
            x = autograd.transpose(x, (0, 2, 3, 1))
        axes = (0, 1, 2) if x.ndim == 4 else (0,)
        if autograd.is_training():
            xf = autograd.cast(x, np.float32) if x.dtype != np.float32 else x
            mean = autograd.reduce_mean(xf, axes)
            var = autograd.reduce_mean(autograd.mul(xf, xf), axes) - autograd.mul(mean, mean)
            # running-stat update: functional rebinding, threaded out of jit
            m = self.momentum
            self.running_mean.data = (m * self.running_mean.data
                                      + (1 - m) * jax.lax.stop_gradient(mean.data))
            self.running_var.data = (m * self.running_var.data
                                     + (1 - m) * jax.lax.stop_gradient(var.data))
        else:
            mean, var = self.running_mean, self.running_var
        y = autograd.batchnorm(x, _maybe_cast(self.gamma, x),
                               _maybe_cast(self.beta, x),
                               _maybe_cast(mean, x), _maybe_cast(var, x),
                               self.eps)
        if nchw:
            y = autograd.transpose(y, (0, 3, 1, 2))
        return y


class MaxPool2d(Layer):
    def __init__(self, kernel_size, stride=None, padding=0,
                 data_format="NHWC", name=None):
        super().__init__(name)
        self.kernel_size, self.stride, self.padding = kernel_size, stride, padding
        self.data_format = data_format

    def forward(self, x):
        if self.data_format == "NCHW":
            x = autograd.transpose(x, (0, 2, 3, 1))
        y = autograd.max_pool2d(x, self.kernel_size, self.stride, self.padding)
        if self.data_format == "NCHW":
            y = autograd.transpose(y, (0, 3, 1, 2))
        return y


class AvgPool2d(Layer):
    def __init__(self, kernel_size, stride=None, padding=0,
                 data_format="NHWC", name=None):
        super().__init__(name)
        self.kernel_size, self.stride, self.padding = kernel_size, stride, padding
        self.data_format = data_format

    def forward(self, x):
        if self.data_format == "NCHW":
            x = autograd.transpose(x, (0, 2, 3, 1))
        y = autograd.avg_pool2d(x, self.kernel_size, self.stride, self.padding)
        if self.data_format == "NCHW":
            y = autograd.transpose(y, (0, 3, 1, 2))
        return y


class GlobalAvgPool2d(Layer):
    def __init__(self, data_format="NHWC", name=None):
        super().__init__(name)
        self.data_format = data_format

    def forward(self, x):
        axes = (1, 2) if self.data_format == "NHWC" else (2, 3)
        return autograd.reduce_mean(x, axes)


class Flatten(Layer):
    def __init__(self, start_axis=1, name=None):
        super().__init__(name)
        self.start_axis = start_axis

    def forward(self, x):
        return autograd.flatten(x, self.start_axis)


class ReLU(Layer):
    def forward(self, x):
        return autograd.relu(x)


class Sigmoid(Layer):
    def forward(self, x):
        return autograd.sigmoid(x)


class Tanh(Layer):
    def forward(self, x):
        return autograd.tanh(x)


class Gelu(Layer):
    def __init__(self, approximate: bool = True, name=None):
        super().__init__(name)
        self.approximate = approximate

    def forward(self, x):
        return autograd.gelu(x, self.approximate)


class SiLU(Layer):
    def forward(self, x):
        return autograd.silu(x)


class LeakyReLU(Layer):
    def __init__(self, slope=0.01, name=None):
        super().__init__(name)
        self.slope = slope

    def forward(self, x):
        return autograd.leakyrelu(x, self.slope)


class Softmax(Layer):
    def __init__(self, axis=-1, name=None):
        super().__init__(name)
        self.axis = axis

    def forward(self, x):
        return autograd.softmax(x, self.axis)


class Dropout(Layer):
    def __init__(self, p=0.5, name=None):
        super().__init__(name)
        self.p = p

    def forward(self, x):
        return autograd.dropout(x, self.p)


class Embedding(Layer):
    def __init__(self, vocab_size, embed_dim, name=None):
        super().__init__(name)
        self.vocab_size, self.embed_dim = vocab_size, embed_dim

    def initialize(self, ids: Tensor):
        dev = ids.device
        self.table = self.register_param(
            "table", Tensor((self.vocab_size, self.embed_dim), dev,
                            np.float32).gaussian(0.0, 0.02))

    def forward(self, ids: Tensor) -> Tensor:
        out = autograd.embedding(self.table, ids)
        # master table is f32; activations run in the device compute dtype
        # (bf16 on TPU) — cast after the gather so only B*T*D bytes move
        dev = ids.device
        dt = getattr(dev, "default_dtype", None)
        if dt is not None and np.dtype(dt) != np.dtype(np.float32):
            out = autograd.cast(out, dt)
        return out


class LayerNorm(Layer):
    def __init__(self, dim=None, eps=1e-5, name=None):
        super().__init__(name)
        self.dim, self.eps = dim, eps

    def initialize(self, x: Tensor):
        d = self.dim or x.shape[-1]
        self.dim = d
        dev = x.device
        self.gamma = self.register_param("gamma", Tensor((d,), dev, np.float32).set_value(1.0))
        self.beta = self.register_param("beta", Tensor((d,), dev, np.float32))

    def forward(self, x):
        return autograd.layernorm(x, _maybe_cast(self.gamma, x),
                                  _maybe_cast(self.beta, x), self.eps)


class RMSNorm(Layer):
    def __init__(self, dim=None, eps=1e-6, name=None):
        super().__init__(name)
        self.dim, self.eps = dim, eps

    def initialize(self, x: Tensor):
        d = self.dim or x.shape[-1]
        self.dim = d
        self.gamma = self.register_param(
            "gamma", Tensor((d,), x.device, np.float32).set_value(1.0))

    def forward(self, x):
        return autograd.rmsnorm(x, _maybe_cast(self.gamma, x), self.eps)


# ---------------------------------------------------------------------------
# recurrent layers — lax.scan over time (XLA-friendly control flow; no
# Python loops in the hot path)
# ---------------------------------------------------------------------------

class _ScanRNNOp(autograd.Operator):
    """Generic scanned RNN cell op; the cell body is a pure function so the
    whole unrolled-in-time computation lowers to one lax.scan.

    `kind`/`hidden` identify the cell for the ONNX exporter (sonnx
    emits a real LSTM/RNN node with the weight layout converted)."""

    def __init__(self, cell_fn, h0_fn, kind: str = "", hidden: int = 0):
        super().__init__()
        self.cell_fn = cell_fn
        self.h0_fn = h0_fn
        self.kind = kind
        self.hidden = hidden

    def fwd(self, x, *weights):
        # x: (B, T, D) -> scan over T
        carry0 = self.h0_fn(x)

        def step(carry, xt):
            new_carry, out = self.cell_fn(carry, xt, weights)
            return new_carry, out

        xs = jnp.swapaxes(x, 0, 1)  # (T, B, D)
        _, ys = jax.lax.scan(step, carry0, xs)
        return jnp.swapaxes(ys, 0, 1)  # (B, T, H)


class RNN(Layer):
    """Vanilla tanh RNN (reference singa.autograd RNN parity)."""

    def __init__(self, hidden_size, name=None):
        super().__init__(name)
        self.hidden_size = hidden_size

    def initialize(self, x: Tensor):
        d, h = x.shape[-1], self.hidden_size
        dev = x.device
        self.Wx = self.register_param("Wx", _xavier_uniform((d, h), d, h, dev))
        self.Wh = self.register_param("Wh", _xavier_uniform((h, h), h, h, dev))
        self.b = self.register_param("b", Tensor((h,), dev, np.float32))

    def forward(self, x: Tensor) -> Tensor:
        h = self.hidden_size

        def cell(carry, xt, weights):
            wx, wh, b = weights
            nh = jnp.tanh(xt @ wx + carry @ wh + b)
            return nh, nh

        def h0(xa):
            return jnp.zeros((xa.shape[0], h), xa.dtype)

        return _ScanRNNOp(cell, h0, "RNN", h)(x, _maybe_cast(self.Wx, x),
                                              _maybe_cast(self.Wh, x),
                                              _maybe_cast(self.b, x))


class LSTM(Layer):
    def __init__(self, hidden_size, name=None):
        super().__init__(name)
        self.hidden_size = hidden_size

    def initialize(self, x: Tensor):
        d, h = x.shape[-1], self.hidden_size
        dev = x.device
        self.Wx = self.register_param("Wx", _xavier_uniform((d, 4 * h), d, 4 * h, dev))
        self.Wh = self.register_param("Wh", _xavier_uniform((h, 4 * h), h, 4 * h, dev))
        self.b = self.register_param("b", Tensor((4 * h,), dev, np.float32))

    def forward(self, x: Tensor) -> Tensor:
        h = self.hidden_size

        def cell(carry, xt, weights):
            wx, wh, b = weights
            hp, cp = carry
            z = xt @ wx + hp @ wh + b
            i, f, g, o = jnp.split(z, 4, axis=-1)
            i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
            g = jnp.tanh(g)
            c = f * cp + i * g
            nh = o * jnp.tanh(c)
            return (nh, c), nh

        def h0(xa):
            z = jnp.zeros((xa.shape[0], h), xa.dtype)
            return (z, z)

        return _ScanRNNOp(cell, h0, "LSTM", h)(x, _maybe_cast(self.Wx, x),
                                               _maybe_cast(self.Wh, x),
                                               _maybe_cast(self.b, x))


class MultiHeadAttention(Layer):
    """Standard MHA; uses the fused attention op from singa_tpu.ops (pallas
    flash attention on TPU, reference jnp path elsewhere)."""

    def __init__(self, num_heads, embed_dim=None, causal=False, name=None):
        super().__init__(name)
        self.num_heads = num_heads
        self.embed_dim = embed_dim
        self.causal = causal

    def initialize(self, x: Tensor, *rest):
        d = self.embed_dim or x.shape[-1]
        self.embed_dim = d
        self.q_proj = Linear(d, d, bias=True)
        self.k_proj = Linear(d, d, bias=True)
        self.v_proj = Linear(d, d, bias=True)
        self.out_proj = Linear(d, d, bias=True)

    def forward(self, x: Tensor, mask: Optional[Tensor] = None,
                cache=None, pos=0):
        from .ops import attention as attn_ops
        B, T, D = x.shape
        H = self.num_heads
        hd = D // H
        q = self.q_proj(x).reshape((B, T, H, hd))
        k = self.k_proj(x).reshape((B, T, H, hd))
        v = self.v_proj(x).reshape((B, T, H, hd))
        if cache is not None:
            from .ops import kv_cache as kv_ops
            ck, cv = kv_ops.update_cache(cache[0], cache[1],
                                         k.data, v.data, pos)
            if isinstance(pos, int) and pos == 0:
                o = attn_ops.attention(q, k, v, causal=self.causal, mask=mask)
            else:
                m_arr = mask.data if isinstance(mask, Tensor) else mask
                o_arr = kv_ops.cached_sdpa(q.data, ck, cv, limit=pos + T,
                                           mask=m_arr)
                o = Tensor(data=o_arr, device=x.device, requires_grad=False)
            return self.out_proj(o.reshape((B, T, D))), (ck, cv)
        o = attn_ops.attention(q, k, v, causal=self.causal, mask=mask)
        return self.out_proj(o.reshape((B, T, D)))


class _MoEOp(autograd.Operator):
    def __init__(self, cf, top_k=1, swiglu=False, dispatch_mode="auto",
                 dropless=False, router_fn=None, n_router=1,
                 experts_held=None, sigmoid=False, route_scale=1.0,
                 groups=(1, 1)):
        super().__init__()
        self.experts_held = experts_held
        self.groups = groups
        # a sigmoid router: its correction bias is the array after the
        # router's (MoE.score_bias)
        self.sigmoid = sigmoid
        self.route_scale = route_scale
        self.cf = cf
        self.top_k = top_k
        self.swiglu = swiglu
        self.dispatch_mode = dispatch_mode
        self.dropless = dropless
        # None: the one router array is the (D, E) matrix of a linear
        # router; else the function of (rows, *router arrays) that
        # yields the logits (a router sub-module's `logits`)
        self.router_fn = router_fn
        self.n_router = n_router

    def fwd(self, xa, *ws):
        from .ops.moe import moe_forward
        rw, ws = ws[:self.n_router], ws[self.n_router:]
        bias, ws = (ws[0], ws[1:]) if self.sigmoid else (None, ws)
        wi, wo, *wg = ws
        router = rw[0] if self.router_fn is None else \
            (lambda xf: self.router_fn(xf, *rw))
        extra = dict(score_bias=bias, route_scale=self.route_scale,
                     groups=self.groups) if self.sigmoid else {}
        out, aux = moe_forward(xa, router, wi, wo, self.cf, return_aux=True,
                               top_k=self.top_k,
                               w_gate=wg[0] if self.swiglu else None,
                               dispatch_mode=self.dispatch_mode,
                               dropless=self.dropless,
                               experts_held=self.experts_held, **extra)
        return out, aux


class _RouterLogitsOp(autograd.Operator):
    def fwd(self, xa, *ws):
        return MLPRouter.logits(xa.reshape(-1, xa.shape[-1]), *ws)


class MLPRouter(Layer):
    """The router of a mixture-of-experts layer as a small MLP: the
    rows projected down to `hidden`, two gelu layers with biases at
    that width, one logit an expert (ops/moe.py::mlp_router_logits).
    Hand it to `MoE(router=)`; called on its own it yields the (N, E)
    f32 logits of its input's rows.  Its weights stay f32 masters, as a
    linear router's matrix does: routing is computed in f32."""

    def __init__(self, num_experts: int, hidden: int, name=None):
        super().__init__(name)
        self.num_experts, self.hidden = num_experts, hidden

    def initialize(self, x: Tensor):
        d, h, e = x.shape[-1], self.hidden, self.num_experts
        dev = x.device
        self.down = self.register_param(
            "down", _xavier_uniform((d, h), d, h, dev))
        self.w1 = self.register_param("w1", _xavier_uniform((h, h), h, h, dev))
        # biases start off non-zero so that the random weights of a test
        # or a benchmark run exercise them
        self.b1 = self.register_param(
            "b1", Tensor((h,), dev, np.float32).gaussian(0.0, 0.02))
        self.w2 = self.register_param("w2", _xavier_uniform((h, h), h, h, dev))
        self.b2 = self.register_param(
            "b2", Tensor((h,), dev, np.float32).gaussian(0.0, 0.02))
        self.w3 = self.register_param("w3", _xavier_uniform((h, e), h, e, dev))

    def weights(self):
        """The tensors `logits` takes after the rows."""
        return (self.down, self.w1, self.b1, self.w2, self.b2, self.w3)

    @staticmethod
    def logits(xf, *ws):
        """(N, D) rows and `weights()`' arrays -> (N, E) f32 logits."""
        from .ops.moe import mlp_router_logits
        return mlp_router_logits(xf, *ws)

    def forward(self, x: Tensor) -> Tensor:
        return _RouterLogitsOp()(x, *self.weights())


class MoE(Layer):
    """Top-`top_k` mixture-of-experts FFN (ops/moe.py — GShard/Switch
    static dispatch, or `dropless`: every assignment computed).
    Stacked expert weights carry a leading E axis; the layer declares
    SHARD_RULES sharding it over the 'expert' mesh axis (the executor
    merges sublayer rules, so models need not repeat them) — with EP
    the dispatch/combine einsums become all-to-alls.

    The router yields the (N, E) logits the gates are taken from.
    `router=None`: a linear router, the (D, E) parameter `router` of
    this layer (the path checkpoints and `models/convert.py` know).
    `router=MLPRouter(...)`: that sub-module, its parameters under
    `router.*`.  A top-1 gate is the chosen expert's softmax
    probability, not renormalised; k > 1 renormalises over the k.

    `experts_held` (dropless only): the ids of the experts whose
    weights this layer holds, as one chip's share of a layer that
    several chips divide: the router keeps its `num_experts` outputs
    and its top-k, the stacks hold `len(experts_held)` experts, and the
    output is their part of the sum (ops/moe.py::_moe_dropless).

    `score="sigmoid"` (dropless only): each expert's score is the
    sigmoid of its logit, the top-k is chosen on the score plus the
    layer's `score_bias` (E,), a correction bias that balances the load
    without an auxiliary loss, and the gates are the chosen scores
    without it, renormalised over the k, times `route_scale`
    (ops/moe.py::_sigmoid_gates).  `groups` = (`n_group`,
    `topk_group`), sigmoid only: the experts are `n_group` groups in id
    order, and the top-k is taken among the experts of the `topk_group`
    groups whose two best score + bias sum highest.

    The router's load-balance auxiliary losses accumulate across
    *training-mode* calls (eval and compile-time dry runs don't
    accumulate — an init-trace entry would leak a dead tracer);
    `pop_aux_loss()` returns their sum and resets — add it to the
    training loss once per step."""

    SHARD_RULES = [
        (r"\.(w_in|w_out|w_gate)$", ("expert", None, None)),
    ]
    # the aux-loss accumulator is a side channel: a forward replayed
    # inside a jax.checkpoint region would leak its tracer (and drop
    # the router's balance-loss gradient) — layer.Remat must bypass
    REMAT_SAFE = False

    def __init__(self, num_experts: int, ffn_dim: int,
                 capacity_factor: float = 1.25, top_k: int = 1,
                 act: str = "relu", dispatch_mode: str = "auto",
                 dropless: bool = False, router: Optional[Layer] = None,
                 experts_held: Optional[Sequence[int]] = None,
                 score: str = "softmax", route_scale: float = 1.0,
                 groups: Tuple[int, int] = (1, 1), name=None):
        super().__init__(name)
        if score not in ("softmax", "sigmoid"):
            raise ValueError(f"MoE score must be softmax or sigmoid, got "
                             f"{score!r}")
        if score == "sigmoid" and not dropless:
            raise ValueError("a sigmoid router is the dropless form's")
        n_group, topk_group = groups
        if (n_group, topk_group) != (1, 1) and (
                score != "sigmoid" or num_experts % n_group
                or not 1 <= topk_group <= n_group
                or top_k > topk_group * (num_experts // n_group)):
            raise ValueError(
                f"groups {groups} of {num_experts} experts at top {top_k}: "
                f"a sigmoid router's groups divide the experts and hold "
                f"the top-k")
        if experts_held is not None:
            experts_held = tuple(int(e) for e in experts_held)
            if not dropless:
                raise ValueError(
                    "experts_held is the dropless form's: a capacity "
                    "buffer is sized for all the experts")
            if not experts_held or len(set(experts_held)) != len(experts_held) \
                    or not all(0 <= e < num_experts for e in experts_held):
                raise ValueError(
                    f"experts_held {experts_held} is not a set of ids "
                    f"out of {num_experts} experts")
        if router is not None and router.num_experts != num_experts:
            raise ValueError(
                f"router yields {router.num_experts} logits for "
                f"{num_experts} experts")
        if not 1 <= top_k <= num_experts:
            raise ValueError(
                f"top_k={top_k} outside [1, num_experts={num_experts}]")
        if act not in ("relu", "swiglu"):
            raise ValueError(f"MoE act must be relu or swiglu, got {act!r}")
        if dispatch_mode not in ("auto", "scatter", "einsum"):
            raise ValueError(f"dispatch_mode must be auto/scatter/einsum, "
                             f"got {dispatch_mode!r}")
        self.num_experts = num_experts
        self.ffn_dim = ffn_dim
        self.capacity_factor = capacity_factor
        self.top_k = top_k
        self.act = act
        # explicit token-movement choice (ops/moe.py docstring): 'auto'
        # resolves the global mesh at trace time — pass scatter/einsum
        # to pin the form independent of when the mesh is installed
        self.dispatch_mode = dispatch_mode
        # exact top-k with every assignment computed (ops/moe.py): the
        # serving form; capacity_factor and dispatch_mode then do nothing
        self.dropless = dropless
        # the experts whose weights this layer holds, where that is a
        # share of the `num_experts` it routes over (ops/moe.py)
        self.experts_held = experts_held
        self.score, self.route_scale = score, route_scale
        self.groups = (int(n_group), int(topk_group))
        self._mlp_router = router is not None
        if router is not None:
            self.router = router
        self._aux_losses: List[Tensor] = []

    def initialize(self, x: Tensor):
        d = x.shape[-1]
        e, h = self.num_experts, self.ffn_dim
        dev = x.device
        held = e if self.experts_held is None else len(self.experts_held)
        if self._mlp_router:
            # the forward hands its weights to the fused op and never
            # calls it, so it is initialised here
            self.router.initialize(x)
            self.router._initialized = True
        else:
            self.router = self.register_param(
                "router", _xavier_uniform((d, e), d, e, dev))
        if self.score == "sigmoid":
            # drawn, not zero, so that random weights route by it: the
            # spread of a score near the k-th is a few hundredths
            self.score_bias = self.register_param(
                "score_bias", Tensor((e,), dev, np.float32).gaussian(
                    0.0, 0.05))
        self.w_in = self.register_param(
            "w_in", Tensor((held, d, h), dev, np.float32).gaussian(
                0.0, (2.0 / (d + h)) ** 0.5))
        self.w_out = self.register_param(
            "w_out", Tensor((held, h, d), dev, np.float32).gaussian(
                0.0, (2.0 / (d + h)) ** 0.5))
        if self.act == "swiglu":
            self.w_gate = self.register_param(
                "w_gate", Tensor((held, d, h), dev, np.float32).gaussian(
                    0.0, (2.0 / (d + h)) ** 0.5))

    def forward(self, x: Tensor) -> Tensor:
        # router stays f32 master: moe_forward computes routing in f32
        extra = (self.w_gate,) if self.act == "swiglu" else ()
        if self._mlp_router:
            rw, fn = self.router.weights(), self.router.logits
        else:
            rw, fn = (self.router,), None
        sigmoid = self.score == "sigmoid"
        bias = (self.score_bias,) if sigmoid else ()
        out, aux = _MoEOp(self.capacity_factor, self.top_k,
                          self.act == "swiglu", self.dispatch_mode,
                          self.dropless, fn, len(rw), self.experts_held,
                          sigmoid, self.route_scale, self.groups)(
            x, *rw, *bias, self.w_in, self.w_out, *extra)
        # accumulate only in training: eval/compile-time dry runs must
        # not leave stale entries (an init-trace tracer here would crash
        # the first real pop_aux_loss)
        if autograd.is_training():
            self._aux_losses.append(aux)
        return out

    @property
    def aux_loss(self) -> Optional[Tensor]:
        """Most recent *training* call's balance loss (eval forwards do
        not record; see pop_aux_loss for the accumulated per-step sum)."""
        return self._aux_losses[-1] if self._aux_losses else None

    def pop_aux_loss(self) -> Optional[Tensor]:
        """Sum of balance losses since the last pop; resets the store."""
        if not self._aux_losses:
            return None
        total = self._aux_losses[0]
        for a in self._aux_losses[1:]:
            total = total + a
        self._aux_losses = []
        return total


class _RematOp(autograd.Operator):
    """Runs a wrapped layer's forward as a PURE jax function under
    jax.checkpoint: the jax.vjp-derived backward then saves only the
    op's inputs and recomputes the block's internals — activation
    memory O(block inputs) instead of O(block internals).

    `extras`: trailing non-differentiable forward args (e.g. an
    attention mask) closed over by the pure fn — they become jaxpr
    constants the checkpoint keeps as residuals."""

    def __init__(self, inner, extras=()):
        super().__init__()
        self.inner = inner
        self.extras = extras

    def fwd(self, x, *param_leaves):
        inner = self.inner
        extras = self.extras
        # reserve a PRNG key for the block's internal RNG (dropout)
        # OUTSIDE the checkpoint: splits inside the checkpoint trace
        # would otherwise write checkpoint-scoped tracers into the
        # global key, crashing the next consumer after the trace closes
        blk_key = tensor_mod._next_key()

        def pure(x_a, *pl):
            ptens = inner._param_list()        # name-preserving
            saved = [(t.data, t.requires_grad, t.stores_grad)
                     for t in ptens]
            saved_key = tensor_mod._rng_key
            try:
                tensor_mod._rng_key = blk_key
                for t, a in zip(ptens, pl):
                    # requires_grad=False: inner ops run plain fwd (the
                    # outer vjp over the whole block owns the gradient)
                    t.data = a
                    t.requires_grad = False
                    t.stores_grad = False
                xt = Tensor(data=x_a, requires_grad=False)
                out = inner.forward(xt, *extras)
                return out.data
            finally:
                tensor_mod._rng_key = saved_key
                for t, (d, rg, sg) in zip(ptens, saved):
                    t.data = d
                    t.requires_grad = rg
                    t.stores_grad = sg

        return jax.checkpoint(pure)(x, *param_leaves)


class Remat(Layer):
    """Activation checkpointing: wrap a (stateless) sublayer so its
    internals are recomputed during backward instead of saved —
    `layer.Remat(block)` trades one extra forward for O(layer) less
    activation HBM, the standard deep-transformer memory lever.

    The wrapped layer must be buffer-free (e.g. no BatchNorm running
    stats: the forward runs again in backward and must be side-effect
    free); such layers fall back to the plain call with a warning.
    Parameter paths are UNCHANGED (the wrapper segment is transparent),
    so checkpoints and shard rules work identically with or without
    the wrapper."""

    def __init__(self, inner: Layer, name=None):
        super().__init__(name)
        self.inner = inner

    # parameter/state paths pass through unchanged: Remat(block) and the
    # bare block have identical checkpoints and shard-rule matches
    def get_params(self, prefix: str = "") -> Dict[str, Tensor]:
        return self.inner.get_params(prefix)

    def set_params(self, params, prefix: str = "") -> None:
        self.inner.set_params(params, prefix)

    def _get_buffers(self, prefix: str = "") -> Dict[str, Tensor]:
        return self.inner._get_buffers(prefix)

    def set_states(self, states, prefix: str = "") -> None:
        self.inner.set_states(states, prefix)

    def forward(self, x: Tensor, *rest):
        if not self.inner._initialized:
            # first call materializes params through the normal lazy
            # path (outside any checkpoint region)
            return self.inner(x, *rest)
        if not autograd.is_training():
            return self.inner(x, *rest)   # nothing to save in eval
        unsafe = [l for l in _walk_layers(self.inner)
                  if not getattr(type(l), "REMAT_SAFE", True)]
        if unsafe or self.inner._buffer_list():
            import warnings
            what = ("side-channel layers "
                    f"({', '.join(type(l).__name__ for l in unsafe)})"
                    if unsafe else "non-trainable buffers")
            warnings.warn(
                f"Remat({self.inner.name}) skipped: wrapped layer has "
                f"{what} (the forward replayed in backward must be "
                f"side-effect free)", stacklevel=2)
            return self.inner(x, *rest)
        # trailing args (attention masks, ...) thread through the
        # checkpoint as closed-over constants when non-differentiable;
        # anything gradient-carrying or structured (KV caches) bypasses
        for r in rest:
            if not (r is None or (isinstance(r, Tensor)
                                  and not r.requires_grad)):
                import warnings
                warnings.warn(
                    f"Remat({self.inner.name}) bypassed for a call with "
                    f"unsupported extra arg {type(r).__name__}",
                    stacklevel=2)
                return self.inner(x, *rest)
        return _RematOp(self.inner, tuple(rest))(
            x, *self.inner._param_list())


def _walk_layers(l):
    yield l
    for s in l._sublayers.values():
        yield from _walk_layers(s)


class _PipelineOp(autograd.Operator):
    """GPipe over the 'pipe' mesh axis, expressed as ONE global-semantics
    pure function (the TPU-native formulation — no shard_map):

      * every block's params are stacked in-graph onto a leading
        (stages, blocks_per_stage) axis and pinned to P('pipe') with a
        sharding constraint, so each pipe rank materializes only its
        stage's weights;
      * each schedule tick runs `vmap` over the stage axis (all stages
        compute concurrently on different microbatches — exactly the
        per-rank stage step of parallel/pipeline.py's shard_map gpipe);
      * the activation hand-off is `jnp.roll` along the 'pipe'-sharded
        stage axis, which GSPMD lowers to a one-hop collective-permute
        over ICI;
      * `lax.scan` drives the n_micro + S - 1 ticks, and because scan
        and roll differentiate, the jax.vjp-derived Operator backward IS
        the reverse pipeline schedule (GPipe backward) for free.

    Bubble ticks are masked to zero so they contribute nothing to
    gradients.  Block internals optionally run under jax.checkpoint
    (remat), composing PP with activation checkpointing.
    """

    def __init__(self, stack: "PipelineStack", extras=()):
        super().__init__()
        self.stack = stack
        # non-grad, batch-leading extra arrays (e.g. a (B,1,1,T) padding
        # mask): microbatched alongside x and gathered per stage per
        # tick, so masked transformer blocks pipeline too
        self.extras = tuple(extras)

    def fwd(self, x, *param_leaves):
        import jax.numpy as jnp

        from .parallel import mesh as mesh_mod

        st = self.stack
        blocks = st.inner
        L, S, M = len(blocks), st.stages, st.n_micro
        k = L // S
        template = blocks[0]
        tpl = template._param_list()
        n_per = len(tpl)
        blk_key = tensor_mod._next_key()
        mesh = mesh_mod.current_mesh()
        extras = self.extras

        def constrain(a, *axes):
            if mesh is None:
                return a
            spec = mesh_mod.P(*[ax if (ax in mesh.shape
                                       and mesh.shape[ax] > 1) else None
                                for ax in axes])
            return jax.lax.with_sharding_constraint(
                a, mesh_mod.NamedSharding(mesh, spec))

        def constrain_stacked(a, tpl_tensor):
            """Stacked (S, k, *param) weights: stage axis over 'pipe',
            trailing param dims under the model's SHARD_RULES (same TP
            layout the executor pinned on the unstacked params — no
            per-step all-gather of TP shards).  _pipe_live() guarantees
            the mesh exists with pipe == stages > 1 whenever this op
            runs."""
            from .parallel import spmd as spmd_mod
            rules = spmd_mod.current_trace_rules()
            pspec = ()
            name = getattr(tpl_tensor, "name", "") or ""
            if rules and name:
                pspec = tuple(spmd_mod.spec_for(
                    name, tuple(tpl_tensor.data.shape), rules, mesh))
            spec = mesh_mod.P("pipe", None, *pspec)
            return jax.lax.with_sharding_constraint(
                a, mesh_mod.NamedSharding(mesh, spec))

        def apply_block(leaves, h, *ex):
            saved = [(t.data, t.requires_grad, t.stores_grad) for t in tpl]
            saved_key = tensor_mod._rng_key
            try:
                tensor_mod._rng_key = blk_key
                for t, a in zip(tpl, leaves):
                    t.data = a
                    t.requires_grad = False
                    t.stores_grad = False
                out = template.forward(
                    Tensor(data=h, requires_grad=False),
                    *(Tensor(data=e, requires_grad=False) for e in ex))
                return out.data
            finally:
                tensor_mod._rng_key = saved_key
                for t, (d, rg, sg) in zip(tpl, saved):
                    t.data = d
                    t.requires_grad = rg
                    t.stores_grad = sg

        if st.remat:
            apply_block = jax.checkpoint(apply_block)

        def pure(x_a, *leaves):
            B = x_a.shape[0]
            if B % M:
                raise ValueError(
                    f"batch {B} not divisible by n_micro={M}")
            mb = B // M
            # stack blocks-major flat leaves into per-param
            # (S, k, *param_shape) arrays: stage axis sharded over
            # 'pipe', param dims under the model's TP rules
            stacked = tuple(
                constrain_stacked(
                    jnp.stack([leaves[b * n_per + j] for b in range(L)])
                    .reshape((S, k) + leaves[j].shape), tpl[j])
                for j in range(n_per))
            x_micro = x_a.reshape((M, mb) + x_a.shape[1:])
            ex_micro = tuple(e.reshape((M, mb) + e.shape[1:])
                             for e in extras)

            def stage_fn(stage_leaves, h, *ex):
                for i in range(k):
                    h = apply_block([a[i] for a in stage_leaves], h, *ex)
                return h

            vstage = jax.vmap(stage_fn,
                              in_axes=(0, 0) + (0,) * len(extras))
            act_shape = (mb,) + x_a.shape[1:]
            bufs0 = jnp.zeros((S,) + act_shape, x_a.dtype).at[0].set(
                x_micro[0])
            outs0 = jnp.zeros((M,) + act_shape, x_a.dtype)
            sidx = jnp.arange(S)
            bcast = (S,) + (1,) * len(act_shape)

            def tick(carry, t):
                bufs, outs = carry
                bufs = constrain(bufs, "pipe", "data")
                # stage s works on microbatch t-s this tick: gather its
                # slice of every extra (mask etc.)
                midx = jnp.clip(t - sidx, 0, M - 1)
                ex_s = tuple(jnp.take(em, midx, axis=0) for em in ex_micro)
                ys = vstage(stacked, bufs, *ex_s)
                live = ((t - sidx) >= 0) & ((t - sidx) < M)
                ys = jnp.where(live.reshape(bcast), ys, 0)
                oidx = t - (S - 1)
                rec = jax.lax.dynamic_update_index_in_dim(
                    outs, ys[S - 1], jnp.clip(oidx, 0, M - 1), axis=0)
                outs = jnp.where(oidx >= 0, rec, outs)
                bufs = jnp.roll(ys, 1, axis=0)
                nxt = jax.lax.dynamic_index_in_dim(
                    x_micro, jnp.clip(t + 1, 0, M - 1), axis=0,
                    keepdims=False)
                inj = jnp.where(t + 1 < M, nxt, jnp.zeros_like(nxt))
                bufs = bufs.at[0].set(inj)
                return (bufs, outs), None

            (_, outs), _ = jax.lax.scan(
                tick, (bufs0, outs0), jnp.arange(M + S - 1))
            return outs.reshape((B,) + x_a.shape[1:])

        return pure(x, *param_leaves)


class PipelineStack(Layer):
    """Pipeline-parallel stack of identical shape-preserving blocks
    (transformer blocks): the Model-API surface for the 'pipe' mesh
    axis.

    Parameter paths are IDENTICAL to a plain block list (`self.blocks =
    PipelineStack([...])` exposes "blocks.0..." exactly like
    `self.blocks = [...]`), so checkpoints round-trip between pipelined
    and sequential instantiations, and DistOpt/ZeRO-1 compose
    unchanged.

    Forward dispatch:
      * a mesh with a 'pipe' axis (>1) in training → the GPipe schedule
        (_PipelineOp), n_micro microbatches over the batch dim;
      * otherwise (no mesh, eval, KV-cached decode, lazy init) → plain
        sequential application, numerically the reference behavior.

    Constraints: len(blocks) % stages == 0, all blocks structurally
    identical, batch % n_micro == 0, blocks buffer-free (same rule as
    layer.Remat); block-internal dropout draws one shared key (Llama
    blocks carry no dropout).
    """

    def __init__(self, blocks, stages: int, n_micro: Optional[int] = None,
                 remat: bool = False, name=None):
        super().__init__(name)
        if stages < 1 or len(blocks) % stages:
            raise ValueError(
                f"{len(blocks)} blocks do not divide into {stages} stages")
        self.inner = list(blocks)
        self.stages = stages
        self.n_micro = n_micro or stages
        self.remat = remat
        # remat must survive the sequential fallback too (a user who
        # sized HBM with remat=True would otherwise OOM on a pipe-less
        # mesh).  The wrappers share the inner blocks; bypass __setattr__
        # so they are not registered as duplicate sublayers for the
        # lazy-init walk.
        self.__dict__["_seq"] = ([Remat(b) for b in self.inner] if remat
                                 else self.inner)

    # param/state paths mirror a plain list attribute ("0.", "1.", ...)
    def get_params(self, prefix: str = "") -> Dict[str, Tensor]:
        out = dict()
        for i, blk in enumerate(self.inner):
            out.update(blk.get_params(f"{prefix}{i}."))
        return out

    def set_params(self, params, prefix: str = "") -> None:
        for i, blk in enumerate(self.inner):
            blk.set_params(params, f"{prefix}{i}.")

    def _get_buffers(self, prefix: str = "") -> Dict[str, Tensor]:
        out = dict()
        for i, blk in enumerate(self.inner):
            out.update(blk._get_buffers(f"{prefix}{i}."))
        return out

    def set_states(self, states, prefix: str = "") -> None:
        for i, blk in enumerate(self.inner):
            blk.set_states(states, f"{prefix}{i}.")

    def __iter__(self):
        return iter(self.inner)

    def __len__(self):
        return len(self.inner)

    def _pipe_live(self) -> bool:
        from .parallel import mesh as mesh_mod
        m = mesh_mod.current_mesh()
        if m is None:
            return False
        pipe = m.shape.get("pipe", 0)
        if pipe == self.stages > 1:
            return True
        if pipe > 1 and pipe != self.stages:
            # a misconfigured pipe axis must not silently train
            # unpipelined with pipe-axis devices replicating work
            import warnings
            warnings.warn(
                f"PipelineStack({self.name}): mesh 'pipe' axis is "
                f"{pipe} but stages={self.stages}; running "
                "sequentially (set pipeline_stages to the mesh's pipe "
                "size)", stacklevel=3)
        return False

    def forward(self, x: Tensor, *rest) -> Tensor:
        rest = tuple(r for r in rest if r is not None)

        def sequential():
            h = x
            for blk in self._seq:
                h = blk(h, *rest) if rest else blk(h)
            return h

        ready = all(b._initialized for b in self.inner)
        if not (ready and autograd.is_training() and self._pipe_live()):
            return sequential()
        why = self._pipe_blocker(x, rest)
        if why:
            import warnings
            warnings.warn(
                f"PipelineStack({self.name}) running sequentially: {why}",
                stacklevel=2)
            return sequential()
        leaves = []
        for blk in self.inner:
            leaves.extend(blk._param_list())
        extras = tuple(r.data if isinstance(r, Tensor) else jnp.asarray(r)
                       for r in rest)
        return _PipelineOp(self, extras)(x, *leaves)

    def _pipe_blocker(self, x, rest) -> Optional[str]:
        """Reason the GPipe path cannot run (None = it can)."""
        if any(b._buffer_list() for b in self.inner):
            return ("blocks hold non-trainable buffers (the pipelined "
                    "forward must be replayable)")
        B = x.shape[0]
        if B % self.n_micro:
            return f"batch {B} not divisible by n_micro={self.n_micro}"
        for r in rest:
            if isinstance(r, Tensor) and r.requires_grad:
                return "gradient-carrying extra args are unsupported"
            shape = getattr(r, "shape", None)
            if not shape or shape[0] != B:
                return (f"extra arg must be batch-leading (got shape "
                        f"{shape}, batch {B})")
        for blk in self.inner:
            for l in _walk_layers(blk):
                if isinstance(l, Dropout) and l.p > 0:
                    return ("Dropout(p>0) inside blocks would draw "
                            "different keys than sequential execution")
                if not getattr(type(l), "REMAT_SAFE", True):
                    return (f"{type(l).__name__} layers carry a "
                            "side-channel (e.g. MoE aux losses) the "
                            "schedule's pure replay would drop")
        return None


class Sequential(Layer):
    def __init__(self, *layers, name=None):
        super().__init__(name)
        self.layers = list(layers)

    def forward(self, x):
        for l in self.layers:
            x = l(x)
        return x


# loss layers (reference exposes these as layers as well as autograd fns)
class CrossEntropyLoss(Layer):
    def forward(self, logits, target):
        return autograd.softmax_cross_entropy(logits, target)


class MSELoss(Layer):
    def forward(self, x, t):
        return autograd.mse_loss(x, t)
