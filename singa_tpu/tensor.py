"""Tensor: n-d array with device placement and autograd hooks.

Capability parity: the reference's ``singa::Tensor`` + per-device math
dispatch tables (BASELINE.json:5 — "Tensor math dispatches to XLA instead
of tensor_math_cuda").  TPU-first design: a Tensor *wraps* an immutable
``jax.Array`` (or a tracer while a step is being captured) and re-binds it
on in-place ops — functionalization-by-rebinding, which is what lets the
imperative SINGA API trace cleanly into a single XLA module (SURVEY.md
section 7.3 item 2).

Module-level functions mirror the reference's ``singa.tensor`` namespace
(from_numpy, to_numpy, add, mul, matmul, reshape, ...).  Differentiable
math routes through singa_tpu.autograd so the tape sees it; raw
(non-differentiable) helpers operate on ``.data`` directly.
"""

from __future__ import annotations

from typing import Iterable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import device as device_mod
from .device import Device

__all__ = [
    "Tensor", "from_numpy", "to_numpy", "from_raw", "zeros", "ones",
    "zeros_like", "ones_like", "full", "arange", "eye", "gaussian",
    "uniform", "bernoulli", "set_seed", "add", "sub", "mul", "div",
    "matmul", "mult", "reshape", "transpose", "flatten", "squeeze",
    "unsqueeze", "concatenate", "stack", "split", "abs", "exp", "log",
    "sqrt", "pow", "square", "sign", "tanh", "sigmoid", "relu", "sum",
    "mean", "max", "min", "argmax", "argmin", "clip", "einsum",
    "copy_data_to_from", "default_float", "sum_all",
    "softmax", "lt", "le", "gt", "ge", "eq",
    "eltwise_mult", "axpy", "add_column", "add_row", "sum_columns",
    "sum_rows", "tensordot", "batchmatmul", "repeat", "ceil", "floor",
    "round",
]

# lazy: creating a PRNGKey initializes the JAX backend, and importing
# singa_tpu must not force that (a process that only imports the
# package must not take the chip)
_rng_key = None


def set_seed(seed: int) -> None:
    global _rng_key
    _rng_key = jax.random.PRNGKey(int(seed))


def _next_key():
    global _rng_key
    if _rng_key is None:
        _rng_key = jax.random.PRNGKey(0)
    _rng_key, sub = jax.random.split(_rng_key)
    return sub


def default_float(dev: Optional[Device]) -> np.dtype:
    return (dev or device_mod.get_default_device()).default_dtype


class Tensor:
    """SINGA-style tensor.

    Attributes mirroring the reference surface:
      * ``device``       — owning Device
      * ``requires_grad``— participates in autograd
      * ``stores_grad``  — is a leaf parameter whose grad is materialized
      * ``creator``      — the autograd Operator that produced it (tape edge)
    """

    __slots__ = ("data", "device", "requires_grad", "stores_grad",
                 "creator", "name", "_grad")
    __array_priority__ = 100  # numpy defers to us in mixed expressions

    def __init__(self, shape: Optional[Sequence[int]] = None,
                 device: Optional[Device] = None, dtype=None,
                 data=None, requires_grad: bool = True,
                 stores_grad: bool = False, creator=None,
                 name: Optional[str] = None):
        self.device = device or device_mod.get_default_device()
        if data is None:
            if shape is None:
                raise ValueError("Tensor needs shape or data")
            dtype = dtype or self.device.default_dtype
            data = jnp.zeros(tuple(shape), dtype=dtype)
        else:
            if isinstance(data, Tensor):
                data = data.data
            elif isinstance(data, np.ndarray):
                data = jnp.asarray(data, dtype=dtype) if dtype else jnp.asarray(data)
            elif not isinstance(data, jnp.ndarray) and not _is_tracer(data):
                data = jnp.asarray(data, dtype=dtype)
            if dtype is not None and data.dtype != np.dtype(dtype) and not _is_tracer(data):
                data = data.astype(dtype)
        self.data = data
        self.requires_grad = requires_grad
        self.stores_grad = stores_grad
        self.creator = creator
        self.name = name
        self._grad = None

    # -- shape/dtype ---------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self.data.shape)

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def ndim(self) -> int:
        return self.data.ndim

    @property
    def size(self) -> int:
        return int(np.prod(self.data.shape)) if self.data.shape else 1

    def nDim(self) -> int:  # noqa: N802 — reference casing
        return self.ndim

    def Size(self) -> int:  # noqa: N802
        return self.size

    @property
    def T(self) -> "Tensor":
        from . import autograd
        return autograd.transpose(self)

    @property
    def grad(self) -> Optional["Tensor"]:
        return self._grad

    @grad.setter
    def grad(self, g) -> None:
        self._grad = g

    # -- device movement / conversion ---------------------------------------
    def to_device(self, dev: Device) -> "Tensor":
        """In-place device move (reference semantics)."""
        if not _is_tracer(self.data):
            self.data = dev.put(self.data)
        self.device = dev
        return self

    def as_type(self, dtype) -> "Tensor":
        from . import autograd
        return autograd.cast(self, dtype)

    def astype(self, dtype) -> "Tensor":
        return self.as_type(dtype)

    def to_numpy(self) -> np.ndarray:
        return np.asarray(self.data)

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        """numpy interop: np.asarray(t) fetches the buffer in one
        device->host copy.  Without this, numpy falls back to
        element-wise __getitem__ — thousands of autograd slice dispatches
        for one conversion (the generate()-with-Tensor-prompt hang)."""
        if copy is False:
            raise ValueError(
                "a device-backed Tensor cannot be converted to numpy "
                "without a copy (np.asarray(..., copy=False))")
        a = np.asarray(self.data)
        if dtype is not None:
            a = a.astype(dtype, copy=False)
        if copy:
            # honor the NumPy 2 contract: copy=True must return a fresh
            # WRITABLE array (np.asarray of a jax.Array can be a
            # read-only zero-copy view)
            a = np.array(a, copy=True)
        return a

    def numpy(self) -> np.ndarray:
        return self.to_numpy()

    def item(self):
        return self.to_numpy().item()

    def clone(self) -> "Tensor":
        return Tensor(data=self.data, device=self.device,
                      requires_grad=self.requires_grad,
                      stores_grad=self.stores_grad, name=self.name)

    def detach(self) -> "Tensor":
        return Tensor(data=self.data, device=self.device,
                      requires_grad=False, stores_grad=False)

    # -- in-place fills (leaf initialization; not differentiated) ------------
    def set_value(self, x) -> "Tensor":
        self.data = jnp.full(self.shape, x, dtype=self.dtype)
        return self

    def gaussian(self, mean: float = 0.0, std: float = 1.0) -> "Tensor":
        self.data = (mean + std * jax.random.normal(
            _next_key(), self.shape, dtype=jnp.float32)).astype(self.dtype)
        return self

    def uniform(self, low: float = 0.0, high: float = 1.0) -> "Tensor":
        self.data = jax.random.uniform(
            _next_key(), self.shape, dtype=jnp.float32,
            minval=low, maxval=high).astype(self.dtype)
        return self

    def bernoulli(self, p: float) -> "Tensor":
        self.data = jax.random.bernoulli(
            _next_key(), p, self.shape).astype(self.dtype)
        return self

    def copy_from(self, src: Union["Tensor", np.ndarray]) -> "Tensor":
        src_data = src.data if isinstance(src, Tensor) else jnp.asarray(src)
        self.data = src_data.reshape(self.shape).astype(self.dtype)
        return self

    def copy_from_numpy(self, np_array: np.ndarray) -> "Tensor":
        return self.copy_from(np_array)

    # -- shape ops (differentiable, route through autograd) ------------------
    def reshape(self, shape) -> "Tensor":
        from . import autograd
        return autograd.reshape(self, shape)

    def view(self, *shape) -> "Tensor":
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = shape[0]
        return self.reshape(shape)

    def transpose(self, axes=None) -> "Tensor":
        from . import autograd
        return autograd.transpose(self, axes)

    def flatten(self, start_axis: int = 0) -> "Tensor":
        from . import autograd
        return autograd.flatten(self, start_axis)

    def squeeze(self, axis=None) -> "Tensor":
        from . import autograd
        return autograd.squeeze(self, axis)

    def sum(self, axis=None, keepdims=False) -> "Tensor":
        from . import autograd
        return autograd.reduce_sum(self, axis, keepdims)

    def mean(self, axis=None, keepdims=False) -> "Tensor":
        from . import autograd
        return autograd.reduce_mean(self, axis, keepdims)

    # -- arithmetic (differentiable) -----------------------------------------
    def __add__(self, other):
        from . import autograd
        return autograd.add(self, _wrap(other, self))

    __radd__ = __add__

    def __sub__(self, other):
        from . import autograd
        return autograd.sub(self, _wrap(other, self))

    def __rsub__(self, other):
        from . import autograd
        return autograd.sub(_wrap(other, self), self)

    def __mul__(self, other):
        from . import autograd
        return autograd.mul(self, _wrap(other, self))

    __rmul__ = __mul__

    def __truediv__(self, other):
        from . import autograd
        return autograd.div(self, _wrap(other, self))

    def __rtruediv__(self, other):
        from . import autograd
        return autograd.div(_wrap(other, self), self)

    def __matmul__(self, other):
        from . import autograd
        return autograd.matmul(self, other)

    def __pow__(self, p):
        from . import autograd
        return autograd.pow(self, p)

    def __neg__(self):
        from . import autograd
        return autograd.neg(self)

    # in-place variants rebind .data (functionalization-by-rebinding)
    def __iadd__(self, other):
        out = self.__add__(other)
        self.data, self.creator = out.data, out.creator
        return self

    def __isub__(self, other):
        out = self.__sub__(other)
        self.data, self.creator = out.data, out.creator
        return self

    def __imul__(self, other):
        out = self.__mul__(other)
        self.data, self.creator = out.data, out.creator
        return self

    def __itruediv__(self, other):
        out = self.__truediv__(other)
        self.data, self.creator = out.data, out.creator
        return self

    # comparisons: non-differentiable masks
    def __lt__(self, other):
        return _cmp(self, other, jnp.less)

    def __le__(self, other):
        return _cmp(self, other, jnp.less_equal)

    def __gt__(self, other):
        return _cmp(self, other, jnp.greater)

    def __ge__(self, other):
        return _cmp(self, other, jnp.greater_equal)

    def __getitem__(self, idx):
        from . import autograd
        return autograd.index(self, idx)

    def __len__(self) -> int:
        return self.shape[0] if self.shape else 0

    def __repr__(self) -> str:
        tag = "tracer" if _is_tracer(self.data) else "array"
        return (f"Tensor(shape={self.shape}, dtype={self.dtype}, "
                f"device={self.device.name}, {tag})")


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _wrap(x, like: Tensor) -> Tensor:
    if isinstance(x, Tensor):
        return x
    return Tensor(data=jnp.asarray(x, dtype=like.dtype), device=like.device,
                  requires_grad=False)


def _cmp(a: Tensor, b, op) -> Tensor:
    bv = b.data if isinstance(b, Tensor) else b
    return Tensor(data=op(a.data, bv).astype(a.dtype), device=a.device,
                  requires_grad=False)


# ---------------------------------------------------------------------------
# module-level constructors (singa.tensor namespace parity)
# ---------------------------------------------------------------------------

def from_numpy(np_array: np.ndarray, dev: Optional[Device] = None) -> Tensor:
    dev = dev or device_mod.get_default_device()
    arr = jnp.asarray(np_array)
    return Tensor(data=dev.put(arr), device=dev, requires_grad=False)


def to_numpy(t: Tensor) -> np.ndarray:
    return t.to_numpy()


def from_raw(jax_array, dev: Optional[Device] = None, **kw) -> Tensor:
    return Tensor(data=jax_array, device=dev or device_mod.get_default_device(), **kw)


def zeros(shape, dev=None, dtype=None) -> Tensor:
    dev = dev or device_mod.get_default_device()
    return Tensor(data=jnp.zeros(shape, dtype=dtype or dev.default_dtype), device=dev)


def ones(shape, dev=None, dtype=None) -> Tensor:
    dev = dev or device_mod.get_default_device()
    return Tensor(data=jnp.ones(shape, dtype=dtype or dev.default_dtype), device=dev)


def full(shape, value, dev=None, dtype=None) -> Tensor:
    dev = dev or device_mod.get_default_device()
    return Tensor(data=jnp.full(shape, value, dtype=dtype or dev.default_dtype), device=dev)


def zeros_like(t: Tensor) -> Tensor:
    return Tensor(data=jnp.zeros_like(t.data), device=t.device)


def ones_like(t: Tensor) -> Tensor:
    return Tensor(data=jnp.ones_like(t.data), device=t.device)


def arange(start, stop=None, step=1, dev=None, dtype=None) -> Tensor:
    dev = dev or device_mod.get_default_device()
    return Tensor(data=jnp.arange(start, stop, step, dtype=dtype), device=dev)


def eye(n, dev=None, dtype=None) -> Tensor:
    dev = dev or device_mod.get_default_device()
    return Tensor(data=jnp.eye(n, dtype=dtype or dev.default_dtype), device=dev)


def gaussian(shape, mean=0.0, std=1.0, dev=None, dtype=None) -> Tensor:
    return Tensor(shape, dev, dtype).gaussian(mean, std)


def uniform(shape, low=0.0, high=1.0, dev=None, dtype=None) -> Tensor:
    return Tensor(shape, dev, dtype).uniform(low, high)


def bernoulli(shape, p, dev=None, dtype=None) -> Tensor:
    return Tensor(shape, dev, dtype).bernoulli(p)


def copy_data_to_from(dst: Tensor, src: Tensor, size: Optional[int] = None) -> None:
    dst.copy_from(src)


# ---------------------------------------------------------------------------
# module-level math: differentiable wrappers over autograd
# ---------------------------------------------------------------------------

def _ag():
    from . import autograd
    return autograd


def add(a, b):
    return _ag().add(a, b)


def sub(a, b):
    return _ag().sub(a, b)


def mul(a, b):
    return _ag().mul(a, b)


def mult(a, b):
    """Reference semantics: `tensor.mult` is MATRIX multiplication
    (GEMM/GEMV); the elementwise product is `eltwise_mult`."""
    return _ag().matmul(a, b)


def eltwise_mult(a, b):
    return _ag().mul(a, b)


def axpy(alpha: float, x: Tensor, y: Tensor) -> Tensor:
    """y += alpha * x in the reference's in-place style (rebinds y's
    buffer; returns y).  BLAS semantics: shapes must match exactly."""
    if tuple(x.shape) != tuple(y.shape):
        raise ValueError(f"axpy shape mismatch: x {x.shape} vs y {y.shape}")
    y.data = (y.data + alpha * x.data).astype(y.dtype)
    return y


def add_column(v: Tensor, m: Tensor) -> Tensor:
    """Add column vector v to every column of matrix m (in place)."""
    if m.ndim != 2 or v.size != m.shape[0]:
        raise ValueError(
            f"add_column needs v of length rows(m): v {v.shape}, m {m.shape}")
    m.data = (m.data + v.data.reshape(-1, 1)).astype(m.dtype)
    return m


def add_row(v: Tensor, m: Tensor) -> Tensor:
    """Add row vector v to every row of matrix m (in place)."""
    if m.ndim != 2 or v.size != m.shape[1]:
        raise ValueError(
            f"add_row needs v of length cols(m): v {v.shape}, m {m.shape}")
    m.data = (m.data + v.data.reshape(1, -1)).astype(m.dtype)
    return m


def sum_columns(m: Tensor) -> Tensor:
    """Sum over columns: (r, c) -> (r,)."""
    return _ag().reduce_sum(m, axis=1)


def sum_rows(m: Tensor) -> Tensor:
    """Sum over rows: (r, c) -> (c,)."""
    return _ag().reduce_sum(m, axis=0)


def tensordot(a, b, axes=2):
    return _ag().tensordot(a, b, axes)


def batchmatmul(a, b):
    """Batched matmul over leading dims (reference name)."""
    return _ag().matmul(a, b)


def repeat(t, repeats, axis=None):
    return _ag().repeat(t, repeats, axis)


def ceil(t):
    return _ag().ceil(t)


def floor(t):
    return _ag().floor(t)


def round(t):  # noqa: A001 - reference op name
    return _ag().round(t)


def div(a, b):
    return _ag().div(a, b)


def matmul(a, b):
    return _ag().matmul(a, b)


def einsum(subscripts, *ts):
    return _ag().einsum(subscripts, *ts)


def reshape(t, shape):
    return _ag().reshape(t, shape)


def transpose(t, axes=None):
    return _ag().transpose(t, axes)


def flatten(t, start_axis=0):
    return _ag().flatten(t, start_axis)


def squeeze(t, axis=None):
    return _ag().squeeze(t, axis)


def unsqueeze(t, axis):
    return _ag().unsqueeze(t, axis)


def concatenate(ts, axis=0):
    return _ag().cat(ts, axis)


def stack(ts, axis=0):
    return _ag().stack(ts, axis)


def split(t, parts, axis=0):
    return _ag().split(t, parts, axis)


def abs(t):
    return _ag().abs(t)


def exp(t):
    return _ag().exp(t)


def log(t):
    return _ag().log(t)


def sqrt(t):
    return _ag().sqrt(t)


def square(t):
    return _ag().mul(t, t)


def pow(t, p):
    return _ag().pow(t, p)


def sign(t):
    return Tensor(data=jnp.sign(t.data), device=t.device, requires_grad=False)


def tanh(t):
    return _ag().tanh(t)


def sigmoid(t):
    return _ag().sigmoid(t)


def relu(t):
    return _ag().relu(t)


def softmax(t, axis=-1):
    return _ag().softmax(t, axis)


def sum(t, axis=None, keepdims=False):
    return _ag().reduce_sum(t, axis, keepdims)


def sum_all(t):
    return float(jnp.sum(t.data))


def mean(t, axis=None, keepdims=False):
    return _ag().reduce_mean(t, axis, keepdims)


def max(t, axis=None, keepdims=False):
    return _ag().reduce_max(t, axis, keepdims)


def min(t, axis=None, keepdims=False):
    return _ag().reduce_min(t, axis, keepdims)


def argmax(t, axis=-1):
    return Tensor(data=jnp.argmax(t.data, axis=axis), device=t.device,
                  requires_grad=False)


def argmin(t, axis=-1):
    return Tensor(data=jnp.argmin(t.data, axis=axis), device=t.device,
                  requires_grad=False)


def clip(t, lo, hi):
    return _ag().clip(t, lo, hi)


def lt(a, b):
    return a < b


def le(a, b):
    return a <= b


def gt(a, b):
    return a > b


def ge(a, b):
    return a >= b


def eq(a, b):
    bv = b.data if isinstance(b, Tensor) else b
    return Tensor(data=(a.data == bv).astype(a.dtype), device=a.device,
                  requires_grad=False)
