"""Device layer: CppCPU / TpuDevice over PJRT (via JAX), mirroring the
reference's ``singa::Device`` hierarchy (capability contract:
/root/repo/BASELINE.json:5 — "add a `singa::TpuDevice` alongside
CppCPU/CudaGPU so Tensor math dispatches to XLA").

TPU-first design notes
----------------------
The reference lineage's Device owns raw memory and an execution stream and
receives ops as closures.  On TPU the idiomatic equivalent is a PJRT
client: memory is device buffers managed by the runtime, and "streams" are
the XLA executable launch queue.  We expose the same *API shape*
(``create_device``, device-owned allocation, host<->device copy) but let
PJRT/XLA own scheduling.  The CppCPU device doubles as the debug/smoke
device (BASELINE.json:7) and can dispatch hot-path math to the native C++
kernel library in ``csrc/`` (see singa_tpu/_core).
"""

from __future__ import annotations

import os
from typing import Any, List, Optional

import jax
import numpy as np

__all__ = [
    "Device",
    "CppCPU",
    "TpuDevice",
    "Platform",
    "create_device",
    "create_cpu_device",
    "create_tpu_device",
    "get_default_device",
    "set_default_device",
    "enable_lazy_alloc",
    "on_tpu",
    "pjrt_plugin_info",
    "pjrt_native_probe",
]

# dtype aliases used across the framework (proto-enum parity kept in
# singa_tpu/proto). We use numpy dtypes as the neutral currency.
float16 = np.float16
bfloat16 = jax.numpy.bfloat16
float32 = np.float32
int32 = np.int32
int64 = np.int64
uint8 = np.uint8


def on_tpu(jax_device=None) -> bool:
    """The one platform predicate: is `jax_device` (default: JAX's first
    device) a TPU.  Kernel selection, Pallas interpret mode and the
    device layer all ask here, and nothing is caught — a backend that
    fails to initialize is an error, not "not a TPU"."""
    d = jax.devices()[0] if jax_device is None else jax_device
    return d.platform == "tpu"


class Device:
    """Base device.

    A Device owns:
      * a list of underlying ``jax.Device`` objects (1 for a single chip,
        many when the device represents a mesh slice),
      * a default floating dtype (bf16 on TPU, f32 on CPU),
      * an execution backend tag: ``"xla"`` (jnp/XLA compute) or
        ``"cpp"`` (native eager kernels from csrc/ for debug paths).
    """

    def __init__(self, name: str, jax_devices: List[Any], backend: str = "xla",
                 default_dtype=np.float32):
        self.name = name
        self.jax_devices = list(jax_devices)
        self.backend = backend
        self.default_dtype = default_dtype
        self.id = jax_devices[0].id if jax_devices else -1
        # graph/buffering flag: models flip this via Model.compile()
        self.graph_enabled = False
        self._verbosity = 0

    # -- reference-API compatibility surface ---------------------------------
    def SetRandSeed(self, seed: int) -> None:  # noqa: N802 (reference casing)
        from . import tensor as _t
        _t.set_seed(seed)

    def EnableGraph(self, enabled: bool) -> None:  # noqa: N802
        self.graph_enabled = bool(enabled)

    def SetVerbosity(self, v: int) -> None:  # noqa: N802
        self._verbosity = int(v)

    def ResetGraph(self) -> None:  # noqa: N802
        from .graph import reset_graph
        reset_graph(self)

    def Sync(self) -> None:  # noqa: N802
        """Block until all queued work on this device is complete."""
        # XLA dispatch is async; a block_until_ready on a trivial op on the
        # device flushes the queue.
        jax.block_until_ready(jax.device_put(0.0, self.jax_devices[0]))

    # -- memory ---------------------------------------------------------------
    def put(self, array) -> Any:
        """Place a host array onto this device (single-chip placement)."""
        return jax.device_put(array, self.jax_devices[0])

    def fetch(self, array) -> np.ndarray:
        """Device -> host copy."""
        return np.asarray(array)

    @property
    def is_tpu(self) -> bool:
        return on_tpu(self.jax_devices[0])

    def memory_stats(self) -> dict:
        return dict(self.jax_devices[0].memory_stats() or {})

    def __repr__(self) -> str:
        return f"<{type(self).__name__} name={self.name} ndev={len(self.jax_devices)} backend={self.backend}>"


class CppCPU(Device):
    """Host CPU device — the debug/smoke device (BASELINE.json:7).

    Math runs eagerly; the hot kernels dispatch to the native C++
    library (csrc/tensor_math_cpp.cc) BY DEFAULT — mirroring the
    reference's tensor_math_cpp dispatch table — and degrade to XLA:CPU
    when the library is unavailable or shapes/dtypes don't qualify, so
    op coverage is total either way.  use_native=False forces pure XLA.
    """

    def __init__(self, use_native: bool = True):
        # process-LOCAL devices: under multi-host (init_distributed),
        # jax.devices() is the global list and other hosts' devices are
        # not addressable for eager placement
        cpus = [d for d in jax.local_devices() if d.platform == "cpu"]
        if not cpus and _has_platform("cpu"):
            cpus = [d for d in jax.devices("cpu")
                    if d.process_index == jax.process_index()]
        if not cpus:
            cpus = [jax.local_devices()[0]]
        super().__init__("CppCPU", cpus[:1], backend="cpp" if use_native else "xla",
                         default_dtype=np.float32)
        self.use_native = use_native


class TpuDevice(Device):
    """TPU device over PJRT (libtpu), the north-star addition
    (BASELINE.json:5). ``id`` selects a local chip; math dispatches to XLA
    and runs bf16 by default to keep the MXU fed."""

    def __init__(self, id: int = 0, default_dtype=None):
        tpus = _accelerator_devices()
        if not tpus:
            raise RuntimeError(
                "No TPU/accelerator platform visible to PJRT. "
                "Use create_cpu_device() or set JAX_PLATFORMS.")
        if not 0 <= id < len(tpus):
            raise ValueError(
                f"TPU id {id} out of range: this process sees "
                f"{len(tpus)} chip(s)")
        dev = tpus[id]
        super().__init__(f"TPU:{dev.id}", [dev], backend="xla",
                         default_dtype=default_dtype or jax.numpy.bfloat16)


def _has_platform(name: str) -> bool:
    try:
        return len(jax.devices(name)) > 0
    except RuntimeError:
        return False


def _accelerator_devices():
    # process-local: a host may only place eager buffers on its own chips
    return [d for d in jax.local_devices() if d.platform not in ("cpu",)]


class Platform:
    """Static queries over available hardware (reference: singa::Platform)."""

    @staticmethod
    def GetNumGPUs() -> int:  # noqa: N802 — reference casing; counts accelerators
        return len(_accelerator_devices())

    @staticmethod
    def GetNumTPUs() -> int:  # noqa: N802
        return len(_accelerator_devices())

    @staticmethod
    def CreateTpuDevices(num: int) -> List["TpuDevice"]:  # noqa: N802
        return [TpuDevice(i) for i in range(num)]

    @staticmethod
    def DeviceQuery() -> str:  # noqa: N802
        lines = []
        for d in jax.devices():
            lines.append(f"{d.id}: platform={d.platform} kind={getattr(d, 'device_kind', '?')}")
        return "\n".join(lines)


_default_device: Optional[Device] = None


def create_cpu_device(use_native: bool = True) -> CppCPU:
    return CppCPU(use_native=use_native)


def create_tpu_device(id: int = 0) -> TpuDevice:
    return TpuDevice(id)


def create_device(kind: str = "auto", id: int = 0) -> Device:
    """The one line that changes when moving CPU -> TPU (BASELINE.json:5).

    kind: 'auto' | 'cpu' | 'cppcpu' | 'tpu' | 'gpu' ('gpu' maps to the
    accelerator for scripts written against the CUDA lineage).
    """
    kind = kind.lower()
    if kind == "auto":
        kind = "tpu" if _accelerator_devices() else "cpu"
    if kind in ("cpu", "cppcpu", "host"):
        return create_cpu_device()
    if kind in ("tpu", "gpu", "cuda", "accelerator"):
        return create_tpu_device(id)
    raise ValueError(f"unknown device kind: {kind!r}")


def get_default_device() -> Device:
    global _default_device
    if _default_device is None:
        _default_device = create_device("auto")
    return _default_device


def set_default_device(dev: Device) -> None:
    global _default_device
    _default_device = dev


def enable_lazy_alloc(flag: bool) -> None:
    """Reference-API no-op: PJRT owns allocation; kept for compatibility."""
    del flag


# ---------------------------------------------------------------------------
# native PJRT touchpoint (csrc/pjrt_device.cc) — SURVEY §7.1
# ---------------------------------------------------------------------------

def _default_plugin_path() -> Optional[str]:
    import importlib.util
    spec = importlib.util.find_spec("libtpu")
    if spec and spec.submodule_search_locations:
        p = os.path.join(list(spec.submodule_search_locations)[0],
                         "libtpu.so")
        if os.path.exists(p):
            return p
    return None


def pjrt_plugin_info(path: Optional[str] = None,
                     init: bool = True) -> dict:
    """Load a PJRT plugin through the NATIVE C++ core and return the
    C-API handshake: {path, api_struct_size, api_version: (major,
    minor), attributes: {name: value}, init_error}.

    This is the device layer's C++ entry onto the TPU runtime
    (csrc/pjrt_device.cc over the official pjrt_c_api.h).  It does NOT
    create a client.
    Raises RuntimeError if the native core or the plugin is
    unavailable."""
    import ctypes as C

    from . import _core

    l = _core.lib()
    if l is None:
        raise RuntimeError("native core unavailable (csrc build failed)")
    path = path or _default_plugin_path()
    if not path:
        raise RuntimeError("no PJRT plugin path given and libtpu not found")
    err = C.create_string_buffer(512)
    h = l.sg_pjrt_load(path.encode(), 1 if init else 0, err, 512)
    if h < 0:
        raise RuntimeError(f"PJRT plugin load failed: {err.value.decode()}")
    major, minor = C.c_int32(), C.c_int32()
    ssize = l.sg_pjrt_api_version(h, C.byref(major), C.byref(minor))
    attrs = {}
    n = l.sg_pjrt_attr_count(h)
    nb, vb = C.create_string_buffer(256), C.create_string_buffer(4096)
    for i in range(max(0, n)):
        if l.sg_pjrt_attr_get(h, i, nb, 256, vb, 4096) >= 0:
            attrs[nb.value.decode()] = vb.value.decode()
    l.sg_pjrt_init_error(h, vb, 4096)
    return {"path": path, "api_struct_size": int(ssize),
            "api_version": (major.value, minor.value),
            "attributes": attrs, "init_error": vb.value.decode(),
            "_handle": int(h)}


def pjrt_native_probe(path: Optional[str] = None) -> dict:
    """OPT-IN deep probe: create a PJRT client through the native core
    and enumerate devices (platform name, per-device description).

    WARNING: this creates a SECOND PJRT client.  A chip belongs to one
    process (and one client) at a time, so never call this in a process
    whose JAX backend already holds the chip — run it in a subprocess
    of a parent that has not touched JAX, with a timeout."""
    import ctypes as C

    from . import _core

    info = pjrt_plugin_info(path)
    l = _core.lib()
    err = C.create_string_buffer(1024)
    c = l.sg_pjrt_client_create(info["_handle"], err, 1024)
    if c < 0:
        raise RuntimeError(f"PJRT client create failed: {err.value.decode()}")
    try:
        buf = C.create_string_buffer(4096)
        l.sg_pjrt_client_platform(c, buf, 4096)
        platform = buf.value.decode()
        ndev = l.sg_pjrt_client_device_count(c)
        devices = []
        for i in range(max(0, ndev)):
            if l.sg_pjrt_device_desc(c, i, buf, 4096) == 0:
                devices.append(buf.value.decode())
        return {**{k: v for k, v in info.items() if k != "_handle"},
                "platform": platform, "num_devices": int(ndev),
                "devices": devices}
    finally:
        l.sg_pjrt_client_destroy(c)
