"""Metrics/observability (SURVEY.md §5): loss, accuracy, throughput, MFU
accounting, with an optional JSONL sink. No external deps."""

from __future__ import annotations

import json
import time
from typing import Dict, Optional

import numpy as np

__all__ = ["Accuracy", "MeanMeter", "Throughput", "MetricsLogger",
           "accuracy", "peak_flops", "peak_hbm_bw", "mfu"]


def accuracy(logits: np.ndarray, labels: np.ndarray) -> float:
    pred = np.argmax(np.asarray(logits), axis=-1)
    return float((pred == np.asarray(labels)).mean())


class Accuracy:
    def __init__(self):
        self.correct = 0
        self.total = 0

    def update(self, logits, labels) -> None:
        pred = np.argmax(np.asarray(logits), axis=-1)
        labels = np.asarray(labels)
        self.correct += int((pred == labels).sum())
        self.total += labels.size

    @property
    def value(self) -> float:
        return self.correct / max(1, self.total)


class MeanMeter:
    def __init__(self):
        self.sum = 0.0
        self.n = 0

    def update(self, v, n: int = 1) -> None:
        self.sum += float(v) * n
        self.n += n

    @property
    def value(self) -> float:
        return self.sum / max(1, self.n)


class Throughput:
    """items/sec over a sliding window."""

    def __init__(self):
        self.t0 = None
        self.items = 0

    def start(self):
        self.t0 = time.perf_counter()
        self.items = 0

    def update(self, n: int):
        if self.t0 is None:
            self.start()
        self.items += n

    @property
    def value(self) -> float:
        if self.t0 is None:
            return 0.0
        dt = time.perf_counter() - self.t0
        return self.items / max(1e-9, dt)


# Peak dense bf16 FLOP/s and HBM bytes/s per chip, for MFU and roofline
# accounting.  Source: Google Cloud TPU documentation, system
# architecture pages per generation (v5e: 197 TFLOP/s, 819 GB/s).
# Ordered most-specific-first: matched as substrings of the PJRT
# device_kind ("TPU v5 lite", "TPU v6 lite", "TPU v4", ...).  The "cpu"
# rows are nominal, so CPU smoke runs can form a ratio; they are not a
# device metric.
_PEAK_FLOPS = (
    ("v5 lite", 197e12),   # v5e
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6 lite", 918e12),   # Trillium / v6e
    ("v6e", 918e12),
    ("v6", 918e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
    ("cpu", 1e12),
)

_PEAK_BW = (
    ("v5 lite", 819e9),    # v5e
    ("v5e", 819e9),
    ("v5p", 2765e9),
    ("v6 lite", 1640e9),   # Trillium / v6e
    ("v6e", 1640e9),
    ("v6", 1640e9),
    ("v5", 2765e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
    ("cpu", 50e9),
)


def _peak_lookup(table, device_kind: Optional[str]) -> float:
    import jax
    kind = device_kind or jax.devices()[0].device_kind
    low = kind.lower()
    for k, v in table:
        if k in low:
            return v
    raise ValueError(
        f"no peak on record for device kind {kind!r}: add it to "
        f"singa_tpu/utils/metrics.py with its source")


def peak_flops(device_kind: Optional[str] = None) -> float:
    """Peak dense bf16 FLOP/s of one chip of `device_kind` (default:
    JAX's first device).  An unknown kind raises."""
    return _peak_lookup(_PEAK_FLOPS, device_kind)


def peak_hbm_bw(device_kind: Optional[str] = None) -> float:
    """Peak HBM bytes/s of one chip of `device_kind`; unknown raises."""
    return _peak_lookup(_PEAK_BW, device_kind)


def mfu(model_flops_per_step: float, step_time_s: float,
        n_chips: int = 1, device_kind: Optional[str] = None) -> float:
    """Achieved model-FLOPs utilization. model_flops must be the *model's*
    FLOPs (e.g. 6*N*T for transformers), not the compiled module's."""
    return model_flops_per_step / (step_time_s * peak_flops(device_kind) * n_chips)


class MetricsLogger:
    """JSONL sink: one dict per line.

    File I/O is unified onto ``obs.events.JsonlSink`` (same atomic-line,
    thread-safe writer the telemetry layer uses), so all JSONL emission
    in the repo shares one implementation."""

    def __init__(self, path: Optional[str] = None, echo: bool = True):
        from ..obs.events import JsonlSink
        self.path = path
        self.echo = echo
        self._sink = JsonlSink(path) if path else None

    def log(self, **kv) -> None:
        kv.setdefault("t", time.time())  # singalint: disable=SGL005 log-line timestamp correlated with obs events across files, not a duration
        payload = {k: _jsonable(v) for k, v in kv.items()}
        if self._sink:
            self._sink.emit(payload)
        if self.echo:
            print(json.dumps(payload))

    def close(self):
        if self._sink:
            self._sink.close()


def _jsonable(v):
    if isinstance(v, (np.floating, np.integer)):
        return v.item()
    if hasattr(v, "item") and getattr(v, "ndim", 1) == 0:
        return float(v)
    return v
