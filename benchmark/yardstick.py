"""The benchmark's own measuring pieces: peaks, FLOPs per token, the
windowed timer, percentiles, the compile log.

Each is a copy of something the program has, kept here because later
PRs may change the program and may not change the yardstick.
"""

from __future__ import annotations

import time

import numpy as np

# Peak dense bf16 FLOP/s and HBM bytes/s of one chip, keyed by a
# substring of JAX's `device_kind`, most specific first.  Source: Google
# Cloud TPU documentation, "TPU v5e" system architecture page (197
# TFLOP/s bf16, 819 GB/s, 16 GB HBM).  Copy of the v5e rows of
# singa_tpu/utils/metrics.py::_PEAK_FLOPS/_PEAK_BW; a copy so that a PR
# that edits the program's table cannot move an MFU.  Only kinds a cell
# has run on are listed; an unknown kind is an error, not a default.
PEAKS = (
    ("v5 lite", {"flops": 197e12, "hbm_bytes_per_s": 819e9}),
    ("v5e", {"flops": 197e12, "hbm_bytes_per_s": 819e9}),
)


def peaks(device_kind: str) -> dict:
    low = device_kind.lower()
    for key, row in PEAKS:
        if key in low:
            return row
    raise SystemExit(f"benchmark: no peak on record for device kind "
                     f"{device_kind!r}; add it to benchmark/yardstick.py "
                     f"with its source")


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """FLOPs the forward and backward passes of a dense decoder need per
    token: 6 per matmul parameter (embedding table left out: a gather)
    plus the attention products over the keys a causal, windowed query
    really attends, 12 * dim per key and layer.  No recompute.

    Modelled on singa_tpu/models/llama.py::Llama.flops_per_token, which
    adds the fused loss's recomputed lm-head (+2*dim*V) and counts all
    T keys for every causal query; both overstate what the algorithm
    needs, so the benchmark keeps its own count.  `c` holds the
    source's keys (hidden_size, ...)."""
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    kv = c["num_key_value_heads"] * (d // c["num_attention_heads"])
    per_layer = d * (2 * d + 2 * kv) + 3 * d * c["intermediate_size"]
    n_matmul = layers * per_layer + d * c["vocab_size"]
    window = c.get("sliding_window") or seq_len
    keys = np.minimum(np.arange(1, seq_len + 1), window).mean()
    return 6.0 * n_matmul + 12.0 * layers * d * float(keys)


def percentile(values, q: float) -> float:
    """The q-th percentile with linear interpolation between order
    statistics (numpy's default), over all the values given."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def timed_windows(step, seconds: float, window_len: int, on_window=None):
    """Dispatch `step()` back to back in windows of `window_len` with one
    `jax.block_until_ready` fence at each window's end, until `seconds`
    have passed.  Returns [(window seconds, [step outputs])].

    The method of singa_tpu/utils/timing.py::windowed_steps (a real
    training loop fences nothing per step, so dispatch latency pipelines
    away); a copy because that one runs a fixed number of windows and
    keeps no outputs.  `on_window(elapsed)` runs between windows,
    outside their timing."""
    import jax

    out, t_start = [], time.perf_counter()
    while time.perf_counter() - t_start < seconds:
        if on_window is not None:
            on_window(time.perf_counter() - t_start)
        t0 = time.perf_counter()
        outs = [step() for _ in range(window_len)]
        jax.block_until_ready(outs[-1])
        out.append((time.perf_counter() - t0, outs))
    return out


class CompileLog:
    """Backend-compile seconds by program and persistent-cache hits and
    misses, from jax's monitoring events.  Copy of
    chip_smoke.py::_CompileLog; printed on earlier lines, never a
    metric."""

    def __init__(self):
        import jax
        self.seconds: dict = {}
        self.hits = self.misses = 0
        self.at: list = []        # host clock at each program built or loaded
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            name = kw.get("fun_name", "?")
            self.at.append(time.perf_counter())
            self.seconds[name] = self.seconds.get(name, 0.0) + secs

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def between(self, t0: float, t1: float) -> int:
        """Programs built or loaded from the cache between two readings
        of `time.perf_counter()`: 0 inside a measured window."""
        return sum(t0 < t <= t1 for t in self.at)

    def summary(self) -> dict:
        return {"compile_s": round(sum(self.seconds.values()), 2),
                "by_program": {k: round(v, 2)
                               for k, v in self.seconds.items() if v >= 0.2},
                "cache_hits": self.hits, "cache_misses": self.misses}
