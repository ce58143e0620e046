"""Step-timing methodology for on-chip benchmarking.

Two measurements, different questions:

``windowed_steps`` — training throughput: windows of K back-to-back
dispatches with ONE fence at the window end, median over windows.
This is how a real training loop runs (nothing fences per step), so
per-dispatch host latency pipelines away and the number is device time.

``fenced_steps`` — per-dispatch latency diagnostic: every step fenced
individually, median.  Includes the dispatch overhead by construction;
a stall shows up as max.

Both report medians over several samples, so one slow window or step
does not move the number.

The fence is ``jax.block_until_ready``.  Checked on the v5e chip
(PR 21): a 17.6-TFLOP bf16 matmul chain returned from dispatch in
0.24 ms and from ``block_until_ready`` in 102 ms (172 TFLOP/s, under
the 197 peak), and a host fetch after it added 2 ms — it waits for the
device.
"""

from __future__ import annotations

import statistics
import time
from typing import Callable, Optional

import jax

__all__ = ["windowed_steps", "fenced_steps"]


def windowed_steps(step: Callable[[], object], *, windows: int = 6,
                   window_len: int = 8, warmup: int = 2,
                   budget_left: Optional[Callable[[], float]] = None,
                   min_budget_s: float = 30.0):
    """Median per-step seconds over `windows` windows of `window_len`
    back-to-back un-fenced steps (one fence at each window end).

    `step()` runs one training/eval step and returns the object to
    fence on (a jax array — e.g. the loss tensor's ``.data``).
    Returns ``(per_step_seconds, stats)`` where stats carries the raw
    window times and the derived per-step min/median/max in ms.

    The budget is consulted after every dispatch and at window ends —
    on a trip the current window is fenced immediately and kept only
    if no complete window exists (scaled by its actual step count).
    Honest limit: dispatches are async, so a fully-stalled window is
    only detected at its closing fence — worst case one window
    (~8 x the stall) is spent before the trip.  The median over windows
    keeps such a window out of the reported number either way."""
    out = None
    tripped = False
    for _ in range(warmup):
        out = step()
        if budget_left is not None and budget_left() < min_budget_s:
            tripped = True
            break
    if out is not None:
        jax.block_until_ready(out)
    wtimes = []
    partial = None          # (seconds, steps) of an aborted window
    done_steps = 0
    for _ in range(windows):
        # honor the budget only once at least one window exists: the
        # caller must get a number even if warmup drained the budget
        if tripped and (wtimes or partial):
            break
        t0 = time.perf_counter()
        k = 0
        for _ in range(window_len):
            out = step()
            k += 1
            if budget_left is not None and budget_left() < min_budget_s:
                tripped = True
                break
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        done_steps += k
        if k == window_len:
            wtimes.append(dt)
        else:
            partial = (dt, k)
    if not wtimes and partial is not None and partial[1] > 0:
        wtimes = [partial[0] / partial[1] * window_len]
    if not wtimes:
        raise RuntimeError("budget exhausted before any timed window")
    wtimes.sort()
    med = statistics.median(wtimes)
    stats = {
        "method": "windowed",
        "window_len": window_len,
        "windows": len(wtimes),
        "n": done_steps,
        "window_ms": [round(t * 1e3, 1) for t in wtimes],
        "min": round(wtimes[0] / window_len * 1e3, 1),
        "median": round(med / window_len * 1e3, 1),
        "max": round(wtimes[-1] / window_len * 1e3, 1),
    }
    _emit_timing_gauge("timing.windowed.step_ms", stats)
    return med / window_len, stats


def fenced_steps(step: Callable[[], object], *, steps: int = 8,
                 warmup: int = 1,
                 budget_left: Optional[Callable[[], float]] = None,
                 min_budget_s: float = 30.0):
    """Median per-step seconds with EVERY step individually fenced
    (per-dispatch latency).  Returns
    ``(per_step_seconds, stats)``."""
    out = None
    for _ in range(warmup):
        out = step()
    if out is not None:
        jax.block_until_ready(out)
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        jax.block_until_ready(step())
        times.append(time.perf_counter() - t0)
        if budget_left is not None and budget_left() < min_budget_s:
            break
    times.sort()
    stats = {
        "method": "fenced",
        "n": len(times),
        "min": round(times[0] * 1e3, 1),
        "median": round(statistics.median(times) * 1e3, 1),
        "mean": round(sum(times) / len(times) * 1e3, 1),
        "max": round(times[-1] * 1e3, 1),
    }
    _emit_timing_gauge("timing.fenced.step_ms", stats)
    return statistics.median(times), stats


def _emit_timing_gauge(name: str, stats: dict) -> None:
    """Mirror a measurement's summary into the structured telemetry
    stream (obs.events) — no-op unless a sink is enabled."""
    from ..obs import events
    events.gauge(name, stats["median"], method=stats["method"],
                 n=stats["n"], min=stats["min"], max=stats["max"])
