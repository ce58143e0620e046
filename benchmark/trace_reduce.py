"""From a profiler trace (`.xplane.pb`) to numbers: the device's busy
time as the union of its op intervals, time by op name, duration by
module (compiled program), idle gaps by the host span under them.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from collections import defaultdict

DEVICE_PLANE = "/device:TPU:0"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def merge(intervals) -> list:
    """(start, end) intervals, sorted, with overlapping ones joined."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_seconds(intervals) -> float:
    """Total length covered by the intervals, overlaps counted once."""
    return sum(e - s for s, e in merge(intervals))


def gaps(intervals) -> list:
    """The idle (start, end) stretches between the busy intervals."""
    busy = merge(intervals)
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:])]


def load(trace_dir: str):
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(paths, key=os.path.getmtime))


def dump(profile) -> None:
    """Every plane and line with its event count: what the reduction is
    written against."""
    for plane in profile.planes:
        print(f"[trace] plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names = sorted({e.name[:80] for e in events})[:6]
            print(f"[trace]   line {line.name!r}: {len(events)} events, "
                  f"e.g. {names}")
            for e in events[:2]:
                print(f"[trace]     stats of {e.name[:60]!r}: "
                      f"{[(k, str(v)[:80]) for k, v in e.stats]}")
            for name in sorted({short_name(e.name, 240) for e in events
                                if "tpu_custom_call" in e.name}):
                print(f"[trace]     custom call: {name}")


def _spans(line) -> list:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def _events(profile, plane_name, line_name):
    for plane in profile.planes:
        if plane.name == plane_name:
            for line in plane.lines:
                if line.name == line_name:
                    return _spans(line)
    return []


def _host_spans(profile, marker="bench."):
    """Every event of the host thread that carries the benchmark's own
    `bench.*` annotations: those, and what the runtime records under
    them on that thread (dispatches, transfers to the host)."""
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            events = _spans(line)
            if any(name.startswith(marker) for name, _, _ in events):
                return events
    return []


def short_name(hlo: str, limit: int = 160) -> str:
    """An op's HLO text without its layouts, cut to `limit` characters."""
    return re.sub(r"\{[^}]*\}", "", hlo)[:limit]


def reduce(profile) -> dict:
    """{"busy_s", "window_s", "ops": {name: (seconds, count)},
    "module_ms": {name: [durations]}, "idle_by_span": {name: seconds}}
    for device 0, or {} when the trace has no device ops."""
    ops = _events(profile, DEVICE_PLANE, OPS_LINE)
    if not ops:
        return {}
    spans = [(s, e) for _, s, e in ops]
    by_op = defaultdict(lambda: [0.0, 0])
    for name, s, e in ops:
        by_op[name][0] += e - s
        by_op[name][1] += 1
    module_ms = defaultdict(list)
    for name, s, e in _events(profile, DEVICE_PLANE, MODULES_LINE):
        module_ms[name.split("(")[0]].append((e - s) * 1e3)
    host = sorted(_host_spans(profile), key=lambda h: h[1])
    idle, i, open_spans = defaultdict(float), 0, []
    for s, e in gaps(spans):               # sorted: one sweep over both
        mid = (s + e) / 2
        while i < len(host) and host[i][1] <= mid:
            open_spans.append(host[i])
            i += 1
        open_spans = [h for h in open_spans if h[2] >= mid]
        name = min(open_spans, key=lambda h: h[2] - h[1])[0] \
            if open_spans else "outside the benchmark's spans"
        idle[name] += e - s
    return {"busy_s": union_seconds(spans),
            "window_s": max(e for _, e in spans) - min(s for s, _ in spans),
            "ops": {k: tuple(v) for k, v in by_op.items()},
            "module_ms": dict(module_ms), "idle_by_span": dict(idle)}


def median_module_ms(reduced: dict, part: str):
    """Median device duration of the module whose name holds `part`."""
    hits = [v for k, vs in reduced.get("module_ms", {}).items()
            if part in k for v in vs]
    return statistics.median(hits) if hits else None


def module_totals(reduced: dict) -> list:
    """[(module, executions, total seconds, median ms)], longest first."""
    rows = [(k, len(v), sum(v) / 1e3, statistics.median(v))
            for k, v in reduced.get("module_ms", {}).items()]
    return sorted(rows, key=lambda r: -r[2])


def breakdown(reduced: dict) -> dict:
    """The contract's `breakdown`: the ten device ops that took most time
    (with their execution count) and idle seconds by host span."""
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:10]
    ops = reduced["ops"]
    return {"device_ops": [[f"x{ops[k][1]} {short_name(k)}", v]
                           for k, v in top({k: v[0] for k, v in ops.items()})],
            "idle_gaps": [[k[:160], v] for k, v in top(reduced["idle_by_span"])]}


#: substrings of the op names of the Pallas flash-attention custom calls
FLASH_OPS = ("tpu_custom_call",)


def idle_share(reduced: dict) -> float | None:
    """Share (%) of the traced span in which no op ran on device 0."""
    if not reduced:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def op_share(reduced: dict, parts) -> float | None:
    """Share (%) of the device's busy time spent in ops whose name holds
    one of `parts`."""
    if not reduced:
        return None
    hit = sum(sec for name, (sec, _) in reduced["ops"].items()
              if any(p in name for p in parts))
    return 100.0 * hit / reduced["busy_s"]
