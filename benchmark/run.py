"""benchmark/run.py — one process, one cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in BENCHMARK.json, makes weights and inputs from
the seed, warms every shape up (set-up), measures for `--seconds`,
checks the outputs against benchmark/reference.py outside the window,
and prints one JSON line last.  `--trace 0` reports the cell's
end-to-end metrics, `--trace 1` its per-layer metrics from a profiler
trace of the window's last seconds.  No TPU, fewer chips than the cell
asks for, or a device kind without peaks: non-zero exit, no result line.
`--dry-run` rehearses the control flow on the CPU at
`LlamaConfig.tiny()`; it reports counts and `correct`, never a metric.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()        # process start, as near as Python gives it

import argparse
import importlib.util
import json
import os
import shutil
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)        # the system under test: singa_tpu

import trace_reduce  # noqa: E402  (beside this file)
import yardstick  # noqa: E402


def stamp(what: str) -> None:
    print(f"[bench] +{time.perf_counter() - T0:.1f} s: {what}", flush=True)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by name (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def overridden(base: dict, over: dict) -> dict:
    """`base` with `over`'s keys on top, nested dicts merged one level."""
    return {**base, **{k: {**base[k], **v} if isinstance(v, dict)
                       and isinstance(base.get(k), dict) else v
                       for k, v in over.items()}}


def load_cell(workload: str, dry_run: bool):
    """(BENCHMARK.json, its entry for the cell, the cell's file, the
    configuration's file), the dry-run overrides merged in."""
    bench = read_json("BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        sys.exit(f"benchmark: no workload {workload!r} in BENCHMARK.json")
    cell = read_json("benchmark", "workloads", entry["name"] + ".json")
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == entry["config"])
    config = read_json(conf_entry["file"])
    if dry_run:
        over = cell.get("dry_run", {})
        config = overridden(config, over.get("config", {}))
        cell = overridden(cell, over.get("cell", {}))
    return bench, entry, cell, config


def applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def llama_config(config: dict, dry_run: bool):
    """The program's LlamaConfig for the source's keys in `config`, and
    the shape it runs, under the source's keys again, for the reference
    and the FLOP count."""
    from singa_tpu import models
    c = models.LlamaConfig.tiny() if dry_run else models.LlamaConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        ffn_dim=config["intermediate_size"],
        max_position=config["max_position_embeddings"],
        rope_theta=config["rope_theta"],
        sliding_window=config["sliding_window"],
        eps=config["rms_norm_eps"])
    return c, {"hidden_size": c.dim, "num_hidden_layers": c.num_layers,
               "num_attention_heads": c.num_heads,
               "num_key_value_heads": c.num_kv_heads,
               "intermediate_size": c.ffn_dim, "vocab_size": c.vocab_size,
               "rope_theta": c.rope_theta, "rms_norm_eps": c.eps,
               "sliding_window": c.sliding_window}


def compile_cache(platform: str):
    """JAX's persistent cache at a fixed place inside the checkout (the
    path is part of the key), or where JAX_COMPILATION_CACHE_DIR says.
    TPU only, as singa_tpu/utils/compile_cache.py has it; every program
    is kept, however quickly it compiled, so that a second run compiles
    nothing."""
    import jax
    if platform != "tpu":
        return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class Tracer:
    """Starts the profiler `trace_seconds` before the window's end and
    stops it after; the runner calls `tick(elapsed)` between steps."""

    def __init__(self, on: bool, seconds: float, trace_seconds: float,
                 workload: str):
        self.start_at = max(0.0, seconds - trace_seconds) if on else None
        self.dir = os.path.join(ROOT, ".bench_trace", workload)
        self.running = False

    def tick(self, elapsed: float) -> None:
        if self.start_at is not None and not self.running \
                and elapsed >= self.start_at:
            import jax
            shutil.rmtree(self.dir, ignore_errors=True)
            # no Python tracer: it slows the host that the idle share
            # is about; TraceAnnotations still land in the trace
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.running = True

    def stop(self, dump: bool) -> dict:
        """The reduced trace ({} when none was taken)."""
        if not self.running:
            return {}
        import jax
        jax.profiler.stop_trace()
        self.running = False
        profile = trace_reduce.load(self.dir)
        if dump:
            trace_reduce.dump(profile)
        reduced = trace_reduce.reduce(profile)
        for name, n, total, med in trace_reduce.module_totals(reduced)[:6]:
            print(f"[trace] module {name}: x{n}, {total:.3f} s in all, "
                  f"median {med:.3f} ms", flush=True)
        shutil.rmtree(self.dir, ignore_errors=True)
        return reduced


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run", action="store_true",
                    help="CPU rehearsal at LlamaConfig.tiny(), 2 s window")
    ap.add_argument("--dump-trace", action="store_true",
                    help="print every plane and line of the trace")
    args = ap.parse_args()

    bench, entry, cell, config = load_cell(args.workload, args.dry_run)
    seconds = 2.0 if args.dry_run else (
        args.seconds if args.seconds is not None else bench["run_seconds"])

    import jax

    devs = jax.devices()
    d0 = devs[0]
    print(f"[bench] device platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    if args.dry_run:
        print("[bench] DRY RUN on the CPU at LlamaConfig.tiny(): counts and "
              "`correct` only, proves nothing about the chip", flush=True)
        peak = None
    else:
        if d0.platform != "tpu":
            sys.exit(f"benchmark: no TPU (jax resolved to {d0.platform})")
        peak = yardstick.peaks(d0.device_kind)
    if len(devs) < entry["chips"]:
        sys.exit(f"benchmark: the cell needs {entry['chips']} chips, "
                 f"jax sees {len(devs)}")
    print(f"[bench] compile cache: {compile_cache(d0.platform) or 'off'}",
          flush=True)
    log = yardstick.CompileLog()

    stamp("device reached")
    from singa_tpu import device
    device.set_default_device(device.create_cpu_device() if args.dry_run
                              else device.create_device("tpu"))
    lcfg, shape = llama_config(config, args.dry_run)
    ctx = SimpleNamespace(
        t0=T0, seed=args.seed, seconds=seconds, dry_run=args.dry_run,
        cell=cell, config=config, llama=lcfg, shape=shape, peak=peak,
        load_module=load_module, stamp=stamp, compile_log=log,
        tracer=Tracer(bool(args.trace), seconds,
                      cell.get("trace_seconds", 4), args.workload),
        dump_trace=args.dump_trace)
    run = load_module("runners", cell["runner"]).run(ctx)
    print(f"[bench] compiles: {json.dumps(log.summary())}", flush=True)

    used = devs[:entry["chips"]]
    peaks_b = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used]
    out_dev = {"platform": d0.platform, "kind": d0.device_kind,
               "count": len(devs), "memory_peak_bytes": max(peaks_b)}
    run.update(workload=args.workload, peak=peak, shape=shape,
               memory_peak_bytes=max(peaks_b))
    metrics, trace = {}, run["trace"]
    if args.trace and not args.dry_run:
        if not trace:
            sys.exit("benchmark: the trace holds no device operation")
        out_dev.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        for m in bench["per_layer"]:
            if applies(m, args.workload):
                value = load_module("layer_metrics", m["name"]).compute(run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    elif not args.dry_run:      # a CPU rehearsal never prints a metric
        for m in bench["end_to_end"]:
            if applies(m, args.workload):
                metrics[m["name"]] = {"value": run["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    line = {"correct": bool(run["correct"]), "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": out_dev}
    if trace:
        line["breakdown"] = trace_reduce.breakdown(trace)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
