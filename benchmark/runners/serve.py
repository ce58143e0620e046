"""Serve runner: the cell's traffic through `ServeEngine`, as
chip_smoke.py::serve_phase builds it, under the arrival loop the cell
names.  Every token is stamped by the benchmark's own clock."""

from __future__ import annotations

import time

import numpy as np


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import reference
    import traffic
    import yardstick
    from singa_tpu import models, serve, tensor

    cfg, cell, shape = ctx.config, ctx.cell, ctx.shape
    tensor.set_seed(ctx.seed)
    m = models.Llama(ctx.llama)
    m.eval()
    # a short example input: jit-init traces the forward it is given
    m.compile([tensor.from_numpy(np.zeros((1, cfg["init_len"]), np.int32))],
              is_train=False, use_graph=True)
    ctx.stamp("weights made (jit-init)")
    e = cfg["engine"]
    eng = serve.ServeEngine(m, num_slots=e["num_slots"], max_len=e["max_len"],
                            block_size=e["block_size"],
                            param_dtype=jnp.dtype(e["param_dtype"]))
    ctx.stamp("engine built")
    t = cell["traffic"]
    streams = [traffic.client_stream(t, shape["vocab_size"], ctx.seed, c)
               for c in range(t["clients"])]
    loop = ctx.load_module("loops", cell["loop"])
    reqs, w0, w1, active, step_ends, snap0 = loop.drive(
        eng, streams, ctx.seconds, t["warmup_rounds"], ctx.tracer.tick)
    ctx.stamp(f"window closed; it opened at +{w0 - ctx.t0:.1f} s")
    trace = ctx.tracer.stop(ctx.dump_trace)
    snap1 = eng.metrics.snapshot()
    if eng.compiled_counts() != (1, 1):
        raise SystemExit(f"benchmark: the engine compiled "
                         f"{eng.compiled_counts()} programs, not (1, 1)")
    window_s = w1 - w0

    inside = lambda ts: w0 < ts <= w1
    tokens = sum(inside(s) for r in reqs for s in r.stamps)
    ttft = [(r.stamps[0] - r.submit) * 1e3 for r in reqs
            if r.stamps and inside(r.stamps[0])]
    itl = [(b - a) * 1e3 for r in reqs
           for a, b in zip(r.stamps, r.stamps[1:]) if inside(b)]
    ended = [r for r in reqs if r.done_at is not None and inside(r.done_at)]
    bad = [r for r in ended
           if r.handle.failed or r.handle.finish_reason != "length"]
    rejected = snap1["rejected"] - snap0["rejected"]
    prompt_tokens = sum(r.prompt_len for r in reqs
                        if r.stamps and inside(r.stamps[0]))
    hit_tokens = snap1["prefix_hit_tokens"] - snap0["prefix_hit_tokens"]
    print(f"[serve] window {window_s:.3f} s: {len(ended)} requests ended "
          f"({len(bad)} badly, {rejected} rejected), {tokens} tokens, "
          f"{snap1['steps'] - snap0['steps']} engine steps, mean active "
          f"{np.mean(active):.2f}/{e['num_slots']}, prefix hits "
          f"{hit_tokens}/{prompt_tokens} prompt tokens; TTFT n={len(ttft)} "
          f"ITL n={len(itl)}", flush=True)

    # correctness, outside the window: the engine goes first, its
    # arena and bf16 weights would not fit beside the reference's pass
    good = [r for r in ended if r not in bad]
    pick = np.random.default_rng([ctx.seed, 9]).choice(
        len(good), size=min(cfg["check"]["requests"], len(good)),
        replace=False)
    seqs = [(good[i].handle.result(), good[i].prompt_len) for i in pick]
    eng.close()
    ctx.stamp("engine drained and closed")
    del eng, loop, streams
    params = {n: p.data for n, p in m.get_params().items()}
    t_ref = time.perf_counter()
    gaps = [reference.greedy_gap(params, seq, plen, cfg["check"]["pad_to"],
                                 shape) for seq, plen in seqs]
    tol = cfg["check"]["tolerance"]
    print(f"[serve] reference check on {len(seqs)} requests: worst (best "
          f"logit - served token's logit) {gaps} (tolerance {tol}), "
          f"{time.perf_counter() - t_ref:.1f} s", flush=True)
    correct = bool(seqs) and all(np.isfinite(g) and g <= tol for g in gaps)

    end_to_end = {"serve_tokens_per_s": tokens / window_s,
                  "ttft_p95_ms": yardstick.percentile(ttft, 95),
                  "itl_p95_ms": yardstick.percentile(itl, 95),
                  "setup_s": w0 - ctx.t0}
    if not ctx.dry_run:         # no time from a CPU run is ever printed
        steps_ms = np.diff([w0] + step_ends) * 1e3
        print(f"[serve] engine step ms: median {np.median(steps_ms):.1f}, "
              f"five longest {np.sort(steps_ms)[-5:].round(1).tolist()}; "
              f"{ctx.compile_log.between(w0, w1)} programs compiled or "
              f"loaded inside the window", flush=True)
        print(f"[serve] medians: TTFT {yardstick.percentile(ttft, 50):.1f} "
              f"ms, ITL {yardstick.percentile(itl, 50):.2f} ms", flush=True)
    return {"correct": correct, "attempted": len(ended) + rejected,
            "failed": len(bad) + rejected, "end_to_end": end_to_end,
            "trace": trace, "window_s": window_s, "active": active,
            "num_slots": e["num_slots"], "prompt_tokens": prompt_tokens,
            "prefix_hit_tokens": hit_tokens, "itl_ms": itl}
