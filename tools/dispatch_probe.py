"""Dispatch/overhead probes for a TPU backend — one CLI.

Consolidates the six r4/r5 probe scripts (dispatch_probe.py, 2, 3, 4,
5, 5b) into subcommands.  Their old findings came through an attachment
that no longer exists; rerun a subcommand on the chip before relying on
one (bench.py's windowed timing, _GenSession's scan-based generation).

  basic     dispatch floor vs scan-amortized matmuls (r4: is step time
            dominated by fixed per-dispatch overhead?)
  fence     true-fence (host fetch) timings + fake donated-param train
            step: enqueue vs completion (r4)
  overhead  separate per-dispatch / per-executed-op / per-static-op
            overheads, then the real small-llama step fenced vs
            windowed vs scan-of-8 (r5 probe 3 — the basis for the
            windowed bench methodology)
  validate  windowed methodology vs un-fakeable single-program scans
            for llama + resnet50 (r5 probe 4)
  matmul    sustained matmul rate at 4096..16384 with varied inputs
            (r5 probe 5 — defeats repeat-call memoization)
  shapes    llama-shaped matmul chains (lm-head, proj, small square)
            to localize the probe-5 square-chain anomaly (r5 probe 5b)

Usage: python tools/dispatch_probe.py <subcommand>
       nohup setsid python tools/dispatch_probe.py overhead \
           > /tmp/probe.out 2>&1 &
"""
from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def fetch(x):
    """True fence: host fetch of one scalar (block_until_ready has been
    seen returning implausibly fast for small repeat-call programs on
    this backend — probe 3/4)."""
    return np.asarray(jax.tree_util.tree_leaves(x)[0]).ravel()[0]


def med(ts):
    return statistics.median(ts)


def med_fenced(fn, n=15):
    jax.block_until_ready(fn())
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return {"med_ms": round(med(ts) * 1e3, 3),
            "min_ms": round(ts[0] * 1e3, 3),
            "max_ms": round(ts[-1] * 1e3, 3), "n": n}


def say(tag, d):
    print(f"{tag:14s} {d}", flush=True)


# ---------------------------------------------------------------------------
# basic — dispatch floor, scan amortization (was dispatch_probe.py)
# ---------------------------------------------------------------------------

def cmd_basic() -> None:
    dev = jax.devices()[0]
    print("device:", dev, flush=True)

    def timed(fn, *args, n=5):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n

    tiny = jnp.ones((8, 8), jnp.float32)
    add = jax.jit(lambda x: x + 1)
    print(f"trivial add dispatch: {timed(add, tiny, n=10)*1e3:.2f} ms",
          flush=True)

    # 2048^3 bf16 matmul: ~17.2 GFLOP -> ~0.09 ms at 197 TFLOP/s peak
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    mm = jax.jit(lambda a: a @ a)
    t_mm = timed(mm, x, n=10)
    print(f"single matmul dispatch: {t_mm*1e3:.2f} ms "
          f"({17.18/t_mm/1e3:.1f} TFLOP/s)", flush=True)

    for k in (16, 64):
        scan_mm = jax.jit(  # singalint: disable=SGL003 each scan length is a distinct program compiled and timed exactly once — the probe measures one-dispatch scan cost, cache hits are not the point
            lambda a, k=k: lax.scan(lambda c, _: (c @ c * 0 + c @ a, None),
                                    a, None, length=k)[0])
        t_scan = timed(scan_mm, x, n=3)
        per = t_scan / (2 * k)       # each iter: TWO matmuls (c@c, c@a)
        print(f"scan of {k}x2 matmuls in ONE dispatch: {t_scan*1e3:.1f} ms "
              f"total, {per*1e3:.3f} ms/matmul "
              f"({17.18/per/1e3:.1f} TFLOP/s)", flush=True)

    k = 16
    t0 = time.perf_counter()
    out = x
    for _ in range(k):
        out = mm(out)
    jax.block_until_ready(out)
    t_sep = (time.perf_counter() - t0) / k
    print(f"{k} separate matmul dispatches: {t_sep*1e3:.2f} ms each",
          flush=True)


# ---------------------------------------------------------------------------
# fence — true-fence timings, donated fake train step (was probe 2)
# ---------------------------------------------------------------------------

def cmd_fence() -> None:
    print("device:", jax.devices()[0], flush=True)
    x = jnp.ones((2048, 2048), jnp.bfloat16)

    mm = jax.jit(lambda a: (a @ a).astype(jnp.bfloat16))
    fetch(mm(x))
    t0 = time.perf_counter(); fetch(mm(x)); t1 = time.perf_counter()
    print(f"matmul, true fence: {(t1-t0)*1e3:.2f} ms", flush=True)

    k = 64
    scan_mm = jax.jit(
        lambda a: lax.scan(lambda c, _: ((c @ a).astype(jnp.bfloat16), None),
                           a, None, length=k)[0])
    fetch(scan_mm(x))
    t0 = time.perf_counter(); fetch(scan_mm(x)); t1 = time.perf_counter()
    print(f"scan of {k} matmuls, true fence: {(t1-t0)*1e3:.1f} ms total, "
          f"{(t1-t0)/k*1e3:.3f} ms/matmul", flush=True)

    # fake train step: 200 param buffers (~400 MB), donated, few matmuls
    n_p = 200
    params = [jnp.ones((512, 2048), jnp.bfloat16) for _ in range(n_p)]

    def step_fn(ps, inp):
        h = inp
        for i in range(0, 8):
            h = (h @ ps[i].T @ ps[i]).astype(jnp.bfloat16)
        loss = jnp.sum(h.astype(jnp.float32))
        new = [(p * 0.999).astype(jnp.bfloat16) for p in ps]
        return new, loss

    step = jax.jit(step_fn, donate_argnums=(0,))
    inp = jnp.ones((256, 2048), jnp.bfloat16)
    params, l = step(params, inp); fetch(l)
    for _ in range(3):
        t0 = time.perf_counter()
        params, l = step(params, inp)
        t_enq = time.perf_counter() - t0
        fetch(l)
        t_tot = time.perf_counter() - t0
        print(f"fake train step ({n_p} donated params): enqueue "
              f"{t_enq*1e3:.1f} ms, complete {t_tot*1e3:.1f} ms", flush=True)

    # same but scan 8 steps inside one dispatch
    def step8(ps, inp):
        def body(c, _):
            return step_fn(c, inp)
        return lax.scan(body, ps, None, length=8)

    jstep8 = jax.jit(step8)
    params2 = [jnp.ones((512, 2048), jnp.bfloat16) for _ in range(n_p)]
    out = jstep8(params2, inp); fetch(out[1])
    t0 = time.perf_counter()
    out = jstep8(params2, inp); fetch(out[1])
    t_tot = time.perf_counter() - t0
    print(f"scan of 8 fake train steps, ONE dispatch: {t_tot*1e3:.1f} ms "
          f"total, {t_tot/8*1e3:.1f} ms/step", flush=True)


# ---------------------------------------------------------------------------
# overhead — dispatch vs executed-op vs static-op; llama windowed (probe 3)
# ---------------------------------------------------------------------------

def cmd_overhead() -> None:
    print("device:", jax.devices()[0], flush=True)

    tiny = jnp.ones((8, 8), jnp.float32)
    add = jax.jit(lambda x: x + 1)
    say("null", med_fenced(lambda: add(tiny)))

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    mm = jax.jit(lambda a: (a @ a).astype(jnp.bfloat16))
    say("mm1", med_fenced(lambda: mm(x)))

    scan_mm = jax.jit(lambda a: lax.scan(
        lambda c, _: ((c @ a).astype(jnp.bfloat16), None),
        a, None, length=64)[0])
    d = med_fenced(lambda: scan_mm(x), n=8)
    d["per_mm_ms"] = round(d["med_ms"] / 64, 3)
    say("scan64", d)

    def unroll(a):
        c = a
        for _ in range(64):
            c = (c @ a).astype(jnp.bfloat16)
        return c
    unroll_mm = jax.jit(unroll)
    d = med_fenced(lambda: unroll_mm(x), n=8)
    d["per_mm_ms"] = round(d["med_ms"] / 64, 3)
    say("unroll64", d)

    xs = jnp.ones((256, 256), jnp.bfloat16)
    unroll_s = jax.jit(lambda a: unroll(a))
    d = med_fenced(lambda: unroll_s(xs), n=8)
    d["per_mm_ms"] = round(d["med_ms"] / 64, 3)
    say("unroll64s", d)

    # --- real model: headline config -----------------------------------
    from singa_tpu import device, models, opt, tensor

    device.set_default_device(device.create_tpu_device())
    tensor.set_seed(0)
    np.random.seed(0)
    cfg = models.LlamaConfig.small()
    cfg.fused_loss = True
    m = models.Llama(cfg)
    m.set_optimizer(opt.SGD(lr=0.01, momentum=0.9))
    ids = tensor.from_numpy(np.random.randint(
        0, cfg.vocab_size, (16, 1024)).astype(np.int32))
    t0 = time.perf_counter()
    m.compile([ids], is_train=True, use_graph=True)
    out = m.train_step(ids)
    jax.block_until_ready(out[-1].data)
    print(f"compile+first step: {time.perf_counter()-t0:.1f}s", flush=True)

    # compiled-program size: executed-op proxy
    try:
        txt = m.graph.compiled.as_text()
        n_instr = txt.count(" = ")
        n_fusion = txt.count(" fusion(")
        ent = txt.find("ENTRY")
        n_entry = txt[ent:].split("\n\n")[0].count(" = ") if ent >= 0 else -1
        print(f"hlo: total_instr={n_instr} fusions={n_fusion} "
              f"entry_instr={n_entry}", flush=True)
    except Exception as e:
        print("hlo text unavailable:", type(e).__name__, e, flush=True)

    def one():
        o = m.train_step(ids)
        return o[-1].data
    say("llama_fenced", med_fenced(one, n=15))

    def win8():
        for _ in range(8):
            o = m.train_step(ids)
        return o[-1].data
    d = med_fenced(win8, n=6)
    d["per_step_ms"] = round(d["med_ms"] / 8, 2)
    say("llama_win8", d)

    _scan_steps(m, (ids.data,), K=8, tag="llama_scan8")


def _scan_steps(m, arrays, K: int, tag: str) -> None:
    """K train steps compiled into ONE lax.scan program, true-fenced —
    the un-fakeable arbiter both `overhead` and `validate` use."""
    ex = next(iter(m._executors.values()))
    fn = ex._jitted.__wrapped__        # (params,buffers,slots,step,rng,*b)

    def multi(params, buffers, slots, step, rng, arrays):
        def body(c, _):
            p, b, s, st = c
            outs, p2, b2, s2 = fn(p, b, s, st, rng, *arrays)
            return (p2, b2, s2, st + 1), outs[-1]
        (p, b, s, st), losses = lax.scan(
            body, (params, buffers, slots, step), None, length=K)
        return losses, p, b, s

    jm = jax.jit(multi, donate_argnums=(0, 1, 2))
    params = {n: t.data for n, t in ex.param_tensors.items()}
    buffers = {n: t.data for n, t in ex.buffer_tensors.items()}
    slots = ex.slots
    step = jnp.asarray(0, jnp.int32)
    rng = jax.random.PRNGKey(0)
    t0 = time.perf_counter()
    losses, params, buffers, slots = jm(params, buffers, slots, step, rng,
                                        arrays)
    fetch(losses)
    print(f"{tag} compile+first: {time.perf_counter()-t0:.1f}s", flush=True)
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        losses, params, buffers, slots = jm(params, buffers, slots, step,
                                            rng, arrays)
        fetch(losses)
        ts.append(time.perf_counter() - t0)
    print(f"{tag}    med {med(ts)*1e3:.1f} ms total, "
          f"{med(ts)/K*1e3:.2f} ms/step  (calls "
          f"{[round(t*1e3) for t in sorted(ts)]}) "
          f"loss[0]={float(losses[0]):.4f} loss[-1]={float(losses[-1]):.4f}",
          flush=True)


# ---------------------------------------------------------------------------
# validate — windowed methodology vs single-program scans (was probe 4)
# ---------------------------------------------------------------------------

def _time_model(name, m, batch, K=16, reps=6):
    def one():
        return m.train_step(*batch)[-1].data

    fetch(one())     # warmup: compiled + steady

    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(one())
        ts.append(time.perf_counter() - t0)
    print(f"{name} fenced_block : {med(ts)*1e3:8.1f} ms/step "
          f"(min {min(ts)*1e3:.1f} max {max(ts)*1e3:.1f})", flush=True)

    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fetch(one())
        ts.append(time.perf_counter() - t0)
    print(f"{name} fenced_fetch : {med(ts)*1e3:8.1f} ms/step "
          f"(min {min(ts)*1e3:.1f} max {max(ts)*1e3:.1f})", flush=True)

    for fname, fence in (("win8_block", jax.block_until_ready),
                         ("win8_fetch", fetch)):
        ts = []
        for _ in range(4):
            t0 = time.perf_counter()
            for _ in range(8):
                out = one()
            fence(out)
            ts.append(time.perf_counter() - t0)
        print(f"{name} {fname:12s} : {med(ts)/8*1e3:8.1f} ms/step "
              f"(windows {[round(t*1e3) for t in sorted(ts)]})", flush=True)

    _scan_steps(m, tuple(b.data for b in batch), K=K, tag=f"{name} scan{K}")


def cmd_validate() -> None:
    print("device:", jax.devices()[0], flush=True)
    from singa_tpu import device, models, opt, tensor

    device.set_default_device(device.create_tpu_device())

    # --- llama headline shape ---
    tensor.set_seed(0)
    np.random.seed(0)
    cfg = models.LlamaConfig.small()
    cfg.fused_loss = True
    m = models.Llama(cfg)
    m.set_optimizer(opt.SGD(lr=0.01, momentum=0.9))
    ids = tensor.from_numpy(np.random.randint(
        0, cfg.vocab_size, (16, 1024)).astype(np.int32))
    t0 = time.perf_counter()
    m.compile([ids], is_train=True, use_graph=True)
    fetch(m.train_step(ids)[-1].data)
    print(f"llama compile: {time.perf_counter()-t0:.1f}s", flush=True)
    _time_model("llama", m, (ids,), K=16)

    # --- resnet50 bench shape ---
    tensor.set_seed(0)
    np.random.seed(0)
    r = models.resnet50(num_classes=1000, cifar_stem=False)
    r.set_optimizer(opt.SGD(lr=0.1, momentum=0.9, weight_decay=1e-4))
    # NHWC — the zoo's layout (the NCHW feed here was the r1-r4 bug)
    x = tensor.from_numpy(np.random.randn(1536, 224, 224, 3)
                          .astype(np.float32))
    y = tensor.from_numpy(np.random.randint(0, 10, (1536,)).astype(np.int32))
    t0 = time.perf_counter()
    r.compile([x], is_train=True, use_graph=True)
    fetch(r.train_step(x, y)[-1].data)
    print(f"resnet compile: {time.perf_counter()-t0:.1f}s", flush=True)
    _time_model("resnet", r, (x, y), K=8)


# ---------------------------------------------------------------------------
# matmul — sustained rate, inputs varied across calls (was probe 5)
# ---------------------------------------------------------------------------

def _bench_rotating(tag, f, xs, flops, reps=6):
    fetch(f(xs[0]))
    ts = []
    for i in range(reps):
        x = xs[i % len(xs)]
        t0 = time.perf_counter()
        fetch(f(x))
        ts.append(time.perf_counter() - t0)
    dt = med(ts)
    print(f"{tag:16s} {dt*1e3:9.2f} ms  {flops/dt/1e12:7.1f} TFLOP/s "
          f"(min {min(ts)*1e3:.2f} max {max(ts)*1e3:.2f})", flush=True)


def cmd_matmul() -> None:
    print("device:", jax.devices()[0], flush=True)

    def mk(n, k=3):
        rng = np.random.RandomState(0)
        base = (rng.randn(n, n) / np.sqrt(n)).astype(np.float32)
        return [jnp.asarray(base * (1.0 + 1e-3 * i), jnp.bfloat16)
                for i in range(k)]

    # every jitted fn returns a SCALAR: the timed region must not
    # include fetching a full (n, n) result to the host
    f = jax.jit(lambda a: (a @ a).astype(jnp.float32).sum())
    for n in (4096, 8192, 16384):
        xs = mk(n)
        _bench_rotating(f"mm{n}", f, xs, 2.0 * n ** 3)

    xs = mk(4096)

    def unroll(a):
        c = a
        for _ in range(16):
            c = (c @ a).astype(jnp.bfloat16)
        return c.astype(jnp.float32).sum()

    _bench_rotating("unroll16", jax.jit(unroll), xs, 16 * 2.0 * 4096 ** 3)

    def scan64(a):
        return lax.scan(lambda c, _: ((c @ a).astype(jnp.bfloat16), None),
                        a, None, length=64)[0].astype(jnp.float32).sum()

    _bench_rotating("scan64", jax.jit(scan64), xs, 64 * 2.0 * 4096 ** 3,
                    reps=3)

    def scan64_f32(a):
        def body(c, _):
            y = jax.lax.dot_general(c, a, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            return y.astype(jnp.bfloat16), None
        return lax.scan(body, a, None, length=64)[0] \
            .astype(jnp.float32).sum()

    _bench_rotating("scan64_f32acc", jax.jit(scan64_f32), xs,
                    64 * 2.0 * 4096 ** 3, reps=3)


# ---------------------------------------------------------------------------
# shapes — llama-shaped matmul chains (was probe 5b)
# ---------------------------------------------------------------------------

def _bench_args(tag, f, args, flops, reps=5):
    fetch(f(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fetch(f(*args))
        ts.append(time.perf_counter() - t0)
    dt = med(ts)
    print(f"{tag:12s} {dt*1e3:9.2f} ms  {flops/dt/1e12:7.1f} TFLOP/s "
          f"(min {min(ts)*1e3:.2f} max {max(ts)*1e3:.2f})", flush=True)


def cmd_shapes() -> None:
    print("device:", jax.devices()[0], flush=True)
    rng = np.random.RandomState(0)
    B, D, V = 16384, 768, 32000
    x = jnp.asarray(rng.randn(B, D).astype(np.float32) / 28, jnp.bfloat16)
    w_head = jnp.asarray(rng.randn(D, V).astype(np.float32) / 28,
                         jnp.bfloat16)
    w_back = jnp.asarray(rng.randn(V, D).astype(np.float32) / 180,
                         jnp.bfloat16)
    w_proj = jnp.asarray(rng.randn(D, D).astype(np.float32) / 28,
                         jnp.bfloat16)

    def lmhead16(x, wh, wb):
        c = x
        for _ in range(8):
            y = (c @ wh).astype(jnp.bfloat16)     # (B, V)
            c = (y @ wb).astype(jnp.bfloat16)     # (B, D)
        return c.astype(jnp.float32).sum()

    fl = 8 * (2.0 * B * D * V + 2.0 * B * V * D)
    _bench_args("lmhead16", jax.jit(lmhead16), (x, w_head, w_back), fl)

    def proj64(x, w):
        def body(c, _):
            return (c @ w).astype(jnp.bfloat16), None
        return lax.scan(body, x, None, length=64)[0] \
            .astype(jnp.float32).sum()

    _bench_args("proj64", jax.jit(proj64), (x, w_proj),
                64 * 2.0 * B * D * D)

    s = jnp.asarray(rng.randn(1024, 1024).astype(np.float32) / 32,
                    jnp.bfloat16)

    def sq1024x64(a):
        def body(c, _):
            return (c @ a).astype(jnp.bfloat16), None
        return lax.scan(body, a, None, length=64)[0] \
            .astype(jnp.float32).sum()

    _bench_args("sq1024x64", jax.jit(sq1024x64), (s,),
                64 * 2.0 * 1024 ** 3)


COMMANDS = {"basic": cmd_basic, "fence": cmd_fence,
            "overhead": cmd_overhead, "validate": cmd_validate,
            "matmul": cmd_matmul, "shapes": cmd_shapes}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="dispatch/overhead probes (consolidated r4/r5 set)")
    p.add_argument("probe", choices=sorted(COMMANDS),
                   help="which probe to run")
    args = p.parse_args(argv)
    COMMANDS[args.probe]()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
