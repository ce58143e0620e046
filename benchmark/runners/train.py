"""Train runner: the compiled-graph trainer exactly as
chip_smoke.py::train_phase builds it, on one fixed seeded batch, steps
dispatched back to back in fenced windows for `--seconds`."""

from __future__ import annotations

import statistics
import time
import warnings

import numpy as np


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import reference
    import yardstick
    from singa_tpu import models, opt, tensor

    cfg, cell, shape = ctx.config, ctx.cell, ctx.shape
    # the two quiet degradations of the train path are errors here
    warnings.filterwarnings(
        "error", message=".*donated buffers were not usable.*")
    warnings.filterwarnings("error", message=".*jit-init trace failed.*")
    tr = cfg["train"]
    ctx.llama.fused_loss = tr["fused_loss"]
    tensor.set_seed(ctx.seed)
    ids_np = np.random.default_rng([ctx.seed, 4]).integers(
        0, shape["vocab_size"], (cell["batch"], cell["seq"])).astype(np.int32)
    m = models.Llama(ctx.llama)
    m.set_optimizer(opt.SGD(lr=tr["lr"], momentum=tr["momentum"]))
    ids = tensor.from_numpy(ids_np)
    m.compile([ids], is_train=True, use_graph=True)

    ctx.stamp("weights made (jit-init)")
    # the reference's loss and one gradient on the initial weights,
    # before step 1 changes them (the step donates its buffers; a copy
    # of all of them kept until after the window would be 4 B a
    # parameter more)
    chk = cfg["check"]
    params = {n: p.data for n, p in m.get_params().items()}
    ref_loss, ref_grad = reference.loss_and_grad(params, ids_np, shape,
                                                 chk["grad_of"])
    w0 = jnp.copy(params[chk["grad_of"]])
    del params
    ctx.stamp("reference loss and gradient taken")

    step = lambda: m.train_step(ids)[-1].data
    warm = [float(step())]
    # SGD's first step with momentum is w1 = w0 - lr * g
    g = (w0 - m.get_params()[chk["grad_of"]].data) / tr["lr"]
    grad_err = float(jnp.linalg.norm(g - ref_grad)
                     / jnp.linalg.norm(ref_grad))
    del w0, g, ref_grad
    warm += [float(step()) for _ in range(cell["warmup_steps"] - 1)]
    temp = m.graph.memory_analysis()["temp_size_in_bytes"]
    print(f"[train] params={m.num_params():,} batch={cell['batch']}x"
          f"{cell['seq']} warm-up losses={[round(v, 4) for v in warm]} "
          f"reference first loss={ref_loss:.4f}; compiled step temp "
          f"(scratch) bytes: {temp:,}", flush=True)
    if not ctx.dry_run and "tpu_custom_call" not in m.graph.compiled_hlo():
        raise SystemExit("benchmark: no Pallas flash kernel in the "
                         "compiled train step")

    ctx.stamp("warm-up done, window opens")
    t_open = time.perf_counter()
    setup_s = t_open - ctx.t0
    windows = yardstick.timed_windows(step, ctx.seconds, cell["window_len"],
                                      on_window=ctx.tracer.tick)
    t_close = time.perf_counter()
    trace = ctx.tracer.stop(ctx.dump_trace)
    losses = [float(v) for _, outs in windows for v in jax.device_get(outs)]
    total_s = sum(dt for dt, _ in windows)
    steps = len(losses)
    tokens_per_s = cell["batch"] * cell["seq"] * steps / total_s
    step_ms = [dt / cell["window_len"] * 1e3 for dt, _ in windows]
    bad = int(np.sum(~np.isfinite(losses)))
    if not ctx.dry_run:         # no time from a CPU run is ever printed
        print(f"[train] step ms by window: {[round(v, 1) for v in step_ms]}; "
              f"{ctx.compile_log.between(t_open, t_close)} programs "
              f"compiled or loaded inside the window", flush=True)
    correct = (abs(warm[0] - ref_loss) <= chk["tolerance"]
               and grad_err <= chk["grad_tolerance"] and bad == 0
               and np.isfinite(warm).all() and warm[-1] < warm[0])
    print(f"[train] {steps} steps in {len(windows)} windows, {total_s:.3f} s; "
          f"|first loss - reference| = {abs(warm[0] - ref_loss):.5f} "
          f"(tolerance {chk['tolerance']}); gradient of {chk['grad_of']}: "
          f"relative error {grad_err:.4f} (tolerance "
          f"{chk['grad_tolerance']}); last loss {losses[-1]:.4f}", flush=True)

    return {"correct": bool(correct), "attempted": steps, "failed": bad,
            "end_to_end": {"train_tokens_per_s": tokens_per_s,
                           "setup_s": setup_s}, "trace": trace,
            "tokens_per_s": tokens_per_s, "seq": cell["seq"],
            "step_ms": statistics.median(step_ms)}
