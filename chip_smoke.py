"""chip_smoke.py — does the system still start on the chip?

Drives the two main paths once through the entry points a user calls,
at the full width of ``models.LlamaConfig.base()`` (d 2048, 16 layers,
16 query / 8 KV heads of 128, FFN 5632, vocab 32,000; weights random
from a seed), and checks what comes out:

* **train** — ``Llama(base, fused_loss)`` + ``SGD(0.01, momentum 0.9)``,
  ``compile(use_graph=True)``, a few ``train_step``s on one fixed 8x1024
  batch: finite falling loss, the Pallas flash kernel in the compiled
  step, no donation or jit-init fallback warning;
* **serve** — the same config in eval behind
  ``ServeEngine(num_slots=8, max_len=1024, block_size=32)``: 8 requests
  behind a shared prefix, every handle finished normally with in-range
  tokens, exactly two compiled programs, a prefix hit, and the streams
  checked against the model's own forward;
* ``--chips N`` — instead of serve, the train phase again under an
  N-way data-parallel mesh with ``DistOpt``: an all-reduce in the step,
  state on every chip, the one-chip loss trajectory reproduced.

A chip belongs to one process at a time, and the 0.9B training state
must be gone before anything else is built, so the phases run as
strictly sequential children of this parent, which never imports jax.
Without a TPU every child, and so the run, fails: nothing here falls
back, retries or catches.  ``--dry-run`` is the CPU rehearsal of the
control flow at ``LlamaConfig.tiny()``; it proves nothing about the
chip.

Prints per phase: wall and compile seconds, peak HBM, persistent-cache
hits and misses.  None of it is a benchmark number.  The run's JSON
summary (``{"ok": true, ..., "phases": {...}, "claim": null}``) is the
second-to-last stdout line; the last is the result the driver reads,
exactly ``{"ok": true, "device": {"platform", "kind", "count"}}`` with
the device as JAX reports it.  A failed run prints neither.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time

#: the whole run must end inside the driver's 1200 s, compiles included
_DEADLINE_S = 1150.0
#: one-chip vs N-chip loss: same global batch, same seed; only the
#: reduction order differs (per-chip partial sums + all-reduce).  The
#: loss is an f32 mean over 8192 tokens near 10.4, so 2e-3 is ~0.02 —
#: under one step's fall, far over reduction-order noise
_DP_LOSS_RTOL = 2e-3


def _sizes(dry_run: bool) -> dict:
    if dry_run:
        return {"batch": 8, "seq": 64, "steps": 6, "num_slots": 8,
                "max_len": 128, "block_size": 8, "prefix": 8,
                "prompt_lens": (8, 16, 32, 48), "new_tokens": 8}
    return {"batch": 8, "seq": 1024, "steps": 6, "num_slots": 8,
            "max_len": 1024, "block_size": 32, "prefix": 32,
            "prompt_lens": (64, 128, 256, 512), "new_tokens": 32}


class _CompileLog:
    """Backend-compile seconds by program name and persistent-cache
    hit/miss counts, from jax's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds: dict = {}
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            name = kw.get("fun_name", "?")
            self.seconds[name] = self.seconds.get(name, 0.0) + secs

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def summary(self) -> dict:
        big = {k: round(v, 2) for k, v in self.seconds.items() if v >= 1.0}
        return {"compile_s": round(sum(self.seconds.values()), 2),
                "compile_s_by_program": big,
                "cache_hits": self.hits, "cache_misses": self.misses}


def _start(dry_run: bool, chips: int):
    """Device gate + per-process setup; returns (jax devices, log)."""
    import warnings

    import jax

    devs = jax.devices()
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    if dry_run:
        print("DRY RUN on the CPU at LlamaConfig.tiny(): rehearses the "
              "control flow, proves nothing about the chip", flush=True)
    elif d0.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — jax resolved to "
                 f"platform={d0.platform}")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} but jax sees {len(devs)}")

    # the two quiet degradations on the train path become errors
    warnings.filterwarnings(
        "error", message=".*donated buffers were not usable.*")
    warnings.filterwarnings("error", message=".*jit-init trace failed.*")

    from singa_tpu import device
    from singa_tpu.utils.compile_cache import enable_compile_cache
    from singa_tpu.utils.metrics import peak_flops, peak_hbm_bw

    cache = enable_compile_cache(d0.platform)
    print(f"compile cache: {cache or 'off'}; peaks for {d0.device_kind!r}: "
          f"{peak_flops(d0.device_kind):.3g} FLOP/s, "
          f"{peak_hbm_bw(d0.device_kind):.3g} B/s", flush=True)
    device.set_default_device(
        device.create_cpu_device() if dry_run
        else device.create_device("tpu"))
    return devs, _CompileLog()


def _finish(name: str, t0: float, devs, log: _CompileLog, out: dict) -> dict:
    stats = [d.memory_stats() or {} for d in devs]
    out.update(log.summary())
    out["wall_s"] = round(time.perf_counter() - t0, 2)
    out["peak_bytes_in_use"] = [s.get("peak_bytes_in_use") for s in stats]
    out["device"] = {"platform": devs[0].platform,
                     "kind": devs[0].device_kind, "count": len(devs)}
    print(f"[{name}] wall {out['wall_s']} s, compile {out['compile_s']} s "
          f"{out['compile_s_by_program']}, peak HBM "
          f"{out['peak_bytes_in_use']} B, persistent cache "
          f"{out['cache_hits']} hits / {out['cache_misses']} misses",
          flush=True)
    return out


def _config(dry_run: bool):
    from singa_tpu import models
    return models.LlamaConfig.tiny() if dry_run \
        else models.LlamaConfig.base()


def train_phase(dry_run: bool = False, mesh_axes: dict | None = None) -> dict:
    """The compiled-graph trainer; under `mesh_axes` the same step
    through DistOpt on that mesh."""
    t0 = time.perf_counter()
    chips = math.prod((mesh_axes or {}).values())
    devs, log = _start(dry_run, chips)

    import numpy as np

    from singa_tpu import models, opt, parallel, tensor

    sz = _sizes(dry_run)
    cfg = _config(dry_run)
    cfg.fused_loss = True
    tensor.set_seed(0)
    np.random.seed(0)
    m = models.Llama(cfg)
    sgd = opt.SGD(lr=0.01, momentum=0.9)
    if mesh_axes:
        parallel.set_mesh(parallel.make_mesh(mesh_axes))
        m.set_optimizer(opt.DistOpt(sgd))
    else:
        m.set_optimizer(sgd)
    ids = tensor.from_numpy(np.random.randint(
        0, cfg.vocab_size, (sz["batch"], sz["seq"])).astype(np.int32))
    m.compile([ids], is_train=True, use_graph=True)

    losses = []
    for _ in range(sz["steps"]):
        losses.append(float(m.train_step(ids)[-1].to_numpy()))
    print(f"[train] mesh={mesh_axes} params={m.num_params():,} "
          f"batch={sz['batch']}x{sz['seq']} losses="
          f"{[round(v, 4) for v in losses]}", flush=True)
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"

    hlo = m.graph.compiled_hlo()
    # peak_bytes_in_use counts live buffers (params + moments), not the
    # step's own scratch (grads, activations) — that is XLA's temp size
    temp = m.graph.memory_analysis()["temp_size_in_bytes"]
    print(f"[train] compiled step temp (scratch) bytes: {temp:,}",
          flush=True)
    if not dry_run:
        # a silent drop to the XLA attention reference is a failure,
        # not a slower pass
        assert "tpu_custom_call" in hlo, \
            "no Pallas flash kernel in the compiled train step"
    used = [(d.memory_stats() or {}).get("bytes_in_use")
            for d in devs[:chips]]
    if mesh_axes:
        assert "all-reduce" in hlo, "no all-reduce in the compiled step"
        spread = {n: len(t.data.sharding.device_set)
                  for n, t in m.get_params().items()}
        assert set(spread.values()) == {chips}, \
            f"params not on all {chips} chips: {spread}"
        if not dry_run:
            assert all(used), f"a chip holds no state: bytes_in_use={used}"
    return _finish("train", t0, devs, log,
                   {"losses": losses, "mesh": mesh_axes,
                    "bytes_in_use": used, "step_temp_bytes": temp})


def serve_phase(dry_run: bool = False) -> dict:
    """The continuous-batching server, then its streams checked against
    `generate()` (reported) and the model's own forward (asserted)."""
    t0 = time.perf_counter()
    devs, log = _start(dry_run, 1)

    import numpy as np

    from singa_tpu import models, serve, tensor
    from singa_tpu.models._generate import GREEDY_TOL_BF16

    sz = _sizes(dry_run)
    cfg = _config(dry_run)
    tensor.set_seed(0)
    rng = np.random.RandomState(0)
    m = models.Llama(cfg)
    m.eval()
    prefix = rng.randint(0, cfg.vocab_size, (sz["prefix"],))
    prompts = [np.concatenate([prefix, rng.randint(0, cfg.vocab_size, (n,))
                               ]).astype(np.int32)
               for n in sz["prompt_lens"] for _ in range(2)]
    m.compile([tensor.from_numpy(prompts[0][None])], is_train=False,
              use_graph=True)

    n_new = sz["new_tokens"]
    eng = serve.ServeEngine(m, num_slots=sz["num_slots"],
                            max_len=sz["max_len"],
                            block_size=sz["block_size"])
    handles = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
    eng.run_until_idle()
    # _dispatch retries and _admit_traced quarantines: a compile error
    # on the chip ends as a `failed` handle, not an exception — so look
    for p, h in zip(prompts, handles):
        assert h.done and h.finish_reason == "length" and not h.failed, \
            (f"prompt len {p.size}: status={h.status} "
             f"reason={h.finish_reason} error={h.error}")
        toks = np.asarray(h.tokens)
        assert toks.shape == (n_new,) and toks.min() >= 0 \
            and toks.max() < cfg.vocab_size, f"bad tokens: {toks}"
    snap = eng.metrics.snapshot()
    assert eng.compiled_counts() == (1, 1), eng.compiled_counts()
    assert snap["prefix_hits"] > 0, "no prefix-cache hit"
    print(f"[serve] {len(handles)}/{len(prompts)} requests x {n_new} tokens, "
          f"programs={eng.compiled_counts()}, prefix_hits="
          f"{snap['prefix_hits']} ({snap['prefix_hit_tokens']} tokens), "
          f"engine steps={eng.metrics.steps}", flush=True)

    # reference 1 (reported): generate() on the shortest and the longest
    agree = {}
    for i in (0, len(prompts) - 1):
        ref = m.generate(prompts[i][None], max_new_tokens=n_new)[
            0, prompts[i].size:]
        got = np.asarray(handles[i].tokens)
        same = ref == got
        agree[int(prompts[i].size)] = {
            "equal": int(same.sum()), "of": n_new,
            "first_mismatch": None if same.all() else int(np.argmin(same))}
    print(f"[serve] engine vs generate() greedy tokens: {agree}", flush=True)

    # reference 2 (asserted): one teacher-forced forward over the served
    # sequence; each served token must be the row maximum of the
    # model's own logits, up to a stated tolerance
    worst = m.greedy_margin(handles[-1].result(), prompts[-1].size)
    print(f"[serve] teacher-forced check: worst (max logit - served "
          f"token's logit) = {worst:.4g} (tolerance {GREEDY_TOL_BF16})",
          flush=True)
    assert worst <= GREEDY_TOL_BF16, \
        f"served stream is not greedy under the model's own forward: {worst}"
    eng.close()
    return _finish("serve", t0, devs, log,
                   {"generate_agreement": agree,
                    "teacher_forced_margin": worst,
                    "prefix_hits": snap["prefix_hits"]})


_PHASES = {
    "train": lambda a: train_phase(a.dry_run),
    "train-dp": lambda a: train_phase(a.dry_run, {"data": a.chips}),
    "serve": lambda a: serve_phase(a.dry_run),
}


def _run_child(phase: str, args, deadline: float) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--chips", str(args.chips)]
    if args.dry_run:
        cmd.append("--dry-run")
    # run() kills the child at the timeout and raises; nothing is caught
    r = subprocess.run(cmd, stdout=subprocess.PIPE, text=True,
                       timeout=max(1.0, deadline - time.monotonic()))
    sys.stdout.write(r.stdout)
    sys.stdout.flush()
    if r.returncode:
        sys.exit(f"chip_smoke: phase {phase} failed (exit {r.returncode})")
    return json.loads(r.stdout.splitlines()[-1])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="N > 1: train on one chip, then N-way data "
                         "parallel, and compare (no serve phase)")
    ap.add_argument("--dry-run", action="store_true",
                    help="CPU rehearsal at LlamaConfig.tiny(); proves "
                         "nothing about the chip")
    ap.add_argument("--phase", choices=sorted(_PHASES),
                    help="internal: run one phase in this process")
    args = ap.parse_args()

    if args.phase:
        print(json.dumps(_PHASES[args.phase](args)), flush=True)
        return

    deadline = time.monotonic() + _DEADLINE_S
    names = ("train", "serve") if args.chips == 1 else ("train", "train-dp")
    phases = {n: _run_child(n, args, deadline) for n in names}
    if args.chips > 1:
        one, dp = phases["train"]["losses"], phases["train-dp"]["losses"]
        assert all(math.isclose(a, b, rel_tol=_DP_LOSS_RTOL)
                   for a, b in zip(one, dp)), \
            f"{args.chips}-chip losses {dp} left the one-chip " \
            f"trajectory {one} (rtol {_DP_LOSS_RTOL})"
        print(f"[train-dp] matches the one-chip trajectory within rtol "
              f"{_DP_LOSS_RTOL}", flush=True)
    device = phases[names[-1]]["device"]
    print(json.dumps({"ok": True, "dry_run": args.dry_run, "device": device,
                      "phases": phases, "claim": None}), flush=True)
    # the result line: these two keys and no others
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
