"""One-shot TPU measurement session.

Collects, in ONE process holding the chip, an evidence package: the
windowed-throughput headline (utils.timing — windows of 8 back-to-back
steps, fenced at window ends, cross-checked against
K-steps-in-ONE-compiled-program), the matmul microbench calibrating
sustained MXU rate, corrected-layout ResNet-50 and BERT secondaries,
GPT-2-through-sonnx inference on chip, MoE with scatter dispatch,
long-context (4k dense, 8k banded-vs-dense), the host-fed input
pipeline proof, and the ablation matrix — then writes PERF_NOTES.md
and tpu_session.json (neither is committed; PERF.md is the record).
Uses the shared persistent compile cache (utils.compile_cache).

Methodology:
  * per-step fencing pays a per-dispatch host latency a real
    (pipelined) training loop never pays, so step time is the median
    over windows of 8 unfenced steps, cross-checked against a
    lax.scan-of-8-steps single program;
  * the fence is jax.block_until_ready (utils.timing).

Internally soft-deadlined: stages are skipped (with a mark) once the
budget is spent.

Usage:  python tools/tpu_session.py      # on the chip machine
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

_T0 = time.monotonic()
_STARTED_AT = time.time()  # singalint: disable=SGL005 session-start epoch timestamp for the durable record's created_at — must correlate across runs/hosts; budget math uses _T0
_BUDGET_S = float(os.environ.get("SINGA_TPU_SESSION_BUDGET_S", "4800"))
# SINGA_TPU_SESSION_SMOKE=1: tiny shapes + CPU pin, to validate the
# session logic end-to-end without a chip
_SMOKE = os.environ.get("SINGA_TPU_SESSION_SMOKE") == "1"
# SINGA_TPU_SESSION_ONLY=a,b,c: run only the named stages (plus probe)
# and MERGE results into the existing session record — for re-running
# stages that failed (OOM/compile-helper) without redoing the session
_ONLY = {n for n in os.environ.get("SINGA_TPU_SESSION_ONLY", "").split(",")
         if n}
# SINGA_TPU_SESSION_DIR: where the record/log/store land (default: the
# repo root).  Exists so tests can exercise the full write path —
# including the smoke-vs-chip guard — against a scratch dir.
_DIR = os.path.abspath(os.environ.get(
    "SINGA_TPU_SESSION_DIR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")))
_LOG = os.path.join(_DIR, "tpu_session.log")
_RESULTS: dict = {"stages": {}}
# run identity for the durable store (singa_tpu.obs.record): one entry
# per (run_id, platform, smoke); platform is stamped by the probe stage
_RUN_ID = f"session-{time.strftime('%Y%m%d-%H%M%S')}-{os.getpid()}"


def mark(msg: str) -> None:
    line = f"[{time.monotonic() - _T0:7.1f}s] {msg}"
    with open(_LOG, "a") as f:
        f.write(line + "\n")
    print(line, flush=True)


def left() -> float:
    return _BUDGET_S - (time.monotonic() - _T0)


def stage(name: str, need_s: float):
    """Decorator: run the stage unless the budget is too tight; record
    outcome + duration; a failing stage never kills the session."""
    def deco(fn):
        def run(*a, **k):
            if _ONLY and name not in _ONLY and name != "probe":
                return None
            if left() < need_s:
                mark(f"SKIP {name}: {left():.0f}s left < {need_s:.0f}s")
                _RESULTS["stages"][name] = {"skipped": True}
                return None
            # promptly drop the previous stage's device buffers (an
            # exception traceback or deferred GC can pin a whole model's
            # HBM into the next stage — the first r5 run OOM-cascaded)
            import gc
            gc.collect()
            t0 = time.monotonic()
            try:
                out = fn(*a, **k)
                _RESULTS["stages"][name] = {"ok": True,
                                            "s": round(time.monotonic() - t0, 1),
                                            "result": out}
                mark(f"DONE {name} in {time.monotonic() - t0:.1f}s: {out}")
                _finish(final=False)   # persist incrementally: a later
                # wedged stage must not cost the whole record
                return out
            except Exception as e:  # noqa: BLE001 - session must continue
                # first line, ANSI-stripped, capped: a remote-compile
                # failure can embed a multi-KB escape-laden helper log
                import re
                msg = re.sub(r"\x1b\[[0-9;]*m", "",
                             str(e).splitlines()[0] if str(e) else "")[:300]
                _RESULTS["stages"][name] = {"ok": False,
                                            "error": f"{type(e).__name__}: "
                                                     f"{msg}"}
                mark(f"FAIL {name}: {type(e).__name__}: {msg}")
                return None
        return run
    return deco


def _fetch(x):
    import numpy as np
    return np.asarray(x).ravel()[0]


def main() -> None:
    open(_LOG, "w").close()
    if _ONLY:
        # merge source is decided by MODE alone (the probe hasn't run
        # yet, so _session_json_path()'s platform-based redirect must
        # not be consulted here): a smoke rerun merges the smoke
        # snapshot — NEVER the on-chip record, which is how r5
        # polluted-then-lost its evidence — and a real rerun merges
        # tpu_session.json so the stages it does NOT rerun survive
        path = _merge_source_path()
        _merge_only_results(path)
        mark(f"ONLY mode: {sorted(_ONLY)} (merging from {path})")
    mark(f"session start, budget {_BUDGET_S:.0f}s"
         + (" [SMOKE]" if _SMOKE else ""))

    import jax

    if _SMOKE:
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp

    @stage("probe", 60)
    def probe():
        d = jax.devices()
        x = jnp.ones((256, 256), jnp.bfloat16)
        jax.block_until_ready(jax.jit(lambda a: a @ a)(x))
        _RESULTS["device"] = getattr(d[0], "device_kind", d[0].platform)
        _RESULTS["platform"] = d[0].platform
        return d[0].platform

    platform = probe()
    if platform is None:
        _finish()
        return

    from singa_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache(platform)

    @stage("flash_fwd_bwd", 120)
    def flash():
        from singa_tpu.ops.flash_attention import flash_attention
        q = jnp.zeros((1, 128, 2, 32) if _SMOKE else (16, 1024, 8, 64),
                      jnp.bfloat16)
        f = jax.jit(lambda q: flash_attention(q, q, q, causal=True))
        jax.block_until_ready(f(q))
        g = jax.jit(jax.grad(
            lambda q: flash_attention(q, q, q, causal=True)
            .astype(jnp.float32).sum()))
        jax.block_until_ready(g(q))
        return "flash fwd+bwd compiled+ran at bench shape"

    flash()

    @stage("flash_banded_fwd_bwd", 120)
    def flash_banded():
        from singa_tpu.ops.flash_attention import flash_attention
        q = jnp.zeros((1, 128, 2, 32) if _SMOKE else (8, 2048, 8, 64),
                      jnp.bfloat16)
        W = 16 if _SMOKE else 512
        f = jax.jit(lambda q: flash_attention(q, q, q, causal=True,
                                              window=W))
        jax.block_until_ready(f(q))
        g = jax.jit(jax.grad(
            lambda q: flash_attention(q, q, q, causal=True, window=W)
            .astype(jnp.float32).sum()))
        jax.block_until_ready(g(q))
        return f"banded flash fwd+bwd compiled+ran (W={W})"

    flash_banded()

    import numpy as np

    from singa_tpu import device, models, opt, tensor
    from singa_tpu.utils.metrics import peak_flops, peak_hbm_bw
    from singa_tpu.utils.timing import fenced_steps, windowed_steps

    device.set_default_device(device.create_cpu_device() if _SMOKE
                              else device.create_tpu_device())
    dev_kind = _RESULTS.get("device", "tpu")
    peak = peak_flops(dev_kind)
    hbm = peak_hbm_bw(dev_kind)

    @stage("matmul_microbench", 240)
    def matmul_micro():
        """Two instruments (r5 probes 5/5b):

        (a) sustained rate on a chain of LLAMA-SHAPED bf16 matmuls
            (16384x768 @ 768x32000 and back, unrolled x8 = 12.88
            TFLOP of exactly known work, scalar-reduced in-program) —
            the calibration the analytic-MFU numbers are judged
            against.

        (b) the on-chip proof that XLA cost_analysis counts a scan
            body ONCE: a 64-iteration scan of 1024^3 matmuls reports
            ~2 GFLOP where 137 execute (VERDICT r4 item 3)."""
        from jax import lax
        rng = np.random.RandomState(0)
        if _SMOKE:
            B, D, V, reps = 64, 32, 128, 2
        else:
            B, D, V, reps = 16384, 768, 32000, 8
        x = jnp.asarray(rng.randn(B, D).astype(np.float32) / 28,
                        jnp.bfloat16)
        wh = jnp.asarray(rng.randn(D, V).astype(np.float32) / 28,
                         jnp.bfloat16)
        wb = jnp.asarray(rng.randn(V, D).astype(np.float32) / 180,
                         jnp.bfloat16)

        def chain(x, wh, wb):
            c = x
            for _ in range(8):
                y = (c @ wh).astype(jnp.bfloat16)
                c = (y @ wb).astype(jnp.bfloat16)
            # scalar-reduce in-program: the timed region must not
            # include fetching a full result to the host
            return c.astype(jnp.float32).sum()

        f = jax.jit(chain)
        _fetch(f(x, wh, wb))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            _fetch(f(x, wh, wb))
            ts.append(time.perf_counter() - t0)
        dt = statistics.median(ts)
        true_flops = 8 * 2.0 * (B * D * V + B * V * D)

        # (b) CA-counts-scan-once proof on a cheap scan
        n1, K = (64, 4) if _SMOKE else (1024, 64)
        s = jnp.asarray(rng.randn(n1, n1).astype(np.float32) / 32,
                        jnp.bfloat16)
        g = jax.jit(lambda a: lax.scan(
            lambda c, _: ((c @ a).astype(jnp.bfloat16), None),
            a, None, length=K)[0].astype(jnp.float32).sum())
        try:
            ca = g.lower(s).compile().cost_analysis()
            ca_flops = float((ca[0] if isinstance(ca, (list, tuple))
                              else ca).get("flops", 0.0))
        except Exception:
            ca_flops = 0.0
        return {"shape": f"{B}x{D}x{V} chain8",
                "true_tflop_per_call": round(true_flops / 1e12, 3),
                "call_ms": round(dt * 1e3, 2),
                "sustained_tflops": round(true_flops / dt / 1e12, 1),
                "mfu_equiv": round(true_flops / dt / peak, 4),
                "scan_proof": {
                    "true_gflop": round(2.0 * n1 ** 3 * K / 1e9, 2),
                    "cost_analysis_gflop": round(ca_flops / 1e9, 2)}}

    matmul_micro()

    # ------------------------------------------------------------------
    def llama_model(fused=True, flash_on=True, batch=16, seqlen=1024,
                    cfg_extra=None, base=False):
        if flash_on:
            os.environ.pop("SINGA_DISABLE_FLASH", None)
        else:
            os.environ["SINGA_DISABLE_FLASH"] = "1"
        tensor.set_seed(0)
        np.random.seed(0)
        cfg = models.LlamaConfig.tiny() if _SMOKE \
            else (models.LlamaConfig.base() if base
                  else models.LlamaConfig.small())
        cfg.max_position = max(cfg.max_position, seqlen)
        cfg.fused_loss = fused
        for k, v in (cfg_extra or {}).items():
            setattr(cfg, k, v)
        m = models.Llama(cfg)
        m.set_optimizer(opt.SGD(lr=0.01, momentum=0.9))
        ids = tensor.from_numpy(np.random.randint(
            0, cfg.vocab_size, (batch, seqlen)).astype(np.int32))
        return m, ids, cfg

    def llama_run(tag: str, fused: bool, flash_on: bool, train: bool,
                  batch: int = 16, seqlen: int = 1024, windows: int = 4,
                  cfg_extra: dict | None = None, keep=None, base=False):
        if _SMOKE:
            batch, seqlen, windows = 2, 64, 2
        m, ids, cfg = llama_model(fused, flash_on, batch, seqlen, cfg_extra,
                                  base=base)
        t0 = time.monotonic()
        m.compile([ids], is_train=train, use_graph=True)
        t_init = time.monotonic() - t0
        t0 = time.monotonic()
        if train:
            out = m.train_step(ids)
            _fetch(out[-1].data)
        else:
            m.eval()
            out = m(ids)
            jax.block_until_ready(out.data)
        t_compile = time.monotonic() - t0

        if train:
            holder = {}

            def one():
                holder["out"] = m.train_step(ids)
                return holder["out"][-1].data
        else:
            holder = {}

            def one():
                holder["out"] = (m(ids),)
                return holder["out"][-1].data

        dt, stats = windowed_steps(one, windows=windows, window_len=8,
                                   warmup=1, budget_left=left)
        _, fstats = fenced_steps(one, steps=6, warmup=0, budget_left=left)
        g = m.graph
        ca = g.cost_analysis() if g is not None else {}
        flops = float(ca.get("flops", 0.0))
        byts = float(ca.get("bytes accessed", 0.0))
        # primary MFU from the analytic formula (6N + attention): XLA
        # cost_analysis counts a scan body once (the chunked CE) and
        # sees no FLOPs inside the Pallas kernel — proven on-chip by
        # the matmul_microbench stage's CA-vs-true comparison
        fl_analytic = (m.flops_per_token(seqlen) * batch * seqlen
                       if train and hasattr(m, "flops_per_token") else 0.0)
        row = {
            "tag": tag, "batch": batch, "seq": seqlen,
            "init_s": round(t_init, 1), "compile_s": round(t_compile, 1),
            "step_ms": round(dt * 1e3, 2),
            "step_stats": stats, "fenced_stats": fstats,
            "tokens_per_s": round(batch * seqlen / dt, 1),
            "mfu": round(fl_analytic / dt / peak, 4) if fl_analytic
            else (round(flops / dt / peak, 4) if flops else None),
            "mfu_cost_analysis": round(flops / dt / peak, 4) if flops
            else None,
            "compiled_tflops": round(flops / 1e12, 3),
            "bytes_gb": round(byts / 1e9, 3),
            "roofline_compute_ms": round(flops / peak * 1e3, 2),
            "roofline_memory_ms": round(byts / hbm * 1e3, 2),
        }
        if train:
            row["loss"] = round(float(holder["out"][-1].to_numpy()), 4)
        if keep is not None:
            keep["m"], keep["ids"] = m, ids
        return row

    head_keep: dict = {}

    def _headline_step_ms():
        r = (_RESULTS["stages"].get("llama_headline") or {}).get("result")
        return r.get("step_ms") if isinstance(r, dict) else None

    @stage("llama_headline", 480)
    def headline():
        """Flagship: the 0.9B config sized for this chip (r5 flagship
        sweep — honest MFU 0.65 vs 0.39 for the 110M `small`)."""
        return llama_run("base09b+flash+fused", True, True, True,
                         batch=8, windows=5, keep=head_keep, base=True)

    headline()

    @stage("llama_small_continuity", 300)
    def small_row():
        """The r1-r4 headline config (110M, b16x1024) under the same
        methodology — the cross-round comparison row."""
        return llama_run("small+flash+fused", True, True, True,
                      batch=16, windows=3)

    small_row()

    @stage("llama_scan_steps_crosscheck", 300)
    def scan_cross():
        """K train steps compiled into ONE lax.scan program — the
        un-fakeable device-time arbiter the windowed headline must
        agree with (it cannot pipeline or mis-fence anything)."""
        if not head_keep:
            raise RuntimeError("headline stage did not run")
        from jax import lax
        m, ids = head_keep["m"], head_keep["ids"]
        K = 2 if _SMOKE else 8
        ex = next(iter(m._executors.values()))
        fn = ex._jitted.__wrapped__

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
        stepc = jnp.asarray(0, jnp.int32)
        rng = jax.random.PRNGKey(0)
        t0 = time.monotonic()
        losses, params, buffers, slots = jm(params, buffers, slots, stepc,
                                            rng, (ids.data,))
        _fetch(losses)
        t_compile = time.monotonic() - t0
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            losses, params, buffers, slots = jm(params, buffers, slots,
                                                stepc, rng, (ids.data,))
            _fetch(losses)
            ts.append(time.perf_counter() - t0)
        # the scan program DONATED the executor's live arrays — rebind
        # the final state so later stages (hostfed_input) can keep
        # training this model
        for n, t in ex.param_tensors.items():
            t.data = params[n]
        for n, t in ex.buffer_tensors.items():
            t.data = buffers[n]
        ex.slots = slots
        dt = statistics.median(ts) / K
        head = _headline_step_ms()
        return {"k": K, "compile_s": round(t_compile, 1),
                "step_ms": round(dt * 1e3, 2),
                "windowed_headline_step_ms": head,
                "agreement": round(dt * 1e3 / head, 3) if head else None}

    scan_cross()
    # release the 0.9B flagship (params + momentum ~7 GB): keeping it
    # alive starved bert_sonnx/gpt2_sonnx into RESOURCE_EXHAUSTED on
    # the first r5 run; hostfed_input builds its own copy later
    head_keep.clear()

    @stage("resnet50", 420)
    def resnet():
        """CORRECTED in r5: feeds NHWC (the zoo's documented layout —
        r1-r4 fed NCHW, which the NHWC convs silently mis-read; every
        earlier committed ResNet number measured that mangled network)
        and counts FLOPs from the model's OWN traced graph
        (utils.flops; resnet50@224 = 8.18 GFLOP/img fwd on the
        2-FLOPs-per-MAC convention, = the published 4.09 GMACs)."""
        tensor.set_seed(0)
        np.random.seed(0)
        if _SMOKE:
            batches, hw = [2], 32
        else:
            from bench import RESNET50_TPU_BATCH
            # try larger-first (better MFU), walk down until one
            # compiles and fits
            batches, hw = [512, RESNET50_TPU_BATCH, 128, 64], 224
        last_err = None
        for b in batches:
            try:
                tensor.set_seed(0)
                np.random.seed(0)
                m = (models.resnet18(num_classes=10, cifar_stem=True)
                     if _SMOKE else
                     models.resnet50(num_classes=1000, cifar_stem=False))
                m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9,
                                        weight_decay=1e-4))
                x = tensor.from_numpy(
                    np.random.randn(b, hw, hw, 3).astype(np.float32))
                y = tensor.from_numpy(
                    np.random.randint(0, 10, (b,)).astype(np.int32))
                m.compile([x], is_train=True, use_graph=True)
                holder = {}

                def one():
                    holder["out"] = m.train_step(x, y)
                    return holder["out"][-1].data

                _fetch(one())
                last_err = None
                break
            except Exception as e:  # noqa: BLE001 - walk down batches
                last_err = e
                mark(f"resnet50 b{b} failed ({type(e).__name__}); "
                     f"trying smaller")
        if last_err is not None:
            raise last_err
        dt, stats = windowed_steps(one, windows=4, window_len=8, warmup=1,
                                   budget_left=left)
        _, fstats = fenced_steps(one, steps=6, warmup=0, budget_left=left)
        from singa_tpu.utils.flops import model_forward_flops
        fl_img = model_forward_flops(m, x)
        fl_an = 3 * fl_img * b
        g = m.graph
        fl_ca = g.flops() if g is not None else 0.0
        return {"batch": b, "image": hw,
                "fwd_gflop_per_image_traced": round(fl_img / 1e9, 3),
                "step_ms": round(dt * 1e3, 1),
                "images_per_s": round(b / dt, 1),
                "step_stats": stats, "fenced_stats": fstats,
                "mfu": round(fl_an / dt / peak, 4),
                "mfu_cost_analysis": round(fl_ca / dt / peak, 4) if fl_ca
                else None,
                "loss": round(float(holder["out"][-1].to_numpy()), 4)}

    resnet()

    @stage("bert_sonnx", 360)
    def bert():
        from singa_tpu import autograd, sonnx
        tensor.set_seed(0)
        np.random.seed(0)
        cfg = (models.BERTConfig.tiny(num_labels=2) if _SMOKE
               else models.BERTConfig(num_labels=2))
        b, seq = (2, 16) if _SMOKE else (256, 128)
        native = models.BERT(cfg)
        ids = tensor.from_numpy(np.random.randint(
            0, cfg.vocab_size, (b, seq)).astype(np.int32))
        rep = sonnx.prepare(sonnx.to_onnx(native, [ids]))
        rep.set_optimizer(opt.SGD(lr=0.01, momentum=0.9))
        rep.set_loss(lambda outs, y: autograd.softmax_cross_entropy(
            outs[0] if isinstance(outs, (list, tuple)) else outs, y))
        labels = tensor.from_numpy(
            np.random.randint(0, 2, (b,)).astype(np.int32))
        rep.compile([ids], is_train=True, use_graph=True)
        holder = {}

        def one():
            holder["out"] = rep.train_step(ids, labels)
            return holder["out"][-1].data

        _fetch(one())
        dt, stats = windowed_steps(one, windows=4, window_len=8, warmup=1,
                                   budget_left=left)
        _, fstats = fenced_steps(one, steps=6, warmup=0, budget_left=left)
        fl = native.flops_per_token(seq) * b * seq
        n_embed = (cfg.vocab_size + cfg.max_position
                   + cfg.type_vocab_size) * cfg.dim
        fl_incl = fl + 6 * n_embed * b * seq
        return {"step_ms": round(dt * 1e3, 1),
                "samples_per_s": round(b / dt, 1),
                "step_stats": stats, "fenced_stats": fstats,
                "mfu_analytic": None if _SMOKE
                else round(fl / dt / peak, 4),
                "mfu_analytic_with_embeddings": None if _SMOKE
                else round(fl_incl / dt / peak, 4)}

    bert()

    @stage("gpt2_sonnx", 540)
    def gpt2():
        """BASELINE.json:9 'BERT-base / GPT-2 inference on TPU': a real
        HF transformers GPT-2 (124M config, random init — zero egress)
        exported via torch.onnx, imported through sonnx, its forward
        run ON CHIP and checked against the native conversion
        (models.convert.from_hf_gpt2) of the SAME weights; then
        KV-cached whole-generation scan decode on chip, tokens/s."""
        import torch
        import transformers
        import transformers.models.gpt2.modeling_gpt2 as mg

        from singa_tpu import sonnx

        if _SMOKE:
            n_embd, n_layer, n_head, vocab = 32, 2, 2, 128
            B, P, N = 2, 8, 4
        else:
            n_embd, n_layer, n_head, vocab = 768, 12, 12, 50257
            B, P, N = 8, 128, 128
        torch.manual_seed(0)
        hcfg = transformers.GPT2Config(
            vocab_size=vocab, n_positions=1024, n_embd=n_embd,
            n_layer=n_layer, n_head=n_head, resid_pdrop=0.0,
            embd_pdrop=0.0, attn_pdrop=0.0, use_cache=False,
            attn_implementation="eager")
        hf = transformers.GPT2LMHeadModel(hcfg).eval()

        class Wrap(torch.nn.Module):
            def __init__(self, m):
                super().__init__()
                self.m = m

            def forward(self, ids):
                return self.m(input_ids=ids, use_cache=False).logits

        def simple_causal_mask(config=None, input_embeds=None,
                               attention_mask=None, cache_position=None,
                               past_key_values=None, position_ids=None,
                               **kw):
            T = input_embeds.shape[1]
            tri = torch.tril(torch.ones(T, T, dtype=torch.bool))
            m_ = torch.zeros(T, T, dtype=input_embeds.dtype).masked_fill(
                ~tri, torch.finfo(input_embeds.dtype).min)
            return m_[None, None].expand(input_embeds.shape[0], 1, T, T)

        import io

        # bypass the only exporter step that imports the (absent) onnx
        # wheel — identity for standard aten models (no onnxscript fns);
        # same recipe as tests/test_sonnx_external._torch_export_bytes
        from torch.onnx._internal.torchscript_exporter import \
            onnx_proto_utils
        orig_add = onnx_proto_utils._add_onnxscript_fn
        onnx_proto_utils._add_onnxscript_fn = \
            lambda model_bytes, custom_opsets: model_bytes
        ids_t = torch.randint(0, vocab, (2, 16))
        orig = getattr(mg, "create_causal_mask", None)
        if orig is not None:
            mg.create_causal_mask = simple_causal_mask
        try:
            buf = io.BytesIO()
            torch.onnx.export(Wrap(hf).eval(), (ids_t,), buf,
                              input_names=["ids"], output_names=["logits"],
                              dynamo=False, opset_version=14)
            data = buf.getvalue()
        finally:
            onnx_proto_utils._add_onnxscript_fn = orig_add
            if orig is not None:
                mg.create_causal_mask = orig
        mark(f"gpt2 onnx export: {len(data)/1e6:.0f} MB")

        t0 = time.monotonic()
        rep = sonnx.prepare(data)
        t_import = time.monotonic() - t0
        ids_np = ids_t.numpy().astype(np.int32)
        t0 = time.monotonic()
        outs = rep.run([ids_np])
        sx = np.asarray(outs[0] if isinstance(outs, (list, tuple)) else outs,
                        dtype=np.float32)
        t_fwd = time.monotonic() - t0

        from singa_tpu.models import convert
        native = convert.from_hf_gpt2(hf)
        native.eval()
        nt = tensor.from_numpy(ids_np)
        native.compile([nt], is_train=False, use_graph=True)
        nx = np.asarray(native(nt).to_numpy(), dtype=np.float32)
        diff = float(np.max(np.abs(sx - nx)))

        prompt = np.random.RandomState(0).randint(
            0, vocab, (B, P)).astype(np.int32)
        pdt = None if _SMOKE else jnp.bfloat16
        t0 = time.monotonic()
        native.generate(prompt, max_new_tokens=N, param_dtype=pdt)
        t_first = time.monotonic() - t0
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = native.generate(prompt, max_new_tokens=N, param_dtype=pdt)
            ts.append(time.perf_counter() - t0)
        dt = statistics.median(ts)
        assert out.shape == (B, P + N)
        return {"params_m": round(sum(p.numel()
                                      for p in hf.parameters()) / 1e6, 1),
                "onnx_mb": round(len(data) / 1e6, 1),
                "sonnx_import_s": round(t_import, 1),
                "sonnx_fwd_s": round(t_fwd, 2),
                "sonnx_vs_native_max_abs": diff,
                "gen_batch": B, "prompt": P, "new_tokens": N,
                "gen_first_call_s": round(t_first, 1),
                "gen_tokens_per_s": round(B * N / dt, 1),
                "gen_ms_per_token": round(dt / N * 1e3, 2)}

    gpt2()

    @stage("llama_generate", 240)
    def generate():
        tensor.set_seed(0)
        np.random.seed(0)
        cfg = models.LlamaConfig.tiny() if _SMOKE \
            else models.LlamaConfig.small()
        B, P, N = (2, 16, 8) if _SMOKE else (8, 128, 128)
        gm = models.Llama(cfg)
        gm.eval()
        prompt = np.random.randint(0, cfg.vocab_size, (B, P)).astype(np.int32)
        gm.compile([tensor.from_numpy(prompt)], is_train=False,
                   use_graph=True)
        pdt = None if _SMOKE else jnp.bfloat16   # bf16 weight reads
        t0 = time.monotonic()
        gm.generate(prompt, max_new_tokens=N, param_dtype=pdt)
        t_first = time.monotonic() - t0
        # median-of-3 (ADVICE r4: min was the most flattering statistic)
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = gm.generate(prompt, max_new_tokens=N, param_dtype=pdt)
            ts.append(time.perf_counter() - t0)
        dt = statistics.median(ts)
        assert out.shape == (B, P + N)
        assert len(gm._gen_sessions) == 1
        return {"batch": B, "prompt": P, "new_tokens": N,
                "first_call_s": round(t_first, 1),
                "tokens_per_s": round(B * N / dt, 1),
                "ms_per_token": round(dt / N * 1e3, 2),
                "ms_per_token_min": round(min(ts) / N * 1e3, 2)}

    generate()

    @stage("llama_moe", 300)
    def moe():
        # Mixtral-style MoE Llama with the r5 SCATTER dispatch (the
        # one-hot dispatch/combine einsums cost O(cf*k*N^2*D) MAC and
        # were the whole 0.16-MFU story in r4).  b8 x seq512 as in r4.
        return llama_run("small+flash+fused+moe4", True, True, True,
                      batch=8, seqlen=512, windows=3,
                      cfg_extra={"num_experts": 4})

    moe()

    @stage("llama_seq8k_banded_vs_dense", 480)
    def seq8k():
        """A shape where the banded kernel PAYS (VERDICT r4 item 5):
        seq 8192, sliding window 1024 — the banded flash path computes
        ~W/T of the dense attention work."""
        if _SMOKE:
            return {"skipped_smoke": True}
        dense = llama_run("small+flash+fused+seq8k", True, True, True,
                          batch=2, seqlen=8192, windows=3)
        banded = llama_run("small+flash+fused+seq8k+win1024", True, True,
                           True, batch=2, seqlen=8192, windows=3,
                           cfg_extra={"sliding_window": 1024})
        return {"dense_step_ms": dense["step_ms"],
                "banded_step_ms": banded["step_ms"],
                "banded_speedup": round(dense["step_ms"]
                                        / banded["step_ms"], 3)}

    seq8k()

    @stage("hostfed_input", 300)
    def hostfed():
        """Host-fed input pipeline on chip (VERDICT r4 item 6): the
        headline config trained from DataLoader batches prefetched to
        the device (64 KB int32 tokens/step) — step
        time must match the device-resident-synthetic headline."""
        from singa_tpu.utils.data import DataLoader, prefetch_to_device
        # fresh model at the headline config (compile is cache-warm):
        # decoupled from head_keep so earlier stages' donation or the
        # runtime's memory pressure can never invalidate this one
        m, ids, _cfg = llama_model(batch=2 if _SMOKE else 8,
                                   seqlen=64 if _SMOKE else 1024,
                                   base=True)
        m.compile([ids], is_train=True, use_graph=True)
        b, t = ids.shape
        rng = np.random.RandomState(1)
        xs = rng.randint(0, _cfg.vocab_size, (b * 64, t)).astype(np.int32)
        dl = DataLoader(xs, batch_size=b, shuffle=True, drop_last=True,
                        seed=0)

        def feed():
            while True:
                for xb, _ in dl:
                    yield xb

        it = prefetch_to_device(feed(), size=2)
        holder = {}

        def one():
            xb = next(it)
            holder["out"] = m.train_step(
                tensor.Tensor(data=xb, requires_grad=False))
            return holder["out"][-1].data

        _fetch(one())
        dt, stats = windowed_steps(one, windows=4, window_len=8, warmup=1,
                                   budget_left=left)
        head = _headline_step_ms()
        return {"step_ms": round(dt * 1e3, 2), "step_stats": stats,
                "synthetic_headline_step_ms": head,
                "ratio": round(dt * 1e3 / head, 3) if head else None}

    hostfed()

    @stage("llama_b16_scaling", 360)
    def b16_scaling():
        # batch scaling on the flagship: 2x tokens/step
        return llama_run("base09b+flash+fused+b16", True, True, True,
                      batch=16, windows=3, base=True)

    b16_scaling()

    @stage("llama_windowed", 240)
    def windowed():
        return llama_run("small+flash+fused+win256", True, True, True,
                      windows=3, cfg_extra={"sliding_window": 256}
                      if not _SMOKE else {"sliding_window": 16})

    windowed()

    @stage("llama_longseq", 300)
    def longseq():
        return llama_run("small+flash+fused+seq4k", True, True, True,
                      batch=4, seqlen=4096, windows=3)

    longseq()

    @stage("llama_noflash", 300)
    def noflash():
        return llama_run("base09b+xla_attn+fused", True, False, True,
                      batch=8, windows=3, base=True)

    noflash()

    @stage("llama_unfused", 300)
    def unfused():
        return llama_run("base09b+flash+unfused_loss", False, True, True,
                      batch=8, windows=3, base=True)

    unfused()

    @stage("llama_fwd_only", 240)
    def fwd_only():
        return llama_run("base09b+fwd+flash", True, True, False,
                      batch=8, windows=3, base=True)

    fwd_only()

    _write_perf_notes(dev_kind)
    _finish()


def _write_perf_notes(dev_kind) -> None:
    out = os.path.join(_DIR, "PERF_NOTES.md")
    if _smoke_like():
        # the r5 incident's second casualty: a CPU smoke session
        # overwrote the committed on-chip PERF_NOTES.md.  Smoke/CPU
        # sessions get their own file, unconditionally.
        out = os.path.join(_DIR, "PERF_NOTES.smoke.md")
    st = _RESULTS["stages"]

    def res(name):
        return (st.get(name) or {}).get("result") or {}

    # rows come from the RECORD (so ONLY-mode merge runs regenerate the
    # full table, not just the rerun stages), in a stable stage order
    order = ["llama_headline", "llama_small_continuity", "llama_moe",
             "llama_seq8k_banded_vs_dense", "llama_b16_scaling",
             "llama_windowed", "llama_longseq", "llama_noflash",
             "llama_unfused", "llama_fwd_only"]
    rows = []
    for name in order:
        r = res(name)
        if name == "llama_seq8k_banded_vs_dense":
            continue          # composite: summarized separately below
        if isinstance(r, dict) and "tag" in r:
            rows.append(r)
    if not rows:
        return

    lines = [
        "# PERF_NOTES — MFU gap analysis (tools/tpu_session.py)",
        "",
        f"Device: {dev_kind}; `base09b` = the 0.9B flagship "
        "(LlamaConfig.base), `small` = the 110M r1-r4 config; fused "
        "chunked CE unless noted, bf16; batch x seq per row.",
        "",
        "**Methodology (r5).** Step time = median over windows of 8 "
        "back-to-back dispatches, fenced (block_until_ready) at window "
        "ends — how a real training loop runs.  Per-step fencing pays "
        "a per-dispatch host latency that pipelined execution hides; "
        "the windowed number is cross-checked against K steps compiled "
        "into ONE lax.scan program (`llama_scan_steps_crosscheck`), "
        "which cannot pipeline or mis-fence anything.  The fenced "
        "per-dispatch medians stay in tpu_session.json as diagnostics "
        "(and are the number comparable to the r1-r4 records).",
        "",
        "| config | batch x seq | init s | compile s | step ms | tok/s | MFU | "
        "TFLOP/step | GB/step | roofline compute ms | roofline memory ms |",
        "|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| {r['tag']} | {r['batch']}x{r['seq']} | "
            f"{r['init_s']} | {r['compile_s']} | "
            f"{r['step_ms']} | {r['tokens_per_s']} | {r['mfu']} | "
            f"{r['compiled_tflops']} | {r['bytes_gb']} | "
            f"{r['roofline_compute_ms']} | {r['roofline_memory_ms']} |")
    by = {r["tag"]: r for r in rows}
    lines += ["", "## Reading", ""]
    h = by.get("base09b+flash+fused")
    sm = by.get("small+flash+fused")
    sc = res("llama_scan_steps_crosscheck")
    if h and sc.get("step_ms"):
        lines.append(
            f"- headline {h['step_ms']} ms/step (windowed) vs "
            f"{sc['step_ms']} ms/step for 8 steps in ONE compiled scan "
            f"program — agreement {sc.get('agreement')}; the windowed "
            "number is device time.  Fenced per-dispatch median: "
            f"{h['fenced_stats']['median']} ms (the difference is host "
            "dispatch overhead a training loop never pays).")
    mm = res("matmul_microbench")
    if mm:
        sp = mm.get("scan_proof") or {}
        lines.append(
            f"- matmul calibration: a model-shaped bf16 chain "
            f"({mm.get('shape')}) of {mm.get('true_tflop_per_call')} "
            f"TFLOP sustains {mm.get('sustained_tflops')} TFLOP/s "
            f"(MFU-equiv {mm.get('mfu_equiv')} of the quoted peak); "
            f"XLA cost_analysis reports {sp.get('cost_analysis_gflop')} "
            f"GFLOP for a 64-iteration scan that executes "
            f"{sp.get('true_gflop')} (body counted once) — why MFU "
            "here uses analytic/traced FLOPs.")
    rn = res("resnet50")
    if rn:
        lines.append(
            f"- ResNet-50 (LAYOUT CORRECTED r5 — r1-r4 fed NCHW into "
            f"the NHWC zoo and measured a mangled 0.83-GFLOP/img "
            f"network): true {rn.get('fwd_gflop_per_image_traced')} "
            f"GFLOP/img fwd traced; {rn.get('images_per_s')} img/s, "
            f"MFU {rn.get('mfu')}.")
    if sm:
        lines.append(
            f"- continuity row: the r1-r4 110M `small` config at the r5 "
            f"methodology runs {sm['step_ms']} ms/step, MFU {sm['mfu']} "
            "(the r4 committed 186.6 ms carried ~30 ms of dispatch "
            "overhead AND a ~19% FLOPs over-count from the embedding "
            "table).")
    nf = by.get("base09b+xla_attn+fused")
    uf = by.get("base09b+flash+unfused_loss")
    fw = by.get("base09b+fwd+flash")
    if h and nf:
        lines.append(f"- flash vs XLA attention: {nf['step_ms']} -> "
                     f"{h['step_ms']} ms/step.")
    if h and uf:
        lines.append(f"- fused vs unfused lm-head loss: {uf['step_ms']} -> "
                     f"{h['step_ms']} ms/step "
                     f"({uf['bytes_gb']} -> {h['bytes_gb']} GB accessed).")
    elif h and (st.get("llama_unfused") or {}).get("error", "").startswith(
            "JaxRuntimeError: RESOURCE_EXHAUSTED"):
        lines.append(
            "- unfused lm-head loss: RESOURCE_EXHAUSTED on the 0.9B "
            "flagship (the (B*T, V) logits + their gradient on top of "
            "the 7 GB f32 train state exceed HBM) — the chunked fused "
            "CE is not just faster, it is what makes this model "
            "trainable at b8 on one chip.")
    if h and fw:
        lines.append(f"- forward is {fw['step_ms']} ms of the "
                     f"{h['step_ms']} ms train step.")
    s8 = res("llama_seq8k_banded_vs_dense")
    if s8.get("banded_speedup"):
        lines.append(
            f"- seq-8192: banded flash (W=1024) {s8['banded_step_ms']} ms "
            f"vs dense {s8['dense_step_ms']} ms — "
            f"{s8['banded_speedup']}x; the first committed shape where "
            "the banded kernel pays.")
    ls = by.get("small+flash+fused+seq4k")
    if ls:
        lines.append(
            f"- long context: seq {ls['seq']} (batch {ls['batch']}) runs "
            f"{ls['step_ms']} ms/step, {ls['tokens_per_s']} tok/s, MFU "
            f"{ls['mfu']}.")
    hf = res("hostfed_input")
    if hf.get("ratio"):
        lines.append(
            f"- host-fed input pipeline: {hf['step_ms']} ms/step from "
            f"DataLoader+prefetch_to_device vs {hf['synthetic_headline_step_ms']} "
            f"synthetic (ratio {hf['ratio']}) — the 64 KB/step token "
            "stream hides under compute.")
    b16 = by.get("base09b+flash+fused+b16")
    if h and b16:
        lines.append(
            f"- batch {b16['batch']} vs {h['batch']}: MFU {h['mfu']} -> "
            f"{b16['mfu']} ({h['tokens_per_s']} -> {b16['tokens_per_s']} "
            "tok/s).")
    if h:
        bound = max(h["roofline_compute_ms"], h["roofline_memory_ms"])
        ceil = (h["roofline_compute_ms"] / bound) if bound else None
        lines.append(f"- roofline (cost-analysis basis): step >= "
                     f"max(compute {h['roofline_compute_ms']} ms, memory "
                     f"{h['roofline_memory_ms']} ms); ceiling MFU "
                     f"{round(ceil, 4) if ceil else '?'} — achieved "
                     f"{h.get('mfu_cost_analysis')} (analytic-basis "
                     f"achieved: {h['mfu']}).  NOTE the CA bytes also "
                     "count scan bodies once, so the memory roofline is "
                     "a lower bound on true traffic.")
    lines += ["", "(Regenerate with `python tools/tpu_session.py` on the "
              "chip; raw JSON in tpu_session.json.)"]
    with open(out, "w") as f:
        f.write("\n".join(lines) + "\n")
    mark(f"wrote {os.path.abspath(out)}")


def _smoke_like() -> bool:
    """Smoke mode, a probe that resolved to CPU, or a probe that never
    ran at all: either way this run carries no on-chip evidence and
    must not displace (or shadow, via the store) any."""
    platform = _RESULTS.get("platform")
    return _SMOKE or platform is None or platform == "cpu"


def _merge_source_path() -> str:
    """The record an ONLY-mode rerun merges FROM — decided by mode
    alone, valid before the probe has stamped a platform."""
    if _SMOKE:
        return os.path.join(_DIR, "tpu_session.smoke.json")
    return os.path.join(_DIR, "tpu_session.json")


def _merge_only_results(path: str) -> None:
    """Merge a previous record's STAGES into this run (ONLY mode),
    stripping the merged record's run-identity metadata: platform,
    device, etc. must be re-established by THIS run's probe.  Otherwise
    a rerun whose probe fails would inherit platform='tpu' from the
    merged record, _smoke_like() would read False, and _finish would
    overwrite the on-chip record and append a falsified non-smoke
    store entry for a run that never touched a chip."""
    try:
        with open(path) as f:
            _RESULTS.update(json.load(f))
    except Exception:
        pass
    for k in ("schema_version", "run_id", "kind", "platform", "smoke",
              "device", "created_at"):
        _RESULTS.pop(k, None)


def _session_json_path() -> str:
    """Where this run's session snapshot goes.

    The round-5 data loss: a CPU smoke session's ``_finish()``
    unconditionally overwrote ``tpu_session.json``, destroying the
    on-chip record.  Now smoke runs ALWAYS write
    ``tpu_session.smoke.json``; a non-smoke run that resolved to CPU
    writes ``tpu_session.cpu.json`` whenever the existing
    ``tpu_session.json`` looks on-chip (legacy records included —
    inference via obs.record.is_onchip_session_doc)."""
    base = os.path.join(_DIR, "tpu_session.json")
    if _SMOKE:
        return os.path.join(_DIR, "tpu_session.smoke.json")
    if _smoke_like():
        # non-smoke run with no on-chip evidence (CPU probe, or probe
        # never ran): preserve an existing on-chip record
        try:
            with open(base) as f:
                existing = json.load(f)
        except Exception:
            existing = None
        from singa_tpu.obs import record as obs_record
        if obs_record.is_onchip_session_doc(existing):
            return os.path.join(_DIR, "tpu_session.cpu.json")
    return base


def _finish(final: bool = True) -> None:
    from singa_tpu.obs import record as obs_record

    # 1. the durable store: one schema-validated entry per run, keyed
    #    (run_id, platform, smoke) — incremental _finish calls supersede
    #    this run's OWN line only; other runs' lines are preserved
    #    byte-for-byte, so a smoke session structurally cannot damage an
    #    on-chip entry
    platform = _RESULTS.get("platform") or ("cpu" if _SMOKE else "unknown")
    try:
        entry = obs_record.new_entry(
            "session", platform, _smoke_like(),
            str(_RESULTS.get("device", "")), run_id=_RUN_ID,
            stages=_RESULTS["stages"])
        obs_record.RunRecord(
            os.path.join(_DIR, obs_record.DEFAULT_STORE)).append(entry)
    except Exception as e:  # noqa: BLE001 - the snapshot below still lands
        mark(f"store append failed: {type(e).__name__}: {e}")

    # 2. the legacy single-doc snapshot (what bench.py and the README
    #    generator read), smoke-guarded via _session_json_path and
    #    written atomically (temp + rename) like the store
    path = _session_json_path()
    doc = dict(_RESULTS)
    doc["schema_version"] = 1
    doc["run_id"] = _RUN_ID
    doc["kind"] = "session"
    doc["platform"] = platform
    doc["smoke"] = _smoke_like()
    doc["device"] = str(_RESULTS.get("device", ""))
    doc["created_at"] = _STARTED_AT
    tmp = path + f".tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    if final:
        mark(f"session end; results in {os.path.abspath(path)}")


if __name__ == "__main__":
    main()
