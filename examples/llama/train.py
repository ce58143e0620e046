"""examples/llama — Llama-3 training with multi-axis GSPMD sharding
(BASELINE.json:11: "Llama-3-8B ... sharded across a v4-32 pod, stretch
goal").

Parallelism is declared as a mesh (DP x TP x SP); the graph executor
shards params/batch by the model's SHARD_RULES and XLA inserts the
collectives over ICI.  On a CPU box, `--force-host-devices 8` builds a
virtual 8-device mesh so the full sharded step compiles and runs.

    python examples/llama/train.py --preset tiny --dp 2 --tp 2 --sp 2 \
        --force-host-devices 8
    python examples/llama/train.py --preset 8b --dp 4 --tp 8   # pod slice
"""

import argparse
import time

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np  # noqa: E402

# importing common pins the cpu backend when --device cpu was passed
import common  # noqa: E402,F401


def main():
    p = argparse.ArgumentParser(description="Llama training (GSPMD sharded)")
    p.add_argument("--device", default="auto", choices=["auto", "cpu", "tpu"],
                   help="cpu pins the host backend before JAX init; tpu "
                        "fails unless jax resolved to a TPU")
    p.add_argument("--preset", default="tiny", choices=["tiny", "small", "8b"])
    p.add_argument("--dp", type=int, default=1, help="data-parallel ways")
    p.add_argument("--tp", type=int, default=1, help="tensor-parallel ways")
    p.add_argument("--sp", type=int, default=1, help="sequence-parallel ways")
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline-parallel stages (GPipe over the "
                        "'pipe' mesh axis; layers must divide evenly)")
    p.add_argument("--micro", type=int, default=0,
                   help="pipeline microbatches (default: = --pp)")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--lr", type=float, default=None,
                   help="default 3e-4; with --opt adafactor, unset "
                        "means the relative-step schedule")
    p.add_argument("--force-host-devices", type=int, default=0,
                   help="virtual CPU devices for meshes without hardware")
    p.add_argument("--window", type=int, default=0,
                   help="sliding-window attention (Mistral-style; "
                        "chunked O(T*W) path for long sequences)")
    p.add_argument("--experts", type=int, default=0,
                   help="Mixtral-style MoE: SwiGLU experts per block "
                        "(use with --ep ways via the 'expert' axis)")
    p.add_argument("--ep", type=int, default=1, help="expert-parallel ways")
    p.add_argument("--opt", default="adamw",
                   choices=["adamw", "adafactor", "sgd"],
                   help="adafactor = factored second moment (r+c floats "
                        "per matrix instead of r*c) with relative step "
                        "size — the big-model TPU recipe")
    p.add_argument("--int8-ring", action="store_true",
                   help="int8-ring quantized gradient sync with error "
                        "feedback (DistOpt compression='int8_ring'; "
                        "pays off on slow inter-host links — see "
                        "docs/parallelism.md)")
    p.add_argument("--zero1", action="store_true",
                   help="ZeRO-1 weight-update sharding: optimizer "
                        "moments sharded over the data axis (1/N HBM)")
    p.add_argument("--fused-loss", action="store_true",
                   help="chunked fused lm-head+CE (no (B*T,V) logits; "
                        "train_one_batch returns (loss, loss))")
    p.add_argument("--plan", action="store_true",
                   help="shape-only capacity plan (no weights allocated): "
                        "per-device param/moment/grad bytes + HBM fit")
    p.add_argument("--generate", type=int, default=0, metavar="N",
                   help="after training, greedy-generate N tokens with "
                        "the KV cache")
    args = p.parse_args()

    if args.force_host_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.force_host_devices}"
        ).strip()
        import jax
        jax.config.update("jax_platforms", "cpu")

    from singa_tpu import device, models, opt, parallel, tensor

    device.set_default_device(common.make_device(args.device))

    presets = {
        "tiny": models.LlamaConfig.tiny,
        "small": models.LlamaConfig.small,
        "8b": models.LlamaConfig.llama3_8b,
    }
    cfg = presets[args.preset]()
    if args.fused_loss:
        cfg.fused_loss = True
    if args.pp > 1:
        cfg.pipeline_stages = args.pp
        cfg.pipeline_microbatches = args.micro
    if args.window:
        if args.window < 1:
            p.error(f"--window must be positive, got {args.window}")
        if args.sp > 1:
            p.error("--window does not compose with --sp (ring attention)")
        cfg.sliding_window = args.window
    if args.experts:
        cfg.num_experts = args.experts
        cfg.moe_top_k = min(cfg.moe_top_k, args.experts)
    if args.ep > 1:
        if not args.experts:
            p.error("--ep needs --experts (an 'expert' axis with no MoE "
                    "replicates weights and wastes devices)")
        if args.experts % args.ep:
            p.error(f"--experts {args.experts} must divide by --ep "
                    f"{args.ep} (otherwise expert weights silently "
                    "replicate instead of sharding)")

    axes = {k: v for k, v in
            (("data", args.dp), ("model", args.tp), ("seq", args.sp),
             ("pipe", args.pp), ("expert", args.ep))
            if v > 1} or {"data": 1}
    mesh = parallel.make_mesh(axes)
    parallel.set_mesh(mesh)
    print(f"mesh axes: {axes}  devices: {mesh.devices.size}")

    if args.plan:
        import jax
        import jax.numpy as jnp
        plan_lr = 3e-4 if args.lr is None else args.lr
        plan_opt = (opt.DistOpt(opt.AdamW(lr=plan_lr),
                                shard_weight_update=True)
                    if args.zero1 else opt.AdamW(lr=plan_lr))
        plan = parallel.plan_train_step(
            models.Llama(cfg), plan_opt,
            (jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32),),
            mesh=mesh)
        gib = 2.0 ** 30
        print(f"params (global):     {plan.param_bytes_global / gib:8.2f} GiB")
        print(f"params / device:     {plan.param_bytes_per_device / gib:8.2f} GiB")
        print(f"moments / device:    {plan.slot_bytes_per_device / gib:8.2f} GiB")
        print(f"grads / device:      {plan.grad_bytes_per_device / gib:8.2f} GiB")
        print(f"state / device:      {plan.per_device_state_bytes / gib:8.2f} GiB")
        for chip in ("v4", "v5e", "v5p"):
            print(f"fits {chip:4s} (75% HBM): {plan.fits(chip)}")
        parallel.set_mesh(None)
        return

    tensor.set_seed(0)
    m = models.Llama(cfg)
    lr = 3e-4 if args.lr is None else args.lr
    base_opt = {"adamw": lambda: opt.AdamW(lr=lr),
                # explicit --lr overrides adafactor's relative step
                "adafactor": lambda: opt.Adafactor(lr=args.lr),
                "sgd": lambda: opt.SGD(lr=lr, momentum=0.9),
                }[args.opt]()
    m.set_optimizer(opt.DistOpt(
        base_opt, shard_weight_update=args.zero1,
        compression="int8_ring" if args.int8_ring else None))
    vocab = min(cfg.vocab_size, 32000)
    ids_np = np.random.RandomState(0).randint(
        0, vocab, (args.batch, args.seq)).astype(np.int32)
    ids = tensor.from_numpy(ids_np)
    print(f"params: {m.num_params() / 1e6:.1f}M; compiling sharded step ...")
    m.compile([ids], is_train=True, use_graph=True)

    flops_step = m.flops_per_token(args.seq) * args.batch * args.seq
    for step in range(args.steps):
        t0 = time.perf_counter()
        _, loss = m.train_step(ids)
        lv = float(np.asarray(loss.data))
        dt = time.perf_counter() - t0
        tok_s = args.batch * args.seq / dt
        print(f"step {step}: loss {lv:.4f}  {tok_s:,.0f} tok/s  "
              f"{flops_step / dt / 1e12:.2f} TFLOP/s")

    if args.generate:
        # KV-cache decoding: compiled prefill + one compiled decode step
        parallel.set_mesh(None)
        prompt = ids_np[:1, : min(8, args.seq)]
        m.generate(prompt, args.generate)     # warm: compile prefill+decode
        t0 = time.perf_counter()
        out = m.generate(prompt, args.generate)
        dt = time.perf_counter() - t0
        print(f"generated {args.generate} tokens "
              f"({args.generate / dt:.1f} tok/s, cached decode): "
              f"{out[0, prompt.shape[1]:].tolist()}")

    parallel.set_mesh(None)


if __name__ == "__main__":
    main()
