"""Shared helpers for the example scripts (reference examples/ layout,
BASELINE.json:7-11).

Datasets: each loader first looks for a local .npz (this image has no
network egress, so no downloads); otherwise it falls back to a
deterministic synthetic set with the same shapes, which keeps every
script runnable end-to-end anywhere."""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def _pin_cpu_backend_if_requested():
    """`--device cpu` must take effect before any JAX backend initializes
    (a CPU run must not take the chip)."""
    if "--device" in sys.argv:
        i = sys.argv.index("--device")
        if i + 1 < len(sys.argv) and sys.argv[i + 1] == "cpu":
            import jax
            jax.config.update("jax_platforms", "cpu")


_pin_cpu_backend_if_requested()

import singa_tpu as singa  # noqa: E402
from singa_tpu.utils.data import DataLoader, synthetic_dataset


def dataset_arrays(name: str, data_dir: str = "", n_synth: int = 2048):
    """Return (x_train, y_train, x_test, y_test, num_classes, input_shape).

    Real data: `<data_dir>/<name>.npz` with arrays x_train/y_train/
    x_test/y_test (images in NHWC float32 [0,1] or uint8)."""
    shapes = {
        "mnist": ((28, 28, 1), 10),
        "cifar10": ((32, 32, 3), 10),
        "cifar100": ((32, 32, 3), 100),
        "imagenet": ((224, 224, 3), 1000),
    }
    if name not in shapes:
        raise ValueError(f"unknown dataset {name}; options: {sorted(shapes)}")
    shape, classes = shapes[name]
    path = os.path.join(data_dir or ".", f"{name}.npz")
    if data_dir and os.path.exists(path):
        z = np.load(path)
        xt = z["x_train"].astype(np.float32)
        if xt.max() > 2.0:
            xt = xt / 255.0
        xe = z["x_test"].astype(np.float32)
        if xe.max() > 2.0:
            xe = xe / 255.0
        if xt.ndim == 3:
            xt, xe = xt[..., None], xe[..., None]
        return (xt, z["y_train"].astype(np.int32),
                xe, z["y_test"].astype(np.int32), classes, shape)
    n_test = max(256, n_synth // 8)
    x, y = synthetic_dataset("images", n=n_synth + n_test, classes=classes,
                             shape=shape)
    return (x[:n_synth], y[:n_synth], x[n_synth:], y[n_synth:], classes, shape)


def base_parser(desc: str) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=desc)
    p.add_argument("--device", default="auto",
                   choices=["auto", "cpu", "tpu"],
                   help="the reference's one-line device change "
                        "(BASELINE.json:5)")
    p.add_argument("--data-dir", default="", help="dir with <dataset>.npz")
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--graph", action=argparse.BooleanOptionalAction,
                   default=True, help="compiled graph mode vs eager")
    p.add_argument("--dist", action="store_true",
                   help="data-parallel over all local devices via DistOpt")
    p.add_argument("--bf16", action="store_true", help="bfloat16 inputs")
    p.add_argument("--log-every", type=int, default=20)
    return p


def make_device(kind: str):
    """`--device tpu` means the TPU: fail rather than train on whatever
    backend came up instead."""
    dev = singa.device.create_device(kind)
    if kind == "tpu" and not dev.is_tpu:
        raise SystemExit(
            f"--device tpu, but jax resolved to "
            f"platform={dev.jax_devices[0].platform}")
    return dev


def train_classifier(model, args, x_train, y_train, x_test, y_test,
                     opt_factory=None):
    """The canonical reference training loop (examples/cnn/train.py
    shape): compile once, train_one_batch per step, eval per epoch."""
    from singa_tpu import opt as opt_mod
    from singa_tpu import parallel
    from singa_tpu.tensor import Tensor
    from singa_tpu.utils import metrics

    dev = make_device(args.device)
    singa.device.set_default_device(dev)
    base = (opt_factory() if opt_factory
            else opt_mod.SGD(lr=args.lr, momentum=0.9, weight_decay=1e-4))
    if args.dist:
        parallel.set_mesh(parallel.data_parallel_mesh())
        sgd = opt_mod.DistOpt(base)
    else:
        sgd = base
    model.set_optimizer(sgd)

    if args.bf16:
        import ml_dtypes
        dtype = ml_dtypes.bfloat16
    else:
        dtype = np.float32
    tx = Tensor(data=x_train[:args.batch_size].astype(dtype), device=dev)
    ty = Tensor(data=y_train[:args.batch_size].astype(np.int32), device=dev)
    model.compile([tx], is_train=True, use_graph=args.graph)

    loader = DataLoader(x_train, y_train, batch_size=args.batch_size,
                        drop_last=True)
    tput = metrics.Throughput()
    for epoch in range(args.epochs):
        model.train()
        acc = metrics.Accuracy()
        loss_m = metrics.MeanMeter()
        t0 = time.perf_counter()
        for step, (xb, yb) in enumerate(loader):
            tx.copy_from(xb.astype(dtype))
            ty.copy_from(yb.astype(np.int32))
            out, loss = model.train_one_batch(tx, ty)
            loss_m.update(float(np.asarray(loss.data)))
            acc.update(np.asarray(out.data), yb)
            tput.update(len(xb))
            if args.log_every and step % args.log_every == 0:
                print(f"epoch {epoch} step {step:4d} "
                      f"loss {loss_m.value:.4f} acc {acc.value:.4f}")
        dt = time.perf_counter() - t0
        test_acc = evaluate(model, x_test, y_test, args.batch_size, dev)
        print(f"epoch {epoch}: train loss {loss_m.value:.4f} "
              f"acc {acc.value:.4f}  test acc {test_acc:.4f}  "
              f"({len(x_train) / dt:.0f} imgs/s)")
    return model


def evaluate(model, x_test, y_test, batch_size, dev) -> float:
    from singa_tpu.tensor import Tensor
    from singa_tpu.utils import metrics

    model.eval()
    acc = metrics.Accuracy()
    tx = None
    for s in range(0, len(x_test) - batch_size + 1, batch_size):
        xb = x_test[s:s + batch_size].astype(np.float32)
        if tx is None:
            tx = Tensor(data=xb, device=dev)
        else:
            tx.copy_from(xb)
        out = model(tx)
        acc.update(np.asarray(out.data), y_test[s:s + batch_size])
    return acc.value
