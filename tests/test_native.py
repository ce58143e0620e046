"""Native runtime tests: tensor_math_cpp kernels vs numpy, scheduler
topo-sort/memory planning, threaded data loader, staging pool."""

import os

import numpy as np
import pytest

from singa_tpu import _core

pytestmark = pytest.mark.skipif(not _core.available(),
                                reason="native core unavailable")


def test_version():
    assert "singa_core" in _core.version()


def test_gemm_matches_numpy():
    rng = np.random.RandomState(0)
    a = rng.randn(37, 53).astype(np.float32)
    b = rng.randn(53, 29).astype(np.float32)
    np.testing.assert_allclose(_core.gemm(a, b), a @ b, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(_core.gemm(a, a, transb=True), a @ a.T,
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(_core.gemm(a, a, transa=True), a.T @ a,
                               rtol=1e-5, atol=1e-4)


def test_elementwise_and_activations():
    rng = np.random.RandomState(1)
    a = rng.randn(1000).astype(np.float32)
    b = rng.randn(1000).astype(np.float32)
    np.testing.assert_allclose(_core.add(a, b), a + b, rtol=1e-6)
    np.testing.assert_allclose(_core.mul(a, b), a * b, rtol=1e-6)
    np.testing.assert_allclose(_core.relu(a), np.maximum(a, 0), rtol=1e-6)
    np.testing.assert_allclose(_core.sigmoid(a), 1 / (1 + np.exp(-a)), rtol=1e-5)
    np.testing.assert_allclose(_core.tanh(a), np.tanh(a), rtol=1e-5)
    s = _core.softmax(a.reshape(10, 100))
    e = np.exp(a.reshape(10, 100) - a.reshape(10, 100).max(1, keepdims=True))
    np.testing.assert_allclose(s, e / e.sum(1, keepdims=True), rtol=1e-5)
    assert _core.array_sum(a) == pytest.approx(a.sum(), rel=1e-4)


def test_conv2d_matches_jax():
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(2)
    x = rng.randn(2, 8, 8, 3).astype(np.float32)
    w = rng.randn(3, 3, 3, 5).astype(np.float32)
    got = _core.conv2d_nhwc(x, w, (2, 2), (1, 1))
    ref = jax.lax.conv_general_dilated(
        jnp.asarray(x), jnp.asarray(w), (2, 2), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_sgd_update_inplace():
    p = np.ones(10, np.float32)
    g = np.full(10, 0.5, np.float32)
    m = np.zeros(10, np.float32)
    _core.sgd_update(p, g, m, lr=0.1, momentum=0.9)
    np.testing.assert_allclose(p, 1.0 - 0.05, rtol=1e-6)
    np.testing.assert_allclose(m, 0.5, rtol=1e-6)


def test_scheduler_toposort_and_memory():
    g = _core.NativeGraph()
    # diamond: a -> b, c -> d ; buffers 0..4
    g.add_node("a", [0], [1], [256])
    g.add_node("b", [1], [2], [256])
    g.add_node("c", [1], [3], [256])
    g.add_node("d", [2, 3], [4], [256])
    order = g.toposort()
    assert order.index(0) < order.index(1) < order.index(3)
    assert order.index(0) < order.index(2) < order.index(3)
    arena, offsets = g.plan_memory()
    assert arena > 0
    # buffer 4 can reuse the arena slot of a dead buffer: arena must be
    # smaller than sum of all buffers (5*256 aligned)
    assert arena < 5 * 256
    assert set(offsets) >= {1, 2, 3, 4}


def test_scheduler_cycle_detection():
    g = _core.NativeGraph()
    g.add_node("a", [1], [0], [64])   # consumes b's output
    g.add_node("b", [0], [1], [64])   # consumes a's output -> cycle
    with pytest.raises(ValueError):
        g.toposort()


def test_native_loader_epoch():
    rng = np.random.RandomState(3)
    x = rng.randn(100, 4).astype(np.float32)
    y = np.arange(100, dtype=np.int32)
    ld = _core.NativeLoader(x, y, batch=32, shuffle=True, seed=7)
    assert ld.batches_per_epoch == 4
    seen = []
    for _ in range(4):
        bx, by = ld.next()
        assert bx.shape[1:] == (4,)
        seen.extend(by.tolist())
    assert sorted(seen) == list(range(100))  # full epoch, no dup/loss
    # samples must match their labels after shuffling
    for i, lab in enumerate(by):
        np.testing.assert_array_equal(bx[i], x[lab])
    ld.close()


def test_native_loader_multiworker_stress():
    """Regression: lost-wakeup deadlock with workers>ring and multi-epoch
    consistency under concurrent assembly (review finding)."""
    rng = np.random.RandomState(4)
    x = rng.randn(1000, 1).astype(np.float32)
    y = np.arange(1000, dtype=np.int32)
    ld = _core.NativeLoader(x, y, batch=32, shuffle=True, seed=0,
                            workers=4, prefetch=4)
    for epoch in range(3):
        seen = []
        for _ in range(ld.batches_per_epoch):
            bx, by = ld.next()
            seen.extend(by.tolist())
            np.testing.assert_array_equal(bx[:, 0], x[by, 0])
        assert sorted(seen) == list(range(1000)), f"epoch {epoch} incomplete"
    ld.close()


def test_dataloader_api_native_and_fallback():
    from singa_tpu.utils.data import DataLoader
    x = np.random.randn(50, 3).astype(np.float32)
    y = np.arange(50, dtype=np.int32)
    for use_native in (True, False):
        dl = DataLoader(x, y, batch_size=16, seed=1, use_native=use_native)
        got = []
        for bx, by in dl:
            got.extend(by.tolist())
        assert sorted(got) == list(range(50))
        dl.close()


def test_pool_allocator():
    l = _core.lib()
    p = l.sg_pool_alloc(1000)
    assert p
    used0 = l.sg_pool_bytes_in_use()
    l.sg_pool_free(p)
    assert l.sg_pool_bytes_in_use() < used0
    # reuse same bucket
    p2 = l.sg_pool_alloc(1000)
    assert p2 == p
    l.sg_pool_free(p2)


def test_native_dispatch_in_autograd():
    """CppCPU(use_native=True) routes hot ops through tensor_math_cpp and
    still produces correct gradients."""
    from singa_tpu import autograd, device, tensor
    dev = device.create_cpu_device(use_native=True)
    device.set_default_device(dev)
    autograd.set_training(True)
    rng = np.random.RandomState(0)
    A = rng.randn(8, 8).astype(np.float32)
    W = tensor.Tensor(data=rng.randn(8, 4).astype(np.float32), device=dev,
                      requires_grad=True, stores_grad=True)
    x = tensor.from_numpy(A, dev)
    y = autograd.relu(autograd.matmul(x, W))
    loss = autograd.reduce_sum(y)
    grads = autograd.backward(loss)
    # reference gradient via numpy
    pre = A @ W.to_numpy()
    gw = A.T @ (np.ones_like(pre) * (pre > 0))
    np.testing.assert_allclose(grads[0][1].to_numpy(), gw, rtol=1e-4, atol=1e-4)


def test_captured_graph_native_schedule():
    from singa_tpu import autograd, device, layer, model, opt, tensor

    class M(model.Model):
        def __init__(self):
            super().__init__()
            self.fc = layer.Linear(8)

        def forward(self, x):
            return self.fc(x)

        def train_one_batch(self, x, y):
            out = self.forward(x)
            loss = autograd.mse_loss(out, y)
            self.optimizer.backward_and_update(loss)
            return out, loss

    m = M()
    m.set_optimizer(opt.SGD(lr=0.1))
    x = tensor.from_numpy(np.random.randn(4, 6).astype(np.float32))
    y = tensor.from_numpy(np.random.randn(4, 8).astype(np.float32))
    m.compile([x], is_train=True, use_graph=True)
    m.train_step(x, y)
    sched = m.graph.schedule()
    assert sched.num_nodes > 5
    assert sched.arena_bytes > 0
    assert len(sched.order) == sched.num_nodes


def test_native_default_and_exercised():
    """use_native defaults on for CppCPU; an eager model step actually
    hits csrc kernels (counter) and matches the pure-XLA path
    (VERDICT r2 item 8)."""
    from singa_tpu import device, models, opt, tensor

    def run(use_native):
        dev = device.create_cpu_device(use_native=use_native)
        device.set_default_device(dev)
        tensor.set_seed(0)
        np.random.seed(0)
        m = models.MLP(perceptron_size=16, num_classes=4)
        m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
        x = tensor.from_numpy(np.random.RandomState(1).randn(8, 10).astype(np.float32))
        y = tensor.from_numpy(np.random.RandomState(2).randint(0, 4, 8).astype(np.int32))
        m.compile([x], is_train=True, use_graph=False)   # eager path
        losses = [float(m.train_step(x, y)[1].to_numpy()) for _ in range(3)]
        return losses

    assert device.create_cpu_device().use_native is True
    _core.reset_stats()
    native_losses = run(True)
    assert _core.stats["calls"] > 0, "csrc kernels were never dispatched"
    _core.reset_stats()
    xla_losses = run(False)
    assert _core.stats["calls"] == 0
    np.testing.assert_allclose(native_losses, xla_losses, rtol=1e-4, atol=1e-5)


class TestScheduleReplay:
    """Schedule.replay consumes the native topo order + arena plan
    (single-threaded deterministic host replay, SURVEY.md §5)."""

    def _jaxpr_graph(self):
        import jax
        import jax.numpy as jnp
        from singa_tpu.graph import CapturedGraph

        def step(w1, b1, w2, x):
            h = jnp.tanh(x @ w1 + b1)
            o = jax.nn.sigmoid(h) * h
            return (o @ w2).sum(), o

        rng = np.random.RandomState(0)
        args = (rng.randn(16, 32).astype(np.float32),
                rng.randn(32).astype(np.float32),
                rng.randn(32, 4).astype(np.float32),
                rng.randn(8, 16).astype(np.float32))
        cj = jax.make_jaxpr(step)(*args)
        return CapturedGraph("t", jaxpr=cj), step, args

    def test_replay_matches_direct(self):
        g, step, args = self._jaxpr_graph()
        s = g.schedule()
        outs = s.replay(*args)
        for got, ref in zip(outs, step(*args)):
            np.testing.assert_allclose(got, np.asarray(ref),
                                       rtol=1e-5, atol=1e-6)
        assert s.native_hits >= 4, "hot ops should hit csrc kernels"

    def test_replay_without_native_kernels(self):
        g, step, args = self._jaxpr_graph()
        s = g.schedule()
        outs = s.replay(*args, use_native=False)
        assert s.native_hits == 0
        for got, ref in zip(outs, step(*args)):
            np.testing.assert_allclose(got, np.asarray(ref),
                                       rtol=1e-5, atol=1e-6)

    def test_replay_model_train_step_graph(self):
        """Replay the REAL captured train-step jaxpr of a compiled model
        and reproduce the jitted loss."""
        import jax
        from singa_tpu import autograd, layer, model, opt, tensor

        class M(model.Model):
            def __init__(self):
                super().__init__()
                self.fc = layer.Linear(8)

            def forward(self, x):
                return self.fc(x)

            def train_one_batch(self, x, y):
                out = self.forward(x)
                loss = autograd.mse_loss(out, y)
                self.optimizer.backward_and_update(loss)
                return out, loss

        tensor.set_seed(0)
        m = M()
        m.set_optimizer(opt.SGD(lr=0.1))
        x = tensor.from_numpy(np.random.RandomState(3).randn(4, 6).astype(np.float32))
        y = tensor.from_numpy(np.random.RandomState(4).randn(4, 8).astype(np.float32))
        m.compile([x], is_train=True, use_graph=True)
        m.train_step(x, y)                 # create the executor + graph
        ex = next(iter(m._executors.values()))
        # numpy snapshots: the jitted step donates its inputs
        params_np = {n: np.asarray(t.data)
                     for n, t in ex.param_tensors.items()}
        slots_np = jax.tree.map(np.asarray, ex.slots)
        import jax.numpy as jnp
        step0 = np.zeros((), np.int32)
        rng = np.asarray(jax.random.fold_in(m._base_key, 1))
        out_jit, _, _, _ = ex._jitted(
            jax.tree.map(jnp.array, params_np), {},
            jax.tree.map(jnp.array, slots_np),
            jnp.array(step0), jnp.array(rng),
            jnp.array(x.data), jnp.array(y.data))
        sched = m.graph.schedule()
        flat, _ = jax.tree.flatten(
            (params_np, {}, slots_np, step0, rng,
             (np.asarray(x.data), np.asarray(y.data))))
        outs = sched.replay(*flat)
        # first replay outputs correspond to the step outputs (out, loss)
        loss_jit = float(np.asarray(out_jit[1]))
        loss_replay = float(outs[1])
        np.testing.assert_allclose(loss_replay, loss_jit, rtol=1e-4, atol=1e-5)


def test_native_core_under_asan():
    """Build csrc under ASan+UBSan and run the native test binary
    (SURVEY.md §5 sanitizer plan; VERDICT r2 item 8)."""
    import shutil
    import subprocess

    if shutil.which("g++") is None or shutil.which("make") is None:
        pytest.skip("native toolchain unavailable")
    csrc = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "..", "csrc")
    r = subprocess.run(["make", "-C", csrc, "asan"], capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-1500:]
    r = subprocess.run([os.path.join(csrc, "test_core_asan")],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, (r.stdout + r.stderr)[-1500:]
    assert "ALL NATIVE TESTS PASSED" in r.stdout


class TestCExtensionBinding:
    """CPython C-API binding (csrc/py_ext.cc): zero-copy buffer-protocol
    kernels matching numpy, preferred by the _core wrappers (SURVEY §2.2
    row 5)."""

    def test_ext_builds_and_loads(self):
        e = _core.ext()
        assert e is not None, "singa_core_ext failed to build/import"
        assert "singa_core" in e.version()

    def test_ext_kernels_match_numpy(self):
        e = _core.ext()
        if e is None:
            pytest.skip("extension unavailable")
        rng = np.random.RandomState(0)
        a = rng.randn(16, 8).astype(np.float32)
        b = rng.randn(8, 12).astype(np.float32)
        out = np.zeros((16, 12), np.float32)
        e.gemm(a, b, out, 16, 8, 12, False, False)
        np.testing.assert_allclose(out, a @ b, rtol=1e-5, atol=1e-5)
        o = np.empty(a.size, np.float32)
        e.relu(a.reshape(-1), o)
        np.testing.assert_array_equal(o, np.maximum(a.reshape(-1), 0))
        sm = np.empty_like(a)
        e.softmax(a, sm, 16, 8)
        ref = np.exp(a - a.max(1, keepdims=True))
        np.testing.assert_allclose(sm, ref / ref.sum(1, keepdims=True),
                                   rtol=1e-5)
        p = np.ones(10, np.float32)
        g = np.full(10, 0.5, np.float32)
        m = np.zeros(10, np.float32)
        e.sgd_update(p, g, m, 0.1, 0.9, 0.0)
        np.testing.assert_allclose(p, 0.95, rtol=1e-6)

    def test_ext_rejects_bad_buffers(self):
        e = _core.ext()
        if e is None:
            pytest.skip("extension unavailable")
        f64 = np.zeros(4, np.float64)
        out = np.zeros(4, np.float32)
        with pytest.raises(TypeError):
            e.relu(f64, out)
        with pytest.raises(ValueError):
            e.add(np.zeros(4, np.float32), np.zeros(3, np.float32), out)

    def test_wrappers_route_through_ext(self):
        if _core.ext() is None:
            pytest.skip("extension unavailable")
        rng = np.random.RandomState(1)
        a = rng.randn(64).astype(np.float32)
        b = rng.randn(64).astype(np.float32)
        np.testing.assert_allclose(_core.add(a, b), a + b, rtol=1e-6)
        np.testing.assert_allclose(_core.gemm(a.reshape(8, 8),
                                              b.reshape(8, 8)),
                                   a.reshape(8, 8) @ b.reshape(8, 8),
                                   rtol=1e-5, atol=1e-5)

    def test_ext_gemm_rejects_inconsistent_dims(self):
        e = _core.ext()
        if e is None:
            pytest.skip("extension unavailable")
        with pytest.raises(ValueError, match="inconsistent"):
            e.gemm(np.zeros(4, np.float32), np.zeros(4, np.float32),
                   np.zeros((8, 8), np.float32), 8, 8, 8, False, False)


class TestPjrtTouchpoint:
    """Native TpuDevice surface (csrc/pjrt_device.cc over the official
    pjrt_c_api.h): plugin load + C-API version handshake + attributes.
    Client creation is NOT exercised here — it would be a second
    client on a chip that belongs to one (docs/native_tpu_device.md)."""

    @pytest.mark.slow  # 463s of the 870s tier-1 budget on a chipless
    # box: libtpu is present but has no device, so plugin init grinds
    # through its retry schedule before the handshake returns.  Runs in
    # the slow lane; tier-1 keeps the two fast negative-path tests below.
    def test_plugin_handshake_against_libtpu(self):
        from singa_tpu import device as device_mod
        if _core.lib() is None:
            pytest.skip("native core unavailable")
        if device_mod._default_plugin_path() is None:
            pytest.skip("libtpu not in this environment")
        info = device_mod.pjrt_plugin_info()
        assert info["api_struct_size"] > 0
        major, minor = info["api_version"]
        assert major >= 0 and minor > 0, info["api_version"]
        assert info["init_error"] == ""
        # libtpu publishes at least the xla/stablehlo version attrs
        assert "xla_version" in info["attributes"], info["attributes"]

    def test_plugin_load_bad_path_raises(self):
        from singa_tpu import device as device_mod
        if _core.lib() is None:
            pytest.skip("native core unavailable")
        with pytest.raises(RuntimeError, match="load failed"):
            device_mod.pjrt_plugin_info(path="/nonexistent/plugin.so")

    def test_plugin_load_non_pjrt_so_raises(self):
        """A real shared object without GetPjrtApi must be rejected by
        the symbol check, not crash."""
        from singa_tpu import device as device_mod
        from singa_tpu._core import _SO
        if _core.lib() is None:
            pytest.skip("native core unavailable")
        with pytest.raises(RuntimeError, match="GetPjrtApi"):
            device_mod.pjrt_plugin_info(path=str(_SO))
