"""Pipeline parallelism ('pipe' mesh axis) — GPipe schedule under
shard_map: forward equals sequential stage application, jax.grad gives
the reverse-schedule backward, composes with DP on a 2-D mesh, and a
pipelined model trains. (VERDICT r2 item 9: implement or retract.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import parallel
from singa_tpu.parallel import pipeline as pp
from singa_tpu.parallel.mesh import P


def _stages(S, d, seed=0):
    rng = np.random.RandomState(seed)
    trees = [{"W": jnp.asarray(rng.randn(d, d).astype(np.float32) * 0.3),
              "b": jnp.asarray(rng.randn(d).astype(np.float32) * 0.1)}
             for _ in range(S)]
    return pp.stack_stage_params(trees)


def _stage_fn(p, x):
    return jax.nn.relu(x @ p["W"] + p["b"])


def _seq(sp, x, S):
    y = x
    for i in range(S):
        y = jax.nn.relu(y @ sp["W"][i] + sp["b"][i])
    return y


class TestGPipe:
    S, N_MICRO, MB, D = 4, 8, 4, 16

    def _pipe_fn(self, mesh, in_specs=(P("pipe"), P()), out_specs=P()):
        return jax.jit(jax.shard_map(
            pp.gpipe(_stage_fn, self.N_MICRO), mesh=mesh,
            in_specs=in_specs, out_specs=out_specs, check_vma=False))

    def test_forward_matches_sequential(self):
        sp = _stages(self.S, self.D)
        x = np.random.RandomState(1).randn(
            self.N_MICRO, self.MB, self.D).astype(np.float32)
        mesh = pp.pipeline_mesh(self.S)
        out = np.asarray(self._pipe_fn(mesh)(sp, jnp.asarray(x)))
        ref = np.asarray(_seq(sp, jnp.asarray(x), self.S))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_backward_matches_sequential(self):
        """grad through scan+ppermute IS the reverse pipeline schedule."""
        sp = _stages(self.S, self.D, seed=2)
        x = jnp.asarray(np.random.RandomState(3).randn(
            self.N_MICRO, self.MB, self.D).astype(np.float32))
        mesh = pp.pipeline_mesh(self.S)
        pf = self._pipe_fn(mesh)

        gp = jax.jit(jax.grad(lambda sp: jnp.sum(pf(sp, x) ** 2)))(sp)
        gs = jax.jit(jax.grad(
            lambda sp: jnp.sum(_seq(sp, x, self.S) ** 2)))(sp)
        for k in ("W", "b"):
            np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(gs[k]),
                                       rtol=1e-4, atol=1e-5)

    def test_collective_permute_in_hlo(self):
        sp = _stages(self.S, self.D)
        x = jnp.zeros((self.N_MICRO, self.MB, self.D), jnp.float32)
        mesh = pp.pipeline_mesh(self.S)
        hlo = self._pipe_fn(mesh).lower(sp, x).compile().as_text()
        assert "collective-permute" in hlo

    def test_dp_times_pp_mesh(self):
        """2-D data x pipe mesh: microbatch dim over 'data', stages over
        'pipe' — same math as 1-D pipeline on the full batch."""
        S = 4
        sp = _stages(S, self.D, seed=4)
        x = np.random.RandomState(5).randn(
            self.N_MICRO, 8, self.D).astype(np.float32)
        mesh = parallel.make_mesh({"data": 2, "pipe": S})
        # stage axis is dim 0 of each stacked leaf; shard over 'pipe'
        f = jax.jit(jax.shard_map(
            pp.gpipe(_stage_fn, self.N_MICRO), mesh=mesh,
            in_specs=(P("pipe"), P(None, "data")),
            out_specs=P(None, "data"), check_vma=False))
        out = np.asarray(f(sp, jnp.asarray(x)))
        ref = np.asarray(_seq(sp, jnp.asarray(x), S))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_pipelined_training_loss_falls(self):
        """End-to-end: SGD on pipeline-parallel stages learns a target."""
        S, d, n_micro, mb = 2, 8, 4, 8
        mesh = parallel.make_mesh({"pipe": S})
        sp = _stages(S, d, seed=6)
        rng = np.random.RandomState(7)
        x = jnp.asarray(rng.randn(n_micro, mb, d).astype(np.float32))
        tgt = jnp.asarray(rng.randn(n_micro, mb, d).astype(np.float32) * 0.1)

        pf = jax.shard_map(pp.gpipe(_stage_fn, n_micro), mesh=mesh,
                           in_specs=(P("pipe"), P()), out_specs=P(),
                           check_vma=False)

        @jax.jit
        def step(sp):
            def loss(sp):
                return jnp.mean((pf(sp, x) - tgt) ** 2)
            l, g = jax.value_and_grad(loss)(sp)
            sp = jax.tree.map(lambda p, gg: p - 0.05 * gg, sp, g)
            return sp, l

        losses = []
        for _ in range(20):
            sp, l = step(sp)
            losses.append(float(l))
        assert losses[-1] < losses[0] * 0.7, losses

    def test_stage_count_mismatch_raises(self):
        """Stacking more stages than the pipe axis size must raise, not
        silently drop stages (r3 review finding)."""
        sp = _stages(4, self.D)                 # 4 stages...
        mesh = pp.pipeline_mesh(2)              # ...on a 2-rank pipe
        x = jnp.zeros((self.N_MICRO, self.MB, self.D), jnp.float32)
        f = jax.shard_map(pp.gpipe(_stage_fn, self.N_MICRO), mesh=mesh,
                          in_specs=(P("pipe"), P()), out_specs=P(),
                          check_vma=False)
        with pytest.raises(ValueError, match="stage count"):
            f(sp, x)


class TestModelAPIPipeline:
    """VERDICT r3 item 5: pipeline parallelism through the normal
    Model surface — models.Llama(cfg, pipeline_stages=S) trains via
    compile/train_one_batch, equals sequential, composes with DistOpt,
    and checkpoints round-trip across pipelined/sequential configs."""

    def _run(self, pipe, steps=4, remat=False, micro=0):
        from singa_tpu import models, opt, tensor
        jax.config.update("jax_default_matmul_precision", "highest")
        tensor.set_seed(0)
        np.random.seed(0)
        cfg = models.LlamaConfig.tiny()
        cfg.num_layers = 4
        cfg.remat = remat
        if pipe:
            parallel.set_mesh(parallel.make_mesh({"data": 2, "pipe": 4}))
            cfg.pipeline_stages = 4
            cfg.pipeline_microbatches = micro
        else:
            parallel.set_mesh(None)
        try:
            m = models.Llama(cfg)
            m.set_optimizer(
                opt.DistOpt(opt.SGD(lr=0.05, momentum=0.9)) if pipe
                else opt.SGD(lr=0.05, momentum=0.9))
            ids = tensor.from_numpy(np.random.randint(
                0, cfg.vocab_size, (8, 16)).astype(np.int32))
            m.compile([ids], is_train=True, use_graph=True)
            losses = [float(m.train_step(ids)[1].to_numpy())
                      for _ in range(steps)]
            hlo = m.graph.compiled_hlo()
        finally:
            parallel.set_mesh(None)
        return m, losses, hlo
    def test_llama_pipeline_matches_sequential(self):
        _, l_seq, _ = self._run(False)
        _, l_pipe, hlo = self._run(True)
        np.testing.assert_allclose(l_seq, l_pipe, rtol=2e-4, atol=2e-5)
        # the schedule's activation hand-off must ride collective-permute
        assert "collective-permute" in hlo
    def test_llama_pipeline_more_microbatches(self):
        """n_micro > stages (smaller bubbles) stays equivalent."""
        _, l_seq, _ = self._run(False, steps=2)
        _, l_pipe, _ = self._run(True, steps=2, micro=8)
        np.testing.assert_allclose(l_seq, l_pipe, rtol=2e-4, atol=2e-5)
    def test_llama_pipeline_with_remat_matches(self):
        _, l_seq, _ = self._run(False, steps=2)
        _, l_pipe, _ = self._run(True, steps=2, remat=True)
        np.testing.assert_allclose(l_seq, l_pipe, rtol=2e-4, atol=2e-5)

    def test_pipeline_checkpoint_roundtrips_to_sequential(self, tmp_path):
        """Param paths are identical pipelined vs not, so a pipelined
        model's checkpoint restores into a sequential one (and the
        restored model predicts identically)."""
        from singa_tpu import models, tensor
        m_pipe, _, _ = self._run(True, steps=2)
        path = str(tmp_path / "ck")
        m_pipe.save_states(path)

        tensor.set_seed(7)
        np.random.seed(7)
        cfg = models.LlamaConfig.tiny()
        cfg.num_layers = 4
        m_seq = models.Llama(cfg)
        ids = tensor.from_numpy(np.random.randint(
            0, cfg.vocab_size, (4, 16)).astype(np.int32))
        m_seq.compile([ids], is_train=False, use_graph=True)
        m_seq.load_states(path)
        m_seq.eval()
        out_seq = m_seq(ids).to_numpy()

        m_pipe.eval()
        out_pipe = m_pipe(ids).to_numpy()
        np.testing.assert_allclose(out_seq, out_pipe, rtol=2e-4,
                                   atol=2e-5)

    def test_bad_stage_division_raises(self):
        from singa_tpu import models
        cfg = models.LlamaConfig.tiny()  # 2 layers
        cfg.pipeline_stages = 4
        with pytest.raises(ValueError, match="stages"):
            models.Llama(cfg)


class TestPipelineComposition:
    """The Model-API pipeline composes with the other mesh axes on one
    3-D mesh: activations data+seq sharded (ring attention under 'seq'),
    or TP rules on the non-pipelined embed/head, all while the block
    stack rides 'pipe' — and the result equals sequential training."""

    def _run(self, axes, pipe_stages, steps=3):
        from singa_tpu import models, opt, tensor
        jax.config.update("jax_default_matmul_precision", "highest")
        tensor.set_seed(0)
        np.random.seed(0)
        cfg = models.LlamaConfig.tiny()
        cfg.num_layers = 4
        cfg.pipeline_stages = pipe_stages
        parallel.set_mesh(parallel.make_mesh(axes) if axes else None)
        try:
            m = models.Llama(cfg)
            m.set_optimizer(
                opt.DistOpt(opt.SGD(lr=0.05, momentum=0.9)) if axes
                else opt.SGD(lr=0.05, momentum=0.9))
            ids = tensor.from_numpy(np.random.randint(
                0, cfg.vocab_size, (8, 32)).astype(np.int32))
            m.compile([ids], is_train=True, use_graph=True)
            losses = [float(m.train_step(ids)[1].to_numpy())
                      for _ in range(steps)]
            if pipe_stages:
                # parity must not pass vacuously via a silent
                # sequential fallback
                assert "collective-permute" in m.graph.compiled_hlo()
            return losses
        finally:
            parallel.set_mesh(None)
    def test_dp_sp_pipe_matches_sequential(self):
        l_seq = self._run(None, 0)
        l_3d = self._run({"data": 2, "seq": 2, "pipe": 2}, 2)
        np.testing.assert_allclose(l_seq, l_3d, rtol=2e-4, atol=2e-5)
    def test_dp_tp_pipe_matches_sequential(self):
        l_seq = self._run(None, 0)
        l_3d = self._run({"data": 2, "model": 2, "pipe": 2}, 2)
        np.testing.assert_allclose(l_seq, l_3d, rtol=2e-4, atol=2e-5)


class TestPipelineExtras:
    """Masked transformer blocks pipeline too: non-grad batch-leading
    extras (padding masks) are microbatched and gathered per stage per
    tick; GPT-2 gains pipeline_stages."""
    def test_gpt2_pipeline_matches_sequential(self):
        from singa_tpu import models, opt, tensor

        def run(pipe):
            jax.config.update("jax_default_matmul_precision", "highest")
            tensor.set_seed(0)
            np.random.seed(0)
            cfg = models.GPT2Config.tiny()
            cfg.num_layers = 4
            cfg.dropout = 0.0
            cfg.pipeline_stages = 4 if pipe else 0
            parallel.set_mesh(
                parallel.make_mesh({"data": 2, "pipe": 4}) if pipe
                else None)
            try:
                m = models.GPT2(cfg)
                m.set_optimizer(
                    opt.DistOpt(opt.SGD(lr=0.05, momentum=0.9)) if pipe
                    else opt.SGD(lr=0.05, momentum=0.9))
                ids = tensor.from_numpy(np.random.randint(
                    0, cfg.vocab_size, (8, 16)).astype(np.int32))
                m.compile([ids], is_train=True, use_graph=True)
                losses = [float(m.train_step(ids)[1].to_numpy())
                          for _ in range(3)]
                if pipe:
                    assert "collective-permute" in m.graph.compiled_hlo()
                return losses
            finally:
                parallel.set_mesh(None)

        np.testing.assert_allclose(run(False), run(True),
                                   rtol=2e-4, atol=2e-5)
    def test_masked_blocks_pipeline_matches_sequential(self):
        from singa_tpu import autograd, layer, model, models, opt, tensor
        from singa_tpu.models.transformer import (_GPT2Block,
                                                  _padding_mask)
        from singa_tpu.tensor import Tensor

        class MaskedNet(model.Model):
            def __init__(self, cfg, pipe):
                super().__init__()
                blocks = [_GPT2Block(cfg) for _ in range(4)]
                self.blocks = (layer.PipelineStack(blocks, stages=4)
                               if pipe else blocks)
                self.head = layer.Linear(4)

            def forward(self, x, mask):
                mk = Tensor(data=_padding_mask(mask), device=x.device,
                            requires_grad=False)
                if isinstance(self.blocks, layer.PipelineStack):
                    x = self.blocks(x, mk)
                else:
                    for blk in self.blocks:
                        x = blk(x, mk)
                return self.head(x.reshape((x.shape[0], -1)))

            def train_one_batch(self, x, mask, y):
                out = self.forward(x, mask)
                loss = autograd.softmax_cross_entropy(out, y)
                self.optimizer.backward_and_update(loss)
                return out, loss

        def run(pipe):
            jax.config.update("jax_default_matmul_precision", "highest")
            tensor.set_seed(0)
            np.random.seed(0)
            cfg = models.GPT2Config.tiny()
            cfg.dropout = 0.0
            parallel.set_mesh(
                parallel.make_mesh({"data": 2, "pipe": 4}) if pipe
                else None)
            try:
                m = MaskedNet(cfg, pipe)
                m.set_optimizer(
                    opt.DistOpt(opt.SGD(lr=0.05, momentum=0.9)) if pipe
                    else opt.SGD(lr=0.05, momentum=0.9))
                x = tensor.from_numpy(
                    np.random.randn(8, 12, cfg.dim).astype(np.float32))
                am = np.ones((8, 12), np.float32)
                am[:, 9:] = 0     # padded tail — mask must matter
                mk = tensor.from_numpy(am)
                y = tensor.from_numpy(
                    np.random.randint(0, 4, (8,)).astype(np.int32))
                m.compile([x, mk], is_train=True, use_graph=True)
                losses = [float(m.train_step(x, mk, y)[1].to_numpy())
                          for _ in range(3)]
                if pipe:
                    assert "collective-permute" in m.graph.compiled_hlo()
                return losses
            finally:
                parallel.set_mesh(None)

        np.testing.assert_allclose(run(False), run(True),
                                   rtol=2e-4, atol=2e-5)

    def test_dropout_blocks_fall_back_with_warning(self):
        from singa_tpu import models, opt, tensor

        jax.config.update("jax_default_matmul_precision", "highest")
        tensor.set_seed(0)
        np.random.seed(0)
        cfg = models.GPT2Config.tiny()
        cfg.num_layers = 4
        cfg.dropout = 0.1           # nonzero: pipeline must decline
        cfg.pipeline_stages = 4
        parallel.set_mesh(parallel.make_mesh({"data": 2, "pipe": 4}))
        try:
            m = models.GPT2(cfg)
            m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05)))
            ids = tensor.from_numpy(np.random.randint(
                0, cfg.vocab_size, (8, 16)).astype(np.int32))
            with pytest.warns(UserWarning, match="Dropout"):
                m.compile([ids], is_train=True, use_graph=True)
                m.train_step(ids)
        finally:
            parallel.set_mesh(None)


@pytest.mark.slow  # 32 s byte-count perf guard (TP x PP); functional
# TP-inside-PP correctness stays tier-1 via the GPipe parity tests
def test_stacked_block_weights_tp_shard_inside_pipeline():
    """Under TP x PP the stacked block weights must carry the model's
    TP rules (trace-scoped SHARD_RULES handoff) — without them every
    step all-gathers the TP shards into a replicated stack.  Guard:
    rules-on accesses measurably fewer bytes, with identical losses."""
    from singa_tpu import models, opt, tensor
    from singa_tpu.parallel import spmd

    def build(rules_on):
        tensor.set_seed(0)
        np.random.seed(0)
        cfg = models.LlamaConfig.tiny()
        cfg.num_layers = 4
        cfg.pipeline_stages = 2
        parallel.set_mesh(
            parallel.make_mesh({"data": 2, "model": 2, "pipe": 2}))
        orig = spmd.current_trace_rules
        if not rules_on:
            spmd.current_trace_rules = lambda: None
        try:
            m = models.Llama(cfg)
            m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05)))
            ids = tensor.from_numpy(np.random.randint(
                0, cfg.vocab_size, (8, 32)).astype(np.int32))
            m.compile([ids], is_train=True, use_graph=True)
            _, loss = m.train_step(ids)
            bytes_acc = float(m.graph.cost_analysis().get(
                "bytes accessed", 0))
            return bytes_acc, float(loss.to_numpy())
        finally:
            spmd.current_trace_rules = orig
            parallel.set_mesh(None)

    b_off, l_off = build(False)
    b_on, l_on = build(True)
    np.testing.assert_allclose(l_off, l_on, rtol=1e-5)
    assert b_on < b_off * 0.9, (b_on, b_off)
