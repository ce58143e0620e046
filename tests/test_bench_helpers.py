"""bench.py helpers and the analytic-FLOPs accounting the headline
metric rests on (docs/performance.md "MFU accounting").  These run
without hardware: the helpers are pure, and the models are tiny."""

import time

import numpy as np

import bench  # repo root is on sys.path via tests/conftest.py
from singa_tpu import models, tensor


class TestTimedStepsStats:
    def test_windowed_median_and_stats(self, monkeypatch):
        """_timed_steps measures windows of 8 back-to-back steps (fence
        at window end; how a real training loop runs) and reports the
        median over windows; a short individually-fenced pass lands in
        stats["fenced"] as the per-dispatch diagnostic.  One slow step
        inflates one window and the median discards it."""
        # isolate from the process-global soft budget (stamped at
        # bench import; a long suite run could otherwise trip it)
        monkeypatch.setattr(bench, "_T0", time.time())
        monkeypatch.setattr(bench, "_BUDGET_S", 420.0)

        class FakeLoss:
            def __init__(self):
                import jax.numpy as jnp
                self.data = jnp.zeros(())

        class FakeModel:
            def train_step(self, *a):
                return (FakeLoss(),)

        dt, out = bench._timed_steps(FakeModel(), (None,), steps=32,
                                     warmup=1)
        s = bench.LAST_STEP_STATS
        assert s["method"] == "windowed"
        assert s["window_len"] == 8
        # steps=32 -> 4 windows of 8 = 32 total back-to-back steps
        assert s["windows"] == 4 and s["n"] == 32
        assert len(s["window_ms"]) == 4
        assert s["min"] <= s["median"] <= s["max"]
        # per-step median = median window time / window length
        assert abs(dt * 1e3 - s["median"]) <= 0.05 + 1e-9
        # fenced diagnostic pass present with its own median
        assert s["fenced"]["method"] == "fenced"
        assert s["fenced"]["n"] == 8

    def test_windowed_steps_median_math(self):
        """utils.timing.windowed_steps: median over windows, not mean —
        one slow window must not move the reported per-step time."""
        from singa_tpu.utils.timing import windowed_steps

        calls = {"n": 0}
        sleeps = [0.0, 0.0, 0.05, 0.0, 0.0]   # one slow window

        def step():
            import jax.numpy as jnp
            w = calls["n"] // 4
            if calls["n"] % 4 == 0 and w < len(sleeps):
                time.sleep(sleeps[w])
            calls["n"] += 1
            return jnp.zeros(())

        dt, stats = windowed_steps(step, windows=4, window_len=4,
                                   warmup=4)
        assert stats["windows"] == 4 and stats["n"] == 16
        # the 50 ms window is the max, not the median
        assert stats["max"] >= 10.0
        assert stats["median"] < 10.0


class TestAxesFor:
    """__graft_entry__._axes_for — the driver-contract mesh factoring
    must be exact for ANY device count (r4 VERDICT weak #8)."""

    def test_products_are_exact(self):
        from __graft_entry__ import _axes_for
        import math
        for n in range(1, 33):
            axes = _axes_for(n)
            assert math.prod(axes.values()) == n, (n, axes)

    def test_known_factorings(self):
        from __graft_entry__ import _axes_for
        assert _axes_for(8) == {"data": 2, "model": 2, "seq": 2}
        assert _axes_for(6) == {"data": 3, "model": 2}
        assert _axes_for(12) == {"data": 3, "model": 2, "seq": 2}
        assert _axes_for(7) == {"data": 7}
        assert _axes_for(1) == {"data": 1}


class TestAnalyticFlopsAccounting:
    """flops_per_token is the headline MFU's numerator — its active-
    compute rules (MoE top-k, sliding-window span) must hold."""

    def test_moe_counts_only_active_experts(self):
        dense = models.Llama(models.LlamaConfig.tiny())
        cfg = models.LlamaConfig.tiny()
        cfg.num_experts = 4            # top-2 of 4
        moe = models.Llama(cfg)
        # initialize params so num_params() sees them
        ids = tensor.from_numpy(
            np.random.RandomState(0).randint(0, 256, (1, 8)).astype(
                np.int32))
        dense(ids)
        moe(ids)
        f_dense = dense.flops_per_token(8)
        f_moe = moe.flops_per_token(8)
        # the matmul-param bank: embeddings excluded (their lookup is a
        # gather — r5 accounting correction)
        n_emb = cfg.vocab_size * cfg.dim
        full_bank = (6 * (moe.num_params() - n_emb)
                     + 12 * cfg.num_layers * cfg.dim * 8)
        # active counts top-2 of 4: strictly less than charging the
        # whole bank, strictly more than the 1-FFN dense model
        assert f_dense < f_moe < full_bank
        # exactly 2 inactive experts' FFNs are excluded per layer
        expert_p = 3 * cfg.dim * cfg.ffn_dim
        assert full_bank - f_moe == 6 * cfg.num_layers * 2 * expert_p

    def test_sliding_window_caps_attention_span(self):
        cfg_full = models.LlamaConfig.tiny()
        cfg_win = models.LlamaConfig.tiny()
        cfg_win.sliding_window = 16
        full = models.Llama(cfg_full)
        win = models.Llama(cfg_win)
        ids = tensor.from_numpy(
            np.random.RandomState(0).randint(0, 256, (1, 64)).astype(
                np.int32))
        full(ids)
        win(ids)
        T, W, c = 64, 16, cfg_full
        diff = full.flops_per_token(T) - win.flops_per_token(T)
        assert diff == 12 * c.num_layers * c.dim * (T - W)
        # below the window length the cap is inert
        assert full.flops_per_token(W) == win.flops_per_token(W)

    def test_bert_excludes_embedding_tables(self):
        cfg = models.BERTConfig.tiny(num_labels=2)
        m = models.BERT(cfg)
        ids = tensor.from_numpy(
            np.random.RandomState(0).randint(0, 256, (1, 16)).astype(
                np.int32))
        m(ids)
        n_total = sum(p.size for p in m.get_params().values())
        n_embed = (cfg.vocab_size + cfg.max_position
                   + cfg.type_vocab_size) * cfg.dim
        expect = 6 * (n_total - n_embed) + 12 * cfg.num_layers * cfg.dim * 16
        assert m.flops_per_token(16) == expect
