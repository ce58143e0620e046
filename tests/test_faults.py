"""singa_tpu.faults (ISSUE 4) — deterministic fault injection and the
serve engine's resilience paths, tier-1 lean.

The acceptance invariants under test:
  * with a FaultPlan injecting transient decode failures plus a prefill
    hang, the engine completes every non-poisoned request with greedy
    tokens bitwise-identical to a fault-free run, quarantined requests
    surface a failed status, and the engine never crashes;
  * with no active plan every injection site is a no-op: no obs events,
    jit caches unchanged, and an empty probe plan counts site calls
    without firing;
  * plans are seeded-deterministic and fail loudly on unknown
    sites/kinds/options;
  * incident records land in the durable store and lint clean.

Budget discipline: ONE llama-tiny engine fixture is shared by every
chaos test here (recovery rebuilds reuse its two compiled programs);
hang-detection (Heartbeat) and decode-exhaustion rebuild tests are
marked ``slow`` per the tier-1 cutoff rules in ROADMAP.md.
"""

import json
import os

import numpy as np
import pytest

from singa_tpu import faults, models, tensor
from singa_tpu.faults import FaultPlan, FaultSpec, InjectedFault
from singa_tpu.obs import events
from singa_tpu.obs import record as obs_record
from singa_tpu.obs import schema
from singa_tpu.serve import (EngineClosed, QuotaExceeded, Router,
                             ServeEngine, SLOClass, build_pools)
from singa_tpu.utils.data import DataLoader
from tools.lint.hlo import assert_program_count


@pytest.fixture(autouse=True)
def _no_plan_leak():
    """A test that dies inside faults.active() must not poison the rest
    of the suite with a live plan (or a lingering sink)."""
    yield
    faults.uninstall()
    events.configure()


# ---------------------------------------------------------------------------
# plan construction, validation, determinism (no jax)
# ---------------------------------------------------------------------------

class TestFaultPlan:
    def test_unknown_site_fails_at_construction(self):
        with pytest.raises(ValueError, match="unknown injection site"):
            FaultSpec("serve.decoed", "error")

    def test_unknown_kind_fails(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            FaultSpec("serve.decode", "explode")

    def test_site_kind_pairing(self):
        # serve.prefill supports error/hang, not nan or torn_write
        with pytest.raises(ValueError, match="does not support"):
            FaultSpec("serve.prefill", "nan")
        with pytest.raises(ValueError, match="does not support"):
            FaultSpec("ckpt.torn", "error")

    def test_triggers_mutually_exclusive(self):
        with pytest.raises(ValueError, match="mutually exclusive"):
            FaultSpec("serve.decode", "error", at=1, every=2)

    def test_option_validation(self):
        with pytest.raises(ValueError):
            FaultSpec("serve.decode", "error", at=0)
        with pytest.raises(ValueError):
            FaultSpec("serve.decode", "error", every=0)
        with pytest.raises(ValueError):
            FaultSpec("serve.decode", "error", p=1.5)
        with pytest.raises(ValueError):
            FaultSpec("serve.decode", "hang", delay_s=-1)

    def test_env_syntax_parses(self):
        p = FaultPlan.parse(
            "serve.decode=error:every=3,times=2;"
            "serve.prefill=hang:at=1,delay=0.5", seed=9)
        assert len(p.specs) == 2 and p.seed == 9
        assert p.specs[0].every == 3 and p.specs[0].times == 2
        assert p.specs[1].kind == "hang" and p.specs[1].delay_s == 0.5
        # `at` defaults to a single fire
        assert p.specs[1].times == 1

    def test_env_syntax_fails_loudly(self):
        # a malformed chaos plan must never silently inject nothing
        with pytest.raises(ValueError, match="expected"):
            FaultPlan.parse("serve.decode")
        with pytest.raises(ValueError, match="unknown fault option"):
            FaultPlan.parse("serve.decode=error:never=3")
        with pytest.raises(ValueError, match="unknown injection site"):
            FaultPlan.parse("serve.typo=error")

    def test_probabilistic_firing_is_seed_deterministic(self):
        def pattern(seed):
            plan = FaultPlan([FaultSpec("serve.decode", "error", p=0.4)],
                             seed=seed)
            return [bool(plan.match("serve.decode", ("error",)))
                    for _ in range(64)]
        a, b = pattern(3), pattern(3)
        assert a == b and any(a) and not all(a)
        assert pattern(4) != a          # a different seed reschedules

    def test_every_and_times_cap(self):
        plan = FaultPlan([FaultSpec("serve.decode", "error",
                                    every=2, times=2)])
        hits = [bool(plan.match("serve.decode", ("error",)))
                for _ in range(8)]
        assert hits == [False, True, False, True, False, False,
                        False, False]
        assert plan.fire_count() == 2

    def test_empty_plan_is_the_call_count_probe(self):
        plan = FaultPlan()
        with faults.active(plan):
            faults.fire("serve.decode")
            faults.fire("serve.decode")
            out = faults.corrupt("device.execute", np.ones(2, np.float32))
        assert plan.calls == {"serve.decode": 2}
        assert plan.fired == [] and not np.isnan(out).any()

    def test_nested_activation_rejected(self):
        with faults.active(FaultPlan()):
            with pytest.raises(RuntimeError, match="already active"):
                with faults.active(FaultPlan()):
                    pass


# ---------------------------------------------------------------------------
# fire / corrupt semantics
# ---------------------------------------------------------------------------

class TestFireCorrupt:
    def test_injected_fault_is_a_runtime_error(self):
        assert issubclass(InjectedFault, RuntimeError)
        plan = FaultPlan([FaultSpec("serve.decode", "error", at=1)])
        with faults.active(plan):
            with pytest.raises(InjectedFault, match="serve.decode"):
                faults.fire("serve.decode")
            faults.fire("serve.decode")     # at=1 fired once; call 2 clean

    def test_no_plan_emits_no_events(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        events.configure(path=path)
        try:
            faults.fire("serve.decode")
            faults.corrupt("device.execute", np.ones(1, np.float32))
        finally:
            events.configure()
        assert not os.path.exists(path) or open(path).read() == ""

    def test_fired_fault_emits_obs_counter(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        plan = FaultPlan([FaultSpec("serve.decode", "error", at=1)])
        events.configure(path=path)
        try:
            with faults.active(plan):
                with pytest.raises(InjectedFault):
                    faults.fire("serve.decode")
        finally:
            events.configure()
        evs = [json.loads(l) for l in open(path)]
        fired = [e for e in evs if e["name"] == "fault.injected"]
        assert len(fired) == 1
        assert fired[0]["site"] == "serve.decode"
        assert fired[0]["fault_kind"] == "error"

    def test_torn_write_truncates_the_ctx_path(self, tmp_path):
        f = tmp_path / "ckpt.npz"
        f.write_bytes(b"x" * 100)
        plan = FaultPlan([FaultSpec("ckpt.torn", "torn_write", at=1)])
        with faults.active(plan):
            faults.fire("ckpt.torn", path=str(f))
        assert f.stat().st_size == 50

    def test_corrupt_nanifies_floats_only(self):
        plan = FaultPlan([FaultSpec("data.next", "nan", at=1)])
        with faults.active(plan):
            plan.match("data.next", ("error", "hang"))   # advance call 1
            x, y = faults.corrupt(
                "data.next",
                (np.ones((2, 3), np.float32), np.ones(2, np.int32)))
        assert np.isnan(x).all()
        assert (y == 1).all() and y.dtype == np.int32

    def test_registry_is_documented(self):
        for name, (desc, kinds) in faults.SITES.items():
            assert desc and kinds, f"site {name} missing doc/kinds"
            assert all(k in faults.KINDS for k in kinds)


# ---------------------------------------------------------------------------
# satellite guards: monotonic failure detection, admission validation
# ---------------------------------------------------------------------------

def test_failure_and_scheduler_are_monotonic_only():
    """Heartbeat/device_liveness_check and the serve scheduler must be
    immune to wall-clock jumps (NTP step, suspend/resume): a
    time.time() reappearing could fire false hang detections or skew
    deadlines.  Was two ad-hoc source greps; now the singalint SGL005
    wall-clock rule (tools/lint) enforces it — repo-wide via the
    tests/test_lint.py clean gate, and pinned here for the two modules
    whose correctness depends on it.  Unlike the repo-wide gate, this
    pin also refuses SGL005 *suppressions*: these two files have no
    legitimate wall-clock use at all, so a future
    suppression-with-reason must not slip one past the test."""
    from singa_tpu.serve import scheduler
    from singa_tpu.utils import failure
    from tools.lint import lint_file

    for mod in (failure, scheduler):
        findings = lint_file(mod.__file__, codes=["SGL005"])
        assert not findings, [f.render() for f in findings]
        with open(mod.__file__, encoding="utf-8") as f:
            assert "disable=SGL005" not in f.read(), \
                f"{mod.__file__}: SGL005 may not be suppressed here"


# ---------------------------------------------------------------------------
# scheduler policy units (no jax)
# ---------------------------------------------------------------------------

class TestSchedulerPolicy:
    def _req(self, deadline_s=None):
        from singa_tpu.serve.scheduler import Request
        return Request(np.array([1, 2], np.int32), 4, deadline_s, None,
                       None)

    def test_shed_overload_evicts_only_hopeless_deadlines(self):
        import time as _t

        from singa_tpu.serve.scheduler import EVICTED, Scheduler
        s = Scheduler(max_queue=8)
        keep_none = self._req(None)           # deadline-less: never shed
        keep_far = self._req(deadline_s=60.0)
        hopeless = self._req(deadline_s=0.05)
        for r in (keep_none, hopeless, keep_far):
            s.offer(r)
        shed = s.shed_overload(_t.monotonic(), lambda pos: 10.0)
        assert shed == [hopeless]
        assert hopeless.state == EVICTED
        assert hopeless.finish_reason == "shed"
        assert list(s.queue) == [keep_none, keep_far]

    def test_requeue_front_preserves_order_and_ignores_backpressure(self):
        from singa_tpu.serve.scheduler import QUEUED, Scheduler
        s = Scheduler(max_queue=1)
        s.offer(self._req())                  # queue now at capacity
        a, b = self._req(), self._req()
        a.state = b.state = "running"
        s.requeue_front([a, b])               # recovery must not be refused
        assert list(s.queue)[:2] == [a, b]
        assert a.state == QUEUED and s.depth == 3


# ---------------------------------------------------------------------------
# data / train / ckpt site wiring (no jit: TinyModel + python loader)
# ---------------------------------------------------------------------------

class _TinyModel:
    """Checkpointable no-jit model stub (mirrors test_train's)."""

    class _P:
        def __init__(self, v):
            self.data = v

    def __init__(self):
        self.w = self._P(np.zeros(2, np.float32))
        self.optimizer = None
        self._step_count = 0
        self._base_key = np.array([0, 1], np.uint32)

    def get_states(self):
        return {"w": self.w}

    def set_states(self, s):
        self.w.data = np.asarray(s["w"])

    def train_step(self, x, y):
        self.w.data = self.w.data + 1.0
        self._step_count += 1
        return None, np.float32(0.5)


def _loader():
    r = np.random.RandomState(7)
    return DataLoader(r.randn(16, 4).astype(np.float32),
                      r.randint(0, 2, 16).astype(np.int32),
                      batch_size=4, seed=3, use_native=False)


class TestDataSite:
    def test_error_at_second_batch(self):
        plan = FaultPlan([FaultSpec("data.next", "error", at=2)])
        with faults.active(plan):
            it = iter(_loader())
            next(it)
            with pytest.raises(InjectedFault, match="data.next"):
                next(it)

    def test_nan_corruption_hits_floats_not_labels(self):
        plan = FaultPlan([FaultSpec("data.next", "nan", at=1)])
        with faults.active(plan):
            x, y = next(iter(_loader()))
        assert np.isnan(x).all() and not np.issubdtype(y.dtype,
                                                       np.floating)

    def test_no_plan_batches_clean(self):
        x, y = next(iter(_loader()))
        assert np.isfinite(x).all()


class TestTrainSite:
    def test_transient_step_fault_is_retried(self):
        from singa_tpu.train import TrainRunner
        plan = FaultPlan([FaultSpec("train.step", "error", at=1)])
        r = TrainRunner(_TinyModel(), _loader(), total_steps=3,
                        to_batch=tuple, _sleep=lambda s: None)
        with faults.active(plan):
            res = r.run()
        assert res.outcome == "completed" and res.steps == 3
        assert plan.fire_count("train.step") == 1

    def test_exhausted_retries_take_the_fatal_path(self):
        from singa_tpu.train import TrainAborted, TrainRunner
        plan = FaultPlan([FaultSpec("train.step", "error")])  # every call
        r = TrainRunner(_TinyModel(), _loader(), total_steps=3,
                        to_batch=tuple, max_retries=1,
                        liveness_timeout=2.0,
                        on_fatal=lambda msg: None,
                        _sleep=lambda s: None)
        with faults.active(plan):
            with pytest.raises(TrainAborted):
                r.run()

    def test_losing_fatal_path_does_not_strand_a_dump(self, tmp_path):
        """Write-exactly-once extends to flight dumps: when a second
        fatal path loses the record race (step-thread abort vs
        heartbeat firing together), it must not leave an orphan
        incidents file that no record's flight_ref references."""
        import time as _t

        from singa_tpu.train import TrainRunner
        store = tmp_path / "runs" / "records.jsonl"
        r = TrainRunner(_TinyModel(), _loader(), total_steps=1,
                        to_batch=tuple, record_store=str(store),
                        on_fatal=lambda msg: None,
                        _sleep=lambda s: None)
        r._t0 = _t.perf_counter()
        r.flight.note("counter", "x")
        r._fatal(0, "first fatal")           # wins: record + dump
        r._heartbeat_failure(1.0, 0)         # loses: neither
        entries = obs_record.RunRecord(str(store)).entries()
        assert len(entries) == 1
        ref = entries[0]["payload"]["flight_ref"]
        dumps = os.listdir(tmp_path / "runs" / "incidents")
        assert dumps == [os.path.basename(ref)]

    def test_ckpt_write_fault_surfaces_like_enospc(self, tmp_path):
        from singa_tpu.train import AsyncCheckpointManager
        ck = AsyncCheckpointManager(str(tmp_path / "ck"))
        plan = FaultPlan([FaultSpec("ckpt.write", "error", at=1)])
        with faults.active(plan):
            # async path: the injected error fires on the writer
            # thread and must surface through wait(), exactly like a
            # real write failure (ENOSPC)
            ck.save(1, _TinyModel())
            with pytest.raises(InjectedFault):
                ck.wait()
        assert ck.steps() == []        # nothing committed
        ck.close()

    def test_torn_commit_falls_back_to_previous(self, tmp_path):
        from singa_tpu.train import AsyncCheckpointManager
        m = _TinyModel()
        ck = AsyncCheckpointManager(str(tmp_path / "ck"), save_every=1)
        m.w.data = np.full(2, 5.0, np.float32)
        ck.save(1, m, block=True)
        plan = FaultPlan([FaultSpec("ckpt.torn", "torn_write", at=1)])
        m.w.data = np.full(2, 9.0, np.float32)
        with faults.active(plan):
            ck.save(2, m, block=True)       # commits, then gets torn
        fresh = _TinyModel()
        with pytest.warns(UserWarning, match="torn checkpoint"):
            aux = ck.restore_latest(fresh)
        assert aux["step"] == 1
        np.testing.assert_array_equal(fresh.w.data, np.full(2, 5.0))
        ck.close()


# ---------------------------------------------------------------------------
# incident records
# ---------------------------------------------------------------------------

class TestIncidentRecords:
    def test_schema_accepts_and_rejects(self):
        good = {"site": "serve.prefill", "fault": "InjectedFault",
                "ref": "req:3", "outcome": "quarantined", "retries": 3}
        schema.validate_incident_payload(good)
        for missing in ("site", "fault", "ref", "outcome", "retries"):
            bad = dict(good)
            del bad[missing]
            with pytest.raises(schema.SchemaError, match=missing):
                schema.validate_incident_payload(bad)
        with pytest.raises(schema.SchemaError, match="retries"):
            schema.validate_incident_payload({**good, "retries": "three"})

    def test_store_roundtrip_and_lint(self, tmp_path):
        store = tmp_path / "runs" / "records.jsonl"
        entry = obs_record.new_entry(
            "incident", "cpu", True, "cpu", run_id="inc-test-1",
            payload={"site": "serve.decode", "fault": "hang",
                     "ref": 7, "outcome": "recovered", "retries": 2})
        obs_record.RunRecord(str(store)).append(entry)
        assert obs_record.RunRecord(str(store)).validate() == []
        import sys as _sys
        _sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                         "..", "tools"))
        import record_check
        assert record_check.check_root(str(tmp_path)) == []
        # and a mangled incident is NAMED, not a raw KeyError
        bad = dict(entry, run_id="inc-test-2",
                   payload={"site": "serve.decode"})
        store.write_text(store.read_text()
                         + json.dumps(bad) + "\n")
        errs = record_check.check_root(str(tmp_path))
        assert errs and "fault" in errs[0]


# ---------------------------------------------------------------------------
# the serve engine chaos suite (one shared compiled engine)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def llama():
    tensor.set_seed(0)
    m = models.Llama(models.LlamaConfig.tiny())
    m.eval()
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32))],
              is_train=False, use_graph=False)
    return m


@pytest.fixture(scope="module")
def engine(llama):
    """The shared chaos engine: every test drains it back to idle, and
    recovery rebuilds reuse its two compiled programs."""
    return ServeEngine(llama, num_slots=3, max_len=24, block_size=8,
                       backoff_base=0.001, backoff_max=0.01)


def _prompts(lens, seed=7, vocab=256):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, (n,)).astype(np.int32) for n in lens]


@pytest.fixture(scope="module")
def baseline(engine):
    """Fault-free greedy streams — the bitwise reference every chaos
    run must reproduce."""
    hs = [engine.submit(p, max_new_tokens=6)
          for p in _prompts([4, 6, 8])]
    engine.run_until_idle()
    assert_program_count(engine, (1, 1))
    return [h.tokens for h in hs]


class TestServeChaos:
    def test_flagship_transient_decode_plus_prefill_hang(
            self, engine, baseline, tmp_path):
        """THE acceptance scenario: transient decode failures + one
        prefill hang + one request that repeatedly poisons prefill.
        All non-poisoned requests finish bitwise-identical to the
        fault-free run, the poisoned one surfaces a failed status, the
        engine never crashes, and nothing recompiled."""
        store = str(tmp_path / "runs" / "records.jsonl")
        engine.record_store = store
        # the poisoned request is submitted FIRST, so its prefill is
        # site calls 1..3 (initial + 2 retries); the healthy requests'
        # prefills start at call 4; the hang delays call 5
        plan = FaultPlan([
            FaultSpec("serve.prefill", "error", every=1, times=3),
            FaultSpec("serve.prefill", "hang", at=5, delay_s=0.05),
            FaultSpec("serve.decode", "error", every=3, times=2),
        ], seed=1)
        try:
            with faults.active(plan):
                poisoned = engine.submit(_prompts([5], seed=3)[0],
                                         max_new_tokens=6)
                with pytest.warns(UserWarning, match="quarantined"):
                    hs = [engine.submit(p, max_new_tokens=6)
                          for p in _prompts([4, 6, 8])]
                    engine.run_until_idle()
        finally:
            engine.record_store = None
        assert [h.tokens for h in hs] == baseline
        assert poisoned.failed and poisoned.status == "failed"
        assert poisoned.finish_reason == "quarantined"
        assert "prefill failed" in poisoned.error
        assert engine.pending == 0
        assert_program_count(engine, (1, 1))
        # 3 poisoned-prefill fires + 1 hang + 2 decode errors
        assert plan.fire_count() == 6
        assert engine.metrics.retries.get("serve.decode") == 2
        assert engine.metrics.quarantined >= 1
        # the quarantine landed as a linted incident record
        entries = obs_record.RunRecord(store).entries()
        assert [e["payload"]["outcome"] for e in entries
                if e["kind"] == "incident"] == ["quarantined"]

    def test_direct_recovery_is_idempotent(self, engine, baseline):
        """Mid-stream arena rebuild + re-prefill reproduces the exact
        greedy streams (and reuses the compiled programs)."""
        hs = [engine.submit(p, max_new_tokens=6)
              for p in _prompts([4, 6, 8])]
        # one tick = prefill wave + one decode: 2 tokens each — every
        # replay re-prefills in block-aligned chunks
        engine.step()
        before = engine.metrics.recoveries
        engine.recover("test")
        engine.recover("test-again")    # twice: still idempotent
        engine.run_until_idle()
        assert [h.tokens for h in hs] == baseline
        assert engine.metrics.recoveries == before + 2
        assert_program_count(engine, (1, 1))

    def test_recovery_replays_long_prompts(self, engine, llama):
        """PR 2's fixed arena failed a replay past prefill_len as
        unrecoverable; chunked prefill has no such cap — a mid-stream
        rebuild re-prefills ANY in-flight replay under max_len and the
        streams stay bit-identical to their references."""
        long_p, short_p = _prompts([9, 4], seed=5)
        ref_long = llama.generate(long_p[None], max_new_tokens=8)[0, 9:]
        ref_short = llama.generate(short_p[None], max_new_tokens=3)[0, 4:]
        h_long = engine.submit(long_p, max_new_tokens=8)
        h_short = engine.submit(short_p, max_new_tokens=3)
        engine.step()                   # long has 2 tokens: replay = 11
        engine.recover("test")
        engine.run_until_idle()
        assert not h_long.failed and not h_short.failed
        np.testing.assert_array_equal(ref_long, np.asarray(h_long.tokens))
        np.testing.assert_array_equal(ref_short,
                                      np.asarray(h_short.tokens))
        assert_program_count(engine, (1, 1))

    def test_block_alloc_fault_mid_stream_recovers_bit_identical(
            self, engine, baseline):
        """ISSUE 6 chaos satellite: the paged arena's allocation seam
        (`serve.block_alloc`) errors on a DECODE-TIME growth call —
        mid-stream, after admission — and the engine rebuilds the
        arena: fresh block pool, block tables and refcounts, every
        in-flight request re-prefilled, streams bit-identical to the
        fault-free run, nothing recompiled."""
        # alloc call order is deterministic: admissions are calls 1-3
        # ([4]->1, [6]->1, [8]->2 blocks), the first growth (slot of
        # the 6-token prompt crossing its block boundary) is call 4
        plan = FaultPlan([FaultSpec("serve.block_alloc", "error", at=4)])
        before = engine.metrics.recoveries
        with faults.active(plan):
            hs = [engine.submit(p, max_new_tokens=6)
                  for p in _prompts([4, 6, 8])]
            engine.run_until_idle()
        assert plan.fire_count() == 1
        assert [h.tokens for h in hs] == baseline
        assert engine.metrics.recoveries == before + 1
        assert_program_count(engine, (1, 1))
        # the rebuilt pool's refcounts are consistent: fully drained
        assert (engine.pool.ref == 0).all()
        assert engine.pool.free_count == engine.pool.num_slots

    def test_block_alloc_fault_at_admission_quarantines(self, engine):
        """An allocation fault BEFORE any block is claimed fails only
        that request (refcounts untouched), mirroring the poisoned-
        prefill quarantine path."""
        plan = FaultPlan([FaultSpec("serve.block_alloc", "error",
                                    every=1, times=3)])
        with faults.active(plan):
            with pytest.warns(UserWarning, match="quarantined"):
                h = engine.submit(_prompts([5], seed=11)[0],
                                  max_new_tokens=3)
                engine.run_until_idle()
        assert h.failed and h.finish_reason == "quarantined"
        assert (engine.pool.ref == 0).all()
        assert engine.pool.free_count == engine.pool.num_slots

    def test_zero_overhead_when_off(self, engine, baseline, tmp_path):
        """Acceptance: with no plan active no obs event is emitted on
        the hot path, and an EMPTY probe plan shows every site is still
        reached — while jit caches stay at one entry each."""
        path = str(tmp_path / "ev.jsonl")
        events.configure(path=path)
        try:
            hs = [engine.submit(p, max_new_tokens=4)
                  for p in _prompts([4, 6])]
            engine.run_until_idle()
        finally:
            events.configure()
        assert all(h.done for h in hs)
        assert all(json.loads(l)["name"] != "fault.injected"
                   for l in open(path))
        probe = FaultPlan()             # counts calls, fires nothing
        with faults.active(probe):
            hs = [engine.submit(p, max_new_tokens=4)
                  for p in _prompts([4, 6])]
            engine.run_until_idle()
        assert probe.calls["serve.prefill"] == 2
        assert probe.calls["serve.decode"] >= 3
        # the paged arena's allocation seam is reached too: one call
        # per admission, plus one growth when the 6-token prompt's
        # stream crosses its first block boundary (6 + 2 = 8)
        assert probe.calls["serve.block_alloc"] == 3
        assert probe.fired == []
        assert_program_count(engine, (1, 1))

    def test_run_until_idle_terminates_when_all_deadline_evicted(
            self, engine):
        """Every queued request dies at its deadline before admission:
        the loop must terminate (not spin on a never-draining queue)
        and every handle must surface the eviction."""
        hs = [engine.submit(p, max_new_tokens=4, deadline_s=0.0)
              for p in _prompts([4, 5, 6, 7])]
        engine.run_until_idle(max_steps=50)
        assert engine.pending == 0
        assert all(h.done and h.finish_reason == "deadline" for h in hs)
        assert all(h.tokens == [] for h in hs)
        assert engine.pool.free_count == engine.pool.num_slots

    def test_overload_shedding_is_deadline_aware(self, engine):
        """With measured ticks saying a queue wave is ~5 s, a queued
        request BEHIND the free-slot window whose deadline cannot span
        the wait is shed (reason 'shed', before burning a prefill),
        while a request the engine would prefill this very tick is
        served even with a sub-tick deadline — shedding never drops a
        request this tick's admission could still satisfy."""
        old = engine._tick_ewma
        engine._tick_ewma = 5.0
        try:
            h_keep = engine.submit(_prompts([4])[0], max_new_tokens=2)
            # position 1 < 3 free slots: prefills this tick, so a
            # deadline well under tick_ewma must NOT shed it
            h_tight = engine.submit(_prompts([5])[0], max_new_tokens=2,
                                    deadline_s=2.0)
            h_far = engine.submit(_prompts([6])[0], max_new_tokens=2,
                                  deadline_s=60.0)
            # position 3 >= 3 free slots: a full ~5 s wave away, its
            # 100 ms deadline is hopeless
            h_shed = engine.submit(_prompts([7])[0], max_new_tokens=2,
                                   deadline_s=0.1)
            engine.run_until_idle()
        finally:
            engine._tick_ewma = old
        assert h_shed.done and h_shed.finish_reason == "shed"
        assert h_shed.tokens == []
        assert h_keep.done and len(h_keep.tokens) == 2
        assert h_tight.done and len(h_tight.tokens) == 2
        assert h_far.done and len(h_far.tokens) == 2
        assert engine.metrics.evicted.get("shed", 0) >= 1

    def test_submit_validates_at_admission(self, engine):
        """Satellite: an impossible request is rejected with a clear
        ValueError at the door, never inside the chunked prefill
        program."""
        with pytest.raises(ValueError, match="max_len"):
            engine.submit(np.arange(23, dtype=np.int32),
                          max_new_tokens=2)        # 25 > max_len 24
        with pytest.raises(ValueError, match="max_len"):
            engine.submit(np.arange(8, dtype=np.int32),
                          max_new_tokens=40)       # past the arena end
        assert engine.pending == 0


class TestDrainClose:
    def test_drain_refuses_submits_while_completing_inflight(self,
                                                             llama):
        refused = []

        eng = ServeEngine(llama, num_slots=2, max_len=24, block_size=8,
                          backoff_base=0.001)

        def try_submit(tok, handle):
            if not refused:
                try:
                    eng.submit(np.array([1, 2], np.int32),
                               max_new_tokens=2)
                except EngineClosed as e:
                    refused.append(e)

        hs = [eng.submit(p, max_new_tokens=4, on_token=try_submit)
              for p in _prompts([4, 6, 8])]   # 3 reqs > 2 slots: queued
        eng.drain()
        assert refused, "submit during drain was not refused"
        assert all(h.done and len(h.tokens) == 4 for h in hs)
        with pytest.raises(EngineClosed, match="draining"):
            eng.submit(np.array([1], np.int32), max_new_tokens=1)
        # close releases the arena and is idempotent
        eng.close()
        eng.close()
        assert eng.pool is None
        with pytest.raises(EngineClosed):
            eng.submit(np.array([1], np.int32), max_new_tokens=1)
        with pytest.raises(EngineClosed):
            eng.step()


# ---------------------------------------------------------------------------
# device.execute site (graph executor; one tiny MLP compile)
# ---------------------------------------------------------------------------

class TestDeviceExecuteSite:
    def test_error_and_nan_on_compiled_step(self):
        from singa_tpu import opt
        np.random.seed(0)
        tensor.set_seed(0)
        m = models.MLP(perceptron_size=(8,), num_classes=4)
        m.set_optimizer(opt.Adam(lr=1e-2))
        x = np.random.RandomState(5).randn(8, 4).astype(np.float32)
        y = np.random.RandomState(6).randint(0, 4, 8).astype(np.int32)
        xb, yb = tensor.from_numpy(x), tensor.from_numpy(y)
        m.compile([xb], is_train=True, use_graph=True)
        m.train_step(xb, yb)            # warm compile, no plan
        plan = FaultPlan([
            FaultSpec("device.execute", "error", at=1),
            FaultSpec("device.execute", "nan", at=2),
        ])
        with faults.active(plan):
            with pytest.raises(InjectedFault, match="device.execute"):
                m.train_step(xb, yb)
            _, loss = m.train_step(xb, yb)   # call 2: clean dispatch,
            assert np.isnan(float(loss.data))  # NaN-corrupted outputs


# ---------------------------------------------------------------------------
# ISSUE 11 acceptance: request traces, the flight recorder, obsq slo
# (shared llama engine — no new compiles in tier-1)
# ---------------------------------------------------------------------------

class TestTraceFlightAcceptance:
    def test_request_traces_derive_ttft_and_tokens(self, engine,
                                                   baseline, tmp_path):
        """Acceptance (a): every completed request reconstructs as a
        single trace — its span-derived TTFT equals the histogram
        observation bit-for-bit, its delivery count equals its token
        list, and no other request's events leak into its trace.  With
        no record_store the engine performs zero file writes beyond the
        sink, while the flight ring is still recording (active even
        when the JSONL sink is off)."""
        path = str(tmp_path / "ev.jsonl")
        events.configure(path=path)
        try:
            hs = [engine.submit(p, max_new_tokens=6)
                  for p in _prompts([4, 6, 8])]
            engine.run_until_idle()
        finally:
            events.configure()
        assert [h.tokens for h in hs] == baseline
        evs = [json.loads(l) for l in open(path)]
        for h in hs:
            mine = [e for e in evs if e.get("trace") == h.trace_id]
            ttft = [e for e in mine if e["name"] == "serve.ttft_ms"]
            assert len(ttft) == 1
            assert ttft[0]["value"] == h.ttft_s * 1e3   # bitwise equal
            toks = [e for e in mine if e["name"] == "serve.token"]
            assert len(toks) == len(h.tokens) == 6
            # no cross-request leakage: every delivery in this trace
            # names this rid, and the prefill span is in-trace
            assert {e["rid"] for e in toks} == {h.rid}
            assert any(e["name"] == "serve.prefill"
                       and e["kind"] == "span" for e in mine)
        # flight ring active without any record_store; zero file writes
        assert engine.flight.snapshot()
        assert sorted(os.listdir(tmp_path)) == ["ev.jsonl"]

    def test_quarantine_dump_holds_the_poisoned_timeline(self, engine,
                                                         tmp_path):
        """Acceptance (b): the quarantine's incident record carries a
        flight_ref, and the dump it points at contains the poisoned
        request's full timeline (submit → injected faults → retries →
        quarantine)."""
        store = str(tmp_path / "runs" / "records.jsonl")
        engine.record_store = store
        plan = FaultPlan([FaultSpec("serve.prefill", "error",
                                    every=1, times=3)])
        try:
            with faults.active(plan):
                with pytest.warns(UserWarning, match="quarantined"):
                    poisoned = engine.submit(_prompts([5], seed=3)[0],
                                             max_new_tokens=4)
                    engine.run_until_idle()
        finally:
            engine.record_store = None
        assert poisoned.failed
        (inc,) = [e for e in obs_record.RunRecord(store).entries()
                  if e["kind"] == "incident"]
        ref = inc["payload"]["flight_ref"]
        dump_path = os.path.join(os.path.dirname(store), ref)
        assert os.path.exists(dump_path)
        from tools import obsq
        timeline = [e["name"] for e in obsq.load_events(dump_path)
                    if e.get("trace") == poisoned.trace_id]
        assert timeline.count("fault.injected") == 3
        for name in ("serve.submitted", "serve.retries",
                     "serve.quarantined"):
            assert name in timeline, timeline
        # and the records audit validates the ref end to end
        from tools.lint import audit
        assert audit.check_records_root(str(tmp_path)) == []

    def test_recovery_dump_ref_lands_in_incident_record(self, engine,
                                                        baseline,
                                                        tmp_path):
        store = str(tmp_path / "runs" / "records.jsonl")
        engine.record_store = store
        try:
            hs = [engine.submit(p, max_new_tokens=6)
                  for p in _prompts([4, 6, 8])]
            engine.step()
            engine.recover("test-flight")
            engine.run_until_idle()
        finally:
            engine.record_store = None
        assert [h.tokens for h in hs] == baseline
        (inc,) = [e for e in obs_record.RunRecord(store).entries()
                  if e["payload"].get("outcome") == "recovered"]
        ref = inc["payload"]["flight_ref"]
        from tools import obsq
        dump = obsq.load_events(os.path.join(os.path.dirname(store),
                                             ref))
        assert any(e["name"] == "serve.recoveries" for e in dump)
        assert_program_count(engine, (1, 1))

    def test_loadgen_chaos_slo_reproducible_from_traces(self, engine,
                                                        tmp_path):
        """THE ISSUE-11 acceptance run: an open-loop loadgen burst under
        an active FaultPlan yields (a) per-request trace-derived TTFT
        equal to the histogram values, (b) a flight dump for the
        quarantine whose ref is in the incident record, and (c) `obsq
        slo` reproducing the emitted serve_load record's p50/p99 and
        tokens/s from the raw traces."""
        from singa_tpu.serve.metrics import ServeMetrics
        from tools import loadgen, obsq

        store = str(tmp_path / "runs" / "records.jsonl")
        path = str(tmp_path / "ev.jsonl")
        # fresh per-run aggregation so the recorded percentiles cover
        # exactly the events this run emits (the module engine's
        # histograms are cumulative across the chaos suite)
        engine.metrics = ServeMetrics(flight=engine.flight)
        engine.record_store = store
        plan = FaultPlan([
            FaultSpec("serve.block_alloc", "error", at=1),
            FaultSpec("serve.decode", "error", every=7, times=2),
        ], seed=5)
        wl = loadgen.build_workload(16, rate_rps=200.0, seed=4,
                                    prompt_lens=(4, 8), new_tokens=(3, 6),
                                    tenants=2, shared_len=6)
        events.configure(path=path)
        try:
            with faults.active(plan):
                with pytest.warns(UserWarning, match="quarantined"):
                    payload = loadgen.run_load(engine, wl)
        finally:
            events.configure()
            engine.record_store = None
        assert engine.pending == 0
        assert plan.fire_count() >= 2
        evs = obsq.load_events(path)
        # (a) every request with a first token: trace TTFT == histogram
        by_trace = {}
        for e in evs:
            if e.get("name") == "serve.ttft_ms" and "trace" in e:
                by_trace[e["trace"]] = e["value"]
        snap = engine.metrics.snapshot()
        assert len(by_trace) == snap["ttft_ms"]["count"]
        # (b) the quarantined request's dump is referenced and holds it
        incidents = [e for e in obs_record.RunRecord(store).entries()
                     if e["kind"] == "incident"]
        quar = [e for e in incidents
                if e["payload"]["outcome"] == "quarantined"]
        assert quar and all("flight_ref" in e["payload"] for e in quar)
        dump = obsq.load_events(os.path.join(
            os.path.dirname(store), quar[0]["payload"]["flight_ref"]))
        assert any(e["name"] == "serve.quarantined" for e in dump)
        # (c) obsq slo reproduces the serve_load payload from traces
        derived = obsq.derive_slo(evs)
        assert derived["requests_with_first_token"] == \
            snap["ttft_ms"]["count"]
        mismatches = obsq.compare_slo(derived, payload,
                                      tol_pct=1.0, tps_tol_pct=60.0)
        assert mismatches == [], mismatches
        # the record itself round-trips through the store + audit
        loadgen.append_record(payload, store)
        from tools.lint import audit
        assert audit.check_records_root(str(tmp_path)) == []


# ---------------------------------------------------------------------------
# slow chaos: hang detection + heartbeat-driven recovery
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestHangRecoverySlow:
    def test_decode_exhaustion_triggers_rebuild(self, engine, baseline):
        """Decode failing past its retry budget escalates to an arena
        rebuild + re-prefill; the streams stay bitwise-identical."""
        plan = FaultPlan([FaultSpec("serve.decode", "error",
                                    every=1, times=4)])
        with faults.active(plan):
            hs = [engine.submit(p, max_new_tokens=6)
                  for p in _prompts([4, 6, 8])]
            engine.run_until_idle()
        assert [h.tokens for h in hs] == baseline
        assert engine.metrics.recoveries >= 1
        assert_program_count(engine, (1, 1))

    def test_heartbeat_hang_drives_recovery(self, llama, engine,
                                            baseline):
        """An injected decode hang outlasting the Heartbeat timeout is
        detected on the monitor thread, recovery runs at the next step
        boundary, and the greedy streams are unchanged."""
        eng = ServeEngine(llama, num_slots=3, max_len=24, block_size=8,
                          backoff_base=0.001,
                          heartbeat_timeout_s=0.15,
                          recover_on_hang=True)
        plan = FaultPlan([FaultSpec("serve.decode", "hang", at=2,
                                    delay_s=0.6)])
        with faults.active(plan):
            hs = [eng.submit(p, max_new_tokens=6)
                  for p in _prompts([4, 6, 8])]
            eng.run_until_idle()
        assert [h.tokens for h in hs] == baseline
        assert eng.metrics.recoveries == 1

    def test_block_alloc_hang_drives_recovery(self, llama, engine,
                                              baseline):
        """The heavy variant of the block_alloc chaos satellite: the
        growth-call hang outlasts the Heartbeat, the monitor requests a
        rebuild, and the recovered streams (tables + refcounts built
        from scratch) are unchanged."""
        eng = ServeEngine(llama, num_slots=3, max_len=24, block_size=8,
                          backoff_base=0.001,
                          heartbeat_timeout_s=0.15,
                          recover_on_hang=True)
        plan = FaultPlan([FaultSpec("serve.block_alloc", "hang", at=4,
                                    delay_s=0.6)])
        with faults.active(plan):
            hs = [eng.submit(p, max_new_tokens=6)
                  for p in _prompts([4, 6, 8])]
            eng.run_until_idle()
        assert [h.tokens for h in hs] == baseline
        assert eng.metrics.recoveries == 1
        assert (eng.pool.ref == 0).all()

    def test_loadgen_overload_soak_survives_chaos(self, llama,
                                                  tmp_path):
        """The loadgen acceptance scenario in-process: an open-loop
        overload run with transient prefill/decode errors AND a
        block_alloc fault completes with no engine crash, every request
        accounted for, and a schema-valid serve_load record."""
        from singa_tpu.obs import record as obs_record
        from tools import loadgen

        eng = ServeEngine(llama, num_slots=4, max_len=32, block_size=8,
                          backoff_base=0.001, backoff_max=0.01,
                          max_recoveries=50)
        plan = FaultPlan([
            FaultSpec("serve.prefill", "error", every=4, times=2),
            FaultSpec("serve.decode", "error", every=10, times=2),
            FaultSpec("serve.block_alloc", "error", at=10),
        ], seed=7)
        wl = loadgen.build_workload(30, rate_rps=200.0, seed=2,
                                    prompt_lens=(4, 8, 12),
                                    new_tokens=(3, 6),
                                    tenants=2, shared_len=8)
        with faults.active(plan):
            payload = loadgen.run_load(eng, wl, deadline_s=5.0)
        assert eng.pending == 0
        assert plan.fire_count() >= 3
        accounted = (payload["completed"] + payload["shed"]
                     + payload["rejected"]
                     + payload["detail"]["deadline_evicted"]
                     + payload["detail"]["quarantined"])
        assert accounted == 30
        store = loadgen.append_record(payload,
                                      str(tmp_path / "records.jsonl"))
        assert obs_record.RunRecord(store).validate() == []

    def test_hang_without_recovery_calls_on_failure(self, llama):
        """recover_on_hang=False keeps the PR-2 abort contract: the
        user's on_failure observes the hang."""
        fired = []
        eng = ServeEngine(llama, num_slots=2, max_len=24, block_size=8,
                          heartbeat_timeout_s=0.15,
                          on_failure=lambda age, step: fired.append(age))
        plan = FaultPlan([FaultSpec("serve.prefill", "hang", at=1,
                                    delay_s=0.6)])
        with faults.active(plan):
            h = eng.submit(_prompts([4])[0], max_new_tokens=2)
            eng.run_until_idle()
        assert fired and fired[0] >= 0.15
        assert h.done            # the sleep returned; decode completed


# ---------------------------------------------------------------------------
# disaggregated tier chaos (ISSUE 12) — same ONE compiled llama engine:
# every worker below shares the module fixture's programs, so the whole
# tier suite adds zero model-program compiles to tier-1 (the handoff
# gather is the sanctioned third program, compiled once on first use)
# ---------------------------------------------------------------------------

class TestDisaggChaos:
    def _tier(self, llama, engine, n, m, **kw):
        pw, dw = build_pools(llama, n, m, template=engine,
                             num_slots=3, max_len=24, block_size=8,
                             backoff_base=0.001, backoff_max=0.01)
        return Router(pw, dw, **kw), pw, dw

    def test_tier_streams_bitwise_identical_zero_new_compiles(
            self, llama, engine, baseline):
        """THE disagg acceptance anchor: greedy streams through a 2:1
        tier are token-identical to the single-engine run (which is
        itself identical to generate()), every worker's jit caches stay
        at the asserted program counts, and the template engine never
        recompiled — the tier rode the ONE compiled program set."""
        tier, pw, dw = self._tier(llama, engine, 2, 1)
        hs = [tier.submit(p, max_new_tokens=6)
              for p in _prompts([4, 6, 8])]
        tier.run_until_idle()
        assert [h.tokens for h in hs] == baseline
        assert tier.pending == 0
        assert_program_count(engine, (1, 1))
        for w in pw + dw:
            assert_program_count(w.engine, (1, 1))
            assert w.engine.handoff_compiled_count() <= 1
        assert tier.metrics.handoffs == 3
        # every request's first token landed on a PREFILL worker and
        # its remaining tokens on a DECODE worker
        snap = tier.metrics.snapshot()
        assert snap["admitted"] == 3
        assert sum(len(h.tokens) for h in hs) == 18

    def test_handoff_fault_reroutes_and_streams_stay_identical(
            self, llama, engine, baseline, tmp_path):
        """Acceptance: injected `serve.handoff` worker death mid-handoff
        — the router re-routes, the request re-prefills from prompt,
        and ALL streams (including the re-routed one) are bitwise
        identical to the fault-free run; the reroute lands as a linted
        incident record whose flight_ref dump parses."""
        store = str(tmp_path / "runs" / "records.jsonl")
        tier, pw, dw = self._tier(llama, engine, 1, 1,
                                  record_store=store)
        plan = FaultPlan([FaultSpec("serve.handoff", "error", at=2)])
        with faults.active(plan):
            hs = [tier.submit(p, max_new_tokens=6)
                  for p in _prompts([4, 6, 8])]
            with pytest.warns(UserWarning, match="re-routing"):
                tier.run_until_idle()
        assert [h.tokens for h in hs] == baseline
        assert plan.fire_count() == 1
        assert tier.metrics.reroutes == 1
        for w in pw + dw:
            assert_program_count(w.engine, (1, 1))
        (inc,) = [e for e in obs_record.RunRecord(store).entries()
                  if e["payload"].get("outcome") == "rerouted"]
        assert inc["payload"]["site"] == "serve.handoff"
        ref = inc["payload"]["flight_ref"]
        from tools import obsq
        dump = obsq.load_events(os.path.join(os.path.dirname(store),
                                             ref))
        assert dump                      # the source worker's timeline
        from tools.lint import audit
        assert audit.check_records_root(str(tmp_path)) == []

    def test_killed_decode_worker_rerouted_bitwise(self, llama, engine,
                                                   baseline, tmp_path):
        """Acceptance: a decode worker killed MID-STREAM (its slots
        hold live requests) — the router re-prefills them from prompt +
        tokens-so-far on the prefill pool, final streams are bitwise
        identical, and the death's incident dump carries the dead
        worker's flight ring with a valid flight_ref."""
        store = str(tmp_path / "runs" / "records.jsonl")
        tier, pw, dw = self._tier(llama, engine, 1, 2,
                                  record_store=store)
        hs = [tier.submit(p, max_new_tokens=6)
              for p in _prompts([4, 6, 8])]
        # a few rounds: prefills hand off and decode begins
        for _ in range(3):
            tier.step()
        victim = next(w for w in dw if w.engine.running_items())
        with pytest.warns(UserWarning, match="died"):
            tier.kill_worker(victim.name)
        assert not victim.alive
        tier.run_until_idle()
        assert [h.tokens for h in hs] == baseline
        assert tier.metrics.worker_deaths == 1
        (inc,) = [e for e in obs_record.RunRecord(store).entries()
                  if e["payload"].get("fault") == "worker_death"]
        assert inc["payload"]["site"] == "serve.router"
        assert inc["payload"]["ref"] == victim.name
        from tools import obsq
        dump = obsq.load_events(os.path.join(
            os.path.dirname(store), inc["payload"]["flight_ref"]))
        assert any(e.get("name") == "serve.handoff_in" for e in dump)
        from tools.lint import audit
        assert audit.check_records_root(str(tmp_path)) == []

    def test_killed_prefill_worker_requeues_to_survivor(
            self, llama, engine, baseline):
        """A dead PREFILL worker's queued + running requests re-route
        to the surviving prefill worker; streams unchanged."""
        tier, pw, dw = self._tier(llama, engine, 2, 1)
        hs = [tier.submit(p, max_new_tokens=6)
              for p in _prompts([4, 6, 8])]
        # kill the prefill worker holding the most queue before any
        # tick — everything it held must replay elsewhere
        dead = max(pw, key=lambda w: w.load)
        assert dead.load > 0
        with pytest.warns(UserWarning, match="died"):
            tier.kill_worker(dead.name)
        tier.run_until_idle()
        assert [h.tokens for h in hs] == baseline
        survivor = next(w for w in pw if w.alive)
        assert survivor.engine.metrics.admitted >= dead.load

    def test_cross_worker_trace_renders_one_timeline(self, llama,
                                                     engine, baseline,
                                                     tmp_path):
        """Acceptance: submit → route → prefill@worker → handoff →
        decode deliveries → finish reconstructs from ONE trace id via
        tools/obsq trace — the id the ROUTER assigned, carried across
        both workers."""
        from tools import obsq
        path = str(tmp_path / "ev.jsonl")
        tier, pw, dw = self._tier(llama, engine, 1, 1)
        events.configure(path=path)
        try:
            h = tier.submit(_prompts([4])[0], max_new_tokens=6)
            tier.run_until_idle()
        finally:
            events.configure()
        assert h.trace_id.startswith(tier.run_id)
        evs = obsq.load_events(path)
        mine = [e for e in evs if e.get("trace") == h.trace_id]
        names = [e["name"] for e in mine]
        for required in ("serve.submitted", "serve.route",
                         "serve.prefill", "serve.handoff",
                         "serve.token", "serve.evicted"):
            assert required in names, (required, names)
        route = next(e for e in mine if e["name"] == "serve.route")
        handoff = next(e for e in mine if e["name"] == "serve.handoff")
        assert route["worker"] == pw[0].name
        assert handoff["src"] == pw[0].name
        assert handoff["dst"] == dw[0].name
        # tokens after the handoff came from the decode worker; the
        # rendered timeline is one trace, human-readable
        rendered = obsq.render_trace(evs, h.trace_id)
        assert "serve.handoff" in rendered and "tokens=6" in rendered

    def test_tenant_quota_and_slo_classes(self, llama, engine):
        """Per-tenant quotas reject at the tier door (QuotaExceeded is
        a QueueFull — loadgen counts it as overload), SLO classes bind
        deadlines, and unknown classes fail loudly."""
        tier, pw, dw = self._tier(
            llama, engine, 1, 1,
            slo_classes={"interactive": SLOClass("interactive", 5.0),
                         "batch": SLOClass("batch", None)},
            tenant_quota=1)
        h1 = tier.submit(_prompts([4])[0], max_new_tokens=2,
                         tenant="acme", slo="interactive")
        assert h1._req.deadline is not None
        with pytest.raises(QuotaExceeded):
            tier.submit(_prompts([4])[0], max_new_tokens=2,
                        tenant="acme")
        h2 = tier.submit(_prompts([4])[0], max_new_tokens=2,
                         tenant="other", slo="batch")
        assert h2._req.deadline is None
        with pytest.raises(ValueError, match="unknown SLO class"):
            tier.submit(_prompts([4])[0], max_new_tokens=2, slo="gold")
        tier.run_until_idle()
        assert h1.done and h2.done
        # quota freed on completion
        h3 = tier.submit(_prompts([4])[0], max_new_tokens=2,
                         tenant="acme")
        tier.run_until_idle()
        assert h3.done
        assert tier.metrics.quota_rejected == 1
        snap = tier.metrics.snapshot()
        assert snap["rejected"] == 1

    def test_handoff_transfers_prefix_cache_keys(self, llama, engine):
        """Refcounts and prefix-cache keys travel WITH the blocks: two
        requests sharing a full prompt block hand off to the same
        decode worker, and the second handoff maps the shared block
        copy-free (the decode pool's prefix cache matched the chain
        key the first handoff registered)."""
        tier, pw, dw = self._tier(llama, engine, 1, 1)
        shared = _prompts([8], seed=11)[0]      # exactly one full block
        p1 = np.concatenate([shared, _prompts([3], seed=12)[0]])
        p2 = np.concatenate([shared, _prompts([5], seed=13)[0]])
        h1 = tier.submit(p1, max_new_tokens=3)
        h2 = tier.submit(p2, max_new_tokens=3)
        tier.run_until_idle()
        ref1 = llama.generate(p1[None], max_new_tokens=3)[0, p1.size:]
        ref2 = llama.generate(p2[None], max_new_tokens=3)[0, p2.size:]
        np.testing.assert_array_equal(np.asarray(h1.tokens), ref1)
        np.testing.assert_array_equal(np.asarray(h2.tokens), ref2)
        # the decode worker saw the shared block twice but holds ONE
        # keyed copy of it (chain keys transferred and matched)
        dump = [e for e in dw[0].engine.flight.snapshot()
                if e.get("name") == "serve.handoff_in"]
        assert len(dump) == 2
        assert sum(e["shared"] for e in dump) >= 1


# ---------------------------------------------------------------------------
# serve.spill — the memory-hierarchy seams (ISSUE 17)
# ---------------------------------------------------------------------------

class TestSpillChaos:
    """Chaos contract for the KV spill tier: a fault at EITHER seam
    (spill write, prefetch read) only degrades performance.  A dead
    spill loses the host copy — the block dies unspilled, exactly the
    pre-spill behavior; a dead prefetch is a spill miss — the prefix
    re-prefills.  Streams stay bitwise identical to ``generate()``
    either way, and every fired fault lands as a ``serve.spill``
    'degraded' incident whose flight_ref resolves to a dump."""

    def _engine(self, llama, store=None):
        # 9 physical blocks: the 20-token churn requests below need 3+
        # blocks each and run two-at-a-time, so the LRU must evict the
        # cold shared-prefix blocks between the two prefix hits
        return ServeEngine(llama, num_slots=2, max_len=32, block_size=8,
                           num_blocks=9, spill_blocks=16,
                           record_store=store)

    @staticmethod
    def _workload():
        rng = np.random.RandomState(17)
        shared = rng.randint(0, 256, (16,)).astype(np.int32)
        tails = [rng.randint(0, 256, (4,)).astype(np.int32)
                 for _ in range(2)]
        churn = [rng.randint(0, 256, (20,)).astype(np.int32)
                 for _ in range(4)]
        return [np.concatenate([shared, t]) for t in tails], churn

    @staticmethod
    def _refs(llama, prompts):
        return [llama.generate(p[None], max_new_tokens=6)[0, p.size:]
                for p in prompts]

    def _drive(self, eng, prompts, churn):
        h1 = eng.submit(prompts[0], max_new_tokens=6)
        eng.run_until_idle()
        for q in churn:
            eng.submit(q, max_new_tokens=4)
        eng.run_until_idle()
        h2 = eng.submit(prompts[1], max_new_tokens=6)
        eng.run_until_idle()
        return h1, h2

    def _check_incidents(self, store, op):
        """Every incident is a valid serve.spill degradation with a
        resolvable flight_ref, and at least one is the seam under
        test (``op``) — a faulted prefetch may trigger further spill
        writes on the re-prefill path, which also fault and record."""
        incidents = [e for e in obs_record.RunRecord(store).entries()
                     if e["kind"] == "incident"]
        assert incidents, "fired spill faults left no incident record"
        for inc in incidents:
            p = inc["payload"]
            assert p["site"] == "serve.spill"
            assert p["outcome"] == "degraded"
            assert p["ref"] in ("op:spill", "op:prefetch")
            ref = p["flight_ref"]
            dump = os.path.join(os.path.dirname(store), ref)
            assert os.path.exists(dump)
        assert any(e["payload"]["ref"] == f"op:{op}" for e in incidents)
        from tools.lint import audit
        root = os.path.dirname(os.path.dirname(store))
        assert audit.check_records_root(root) == []

    def test_spill_write_fault_dies_unspilled(self, llama, tmp_path):
        """Every spill write errors: nothing reaches the host store,
        the re-hit re-prefills (a plain miss), streams are unchanged."""
        store = str(tmp_path / "runs" / "records.jsonl")
        prompts, churn = self._workload()
        refs = self._refs(llama, prompts)
        eng = self._engine(llama, store)
        plan = FaultPlan([FaultSpec("serve.spill", "error")])
        with faults.active(plan):
            h1, h2 = self._drive(eng, prompts, churn)
        assert plan.fire_count() > 0
        np.testing.assert_array_equal(refs[0], np.asarray(h1.tokens))
        np.testing.assert_array_equal(refs[1], np.asarray(h2.tokens))
        # every copy was refused BEFORE it happened: store empty,
        # metrics clean — this is bitwise the pre-spill engine
        assert len(eng.pool.spill) == 0
        assert eng.metrics.spilled_blocks == 0
        assert eng.metrics.prefetch_hits == 0
        assert_program_count(eng, (1, 1))
        self._check_incidents(store, "spill")

    def test_prefetch_fault_is_a_spill_miss(self, llama, tmp_path):
        """Spills land fault-free, then the prefetch on the prefix
        re-hit errors: the restore is abandoned BEFORE the payload is
        popped (the store keeps it), the prefix re-prefills, and the
        stream is unchanged."""
        store = str(tmp_path / "runs" / "records.jsonl")
        prompts, churn = self._workload()
        refs = self._refs(llama, prompts)
        eng = self._engine(llama, store)
        h1 = eng.submit(prompts[0], max_new_tokens=6)
        eng.run_until_idle()
        for q in churn:
            eng.submit(q, max_new_tokens=4)
        eng.run_until_idle()
        spilled = eng.metrics.spilled_blocks
        assert spilled > 0 and len(eng.pool.spill) > 0
        # now ONLY the prefetch seam can fire: churn is drained, and
        # the next fires at this site are the re-hit's restores
        plan = FaultPlan([FaultSpec("serve.spill", "error")])
        with faults.active(plan):
            h2 = eng.submit(prompts[1], max_new_tokens=6)
            eng.run_until_idle()
        assert plan.fire_count() > 0
        np.testing.assert_array_equal(refs[0], np.asarray(h1.tokens))
        np.testing.assert_array_equal(refs[1], np.asarray(h2.tokens))
        # the miss re-prefilled: no restore was counted, and the store
        # still holds every payload the fault-free churn spilled
        assert eng.metrics.prefetch_hits == 0
        assert_program_count(eng, (1, 1))
        self._check_incidents(store, "prefetch")

    def test_fault_free_spill_roundtrip_is_bitwise(self, llama):
        """The no-fault control for the two tests above: same workload,
        blocks spill AND restore, streams still bitwise generate()."""
        prompts, churn = self._workload()
        refs = self._refs(llama, prompts)
        eng = self._engine(llama)
        h1, h2 = self._drive(eng, prompts, churn)
        np.testing.assert_array_equal(refs[0], np.asarray(h1.tokens))
        np.testing.assert_array_equal(refs[1], np.asarray(h2.tokens))
        assert eng.metrics.spilled_blocks > 0
        assert eng.metrics.prefetch_hits > 0
        assert_program_count(eng, (1, 1))
