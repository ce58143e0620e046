"""Tests for singa_tpu.train — the fault-tolerant orchestrator.

The headline guarantees, each asserted here:

* kill-and-resume equivalence: train N steps straight vs train k,
  "crash", resume, train N-k — bitwise-equal params AND Adam moments;
* crash consistency: a torn checkpoint (truncated npz) is never
  loadable; restore falls back to the previous commit;
* async overlap: serialization runs on the writer thread while the
  step thread keeps stepping (proved via obs span timings);
* preemption: SIGTERM requests checkpoint-and-exit at the next step
  boundary, and the next incarnation resumes;
* repeated failure → emergency checkpoint + durable train_run record +
  on_fatal.

Runtime discipline (ROADMAP: the tier-1 budget is cutoff-bound): the
orchestration-logic tests run against a tiny in-memory stub model (no
jit); only the equivalence tests compile, and those use an 8-wide MLP
for <=8 steps.
"""

import json
import os
import signal
import sys
import time

import numpy as np
import pytest

from singa_tpu import models, opt, parallel, tensor
from singa_tpu.obs import events, record
from singa_tpu.obs.record import RunRecord
from singa_tpu.obs.schema import SchemaError
from singa_tpu.train import (AsyncCheckpointManager, CheckpointCorrupt,
                             PreemptionHandler, RunState, TrainAborted,
                             TrainRunner)
from singa_tpu.utils import checkpoint, failure
from singa_tpu.utils.data import DataLoader

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

N, DIM, CLASSES, BS = 32, 8, 4, 8


@pytest.fixture(autouse=True)
def _reset_events():
    yield
    events.configure()


def _arrays(seed=7, n=N, dim=DIM):
    r = np.random.RandomState(seed)
    return (r.randn(n, dim).astype(np.float32),
            r.randint(0, CLASSES, n).astype(np.int32))


def _loader(x, y, bs=BS):
    # python pipeline: resume is bit-reproducible only within one
    # pipeline, and the native loader hands off to python on restore
    return DataLoader(x, y, batch_size=bs, seed=3, drop_last=True,
                      use_native=False)


def _mlp(graph=True):
    """Fresh deterministically-initialized compiled MLP+Adam."""
    np.random.seed(0)
    tensor.set_seed(0)
    m = models.MLP(perceptron_size=(8,), num_classes=CLASSES)
    m.set_optimizer(opt.Adam(lr=1e-2))
    xb = np.random.RandomState(5).randn(BS, DIM).astype(np.float32)
    m.compile([tensor.from_numpy(xb)], is_train=True, use_graph=graph)
    return m


class TinyModel:
    """Minimal checkpointable model stub: keeps orchestration tests off
    the jit path entirely (each train_step increments a weight)."""

    class _P:
        def __init__(self, v):
            self.data = v

    def __init__(self):
        self.w = self._P(np.zeros(4, np.float32))
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


def _tiny_runner(tmp_path, model=None, total=6, save_every=100, **kw):
    x, y = _arrays()
    kw.setdefault("to_batch", tuple)
    return TrainRunner(
        model if model is not None else TinyModel(),
        _loader(x, y), total_steps=total,
        ckpt=AsyncCheckpointManager(str(tmp_path / "ck"),
                                    save_every=save_every), **kw)


def _params(m):
    return {n: np.asarray(t.data) for n, t in m.get_states().items()}


def _moments(m):
    return {n: [np.asarray(a) for a in leaves]
            for n, leaves in m.optimizer.slot_arrays().items()}


# ---------------------------------------------------------------------------
# the acceptance headline: kill-and-resume equivalence
# ---------------------------------------------------------------------------

class TestKillAndResume:
    def test_bitwise_equal_params_and_adam_moments(self, tmp_path):
        """6 straight compiled steps == 3 steps + crash + resume + 3:
        params and Adam m/v bitwise-identical, data cursor included."""
        x, y = _arrays()

        m_straight = _mlp()
        r = TrainRunner(m_straight, _loader(x, y), total_steps=6,
                        ckpt=AsyncCheckpointManager(str(tmp_path / "a"),
                                                    save_every=2))
        assert r.run().outcome == "completed"
        r.__exit__()

        m_killed = _mlp()   # the incarnation that will "crash" after 3
        r1 = TrainRunner(m_killed, _loader(x, y), total_steps=3,
                         ckpt=AsyncCheckpointManager(str(tmp_path / "b"),
                                                     save_every=2))
        assert r1.run().steps == 3
        r1.__exit__()
        del m_killed         # crash: nothing carries over but the files

        m_resumed = _mlp()
        r2 = TrainRunner(m_resumed, _loader(x, y), total_steps=6,
                         ckpt=AsyncCheckpointManager(str(tmp_path / "b"),
                                                     save_every=2))
        res = r2.run()
        r2.__exit__()
        assert res.resumed_from == 3 and res.steps == 6

        ps, pr = _params(m_straight), _params(m_resumed)
        assert set(ps) == set(pr)
        for n in ps:
            np.testing.assert_array_equal(ps[n], pr[n], err_msg=n)
        ms, mr = _moments(m_straight), _moments(m_resumed)
        assert set(ms) == set(mr)
        for n in ms:
            assert len(ms[n]) == len(mr[n]) == 2   # Adam m, v
            for a, b in zip(ms[n], mr[n]):
                np.testing.assert_array_equal(a, b, err_msg=f"moment {n}")
        # optimizer step counter resumed too (bias correction depends
        # on it: equal moments with a different t would diverge next)
        assert m_resumed.optimizer.step_counter == \
            m_straight.optimizer.step_counter == 6

    def test_dataloader_state_roundtrip(self):
        x, y = _arrays(seed=11)

        def take(loader, k):
            out = []
            while len(out) < k:
                for b in loader:
                    out.append(b)
                    if len(out) == k:
                        break
            return out

        straight = take(_loader(x, y), 6)
        interrupted = _loader(x, y)
        take(interrupted, 3)
        st = interrupted.state_dict()
        assert st["batch_idx"] == 3 and st["epoch"] == 0

        resumed = _loader(x, y)
        resumed.load_state_dict(st)
        got = take(resumed, 3)
        for (ax, ay), (bx, by) in zip(straight[3:], got):
            np.testing.assert_array_equal(ax, bx)
            np.testing.assert_array_equal(ay, by)

    def test_dataloader_warns_once_on_length_change(self):
        x, y = _arrays()
        a = _loader(x, y)
        next(iter(a))
        st = a.state_dict()
        b = _loader(x[:24], y[:24])
        with pytest.warns(UserWarning, match="length changed"):
            b.load_state_dict(st)
        import warnings as w
        with w.catch_warnings():
            w.simplefilter("error")
            b.load_state_dict(st)   # warn-once: second load is silent

    def test_run_state_version_guard(self):
        rs = RunState(step=3, epoch=1, data_state={"epoch": 1},
                      rng_key=[1, 2], model_step_count=3, run_id="r")
        assert RunState.from_aux(rs.to_aux()) == rs
        bad = rs.to_aux()
        bad["version"] = 99
        with pytest.raises(SchemaError, match="version"):
            RunState.from_aux(bad)


# ---------------------------------------------------------------------------
# crash consistency: commit markers, torn writes, retention
# ---------------------------------------------------------------------------

class TestCrashConsistency:
    def test_torn_npz_rejected_and_falls_back(self, tmp_path):
        mgr = AsyncCheckpointManager(str(tmp_path), save_every=1)
        m = TinyModel()
        m.train_step(None, None)
        mgr.save(1, m, run_state=RunState.capture(m, None, 1, "r"),
                 block=True)
        m.train_step(None, None)
        mgr.save(2, m, run_state=RunState.capture(m, None, 2, "r"),
                 block=True)
        # tear the newest commit: truncate the npz under its marker
        p2 = mgr.path(2)
        with open(p2, "r+b") as f:
            f.truncate(os.path.getsize(p2) - 16)
        with pytest.raises(CheckpointCorrupt, match="sha256|size"):
            mgr.load_step(2, TinyModel())
        fresh = TinyModel()
        with pytest.warns(UserWarning, match="torn"):
            aux = mgr.restore_latest(fresh)
        assert aux is not None and aux["step"] == 1
        np.testing.assert_array_equal(fresh.w.data,
                                      np.ones(4, np.float32))

    def test_uncommitted_npz_never_loadable(self, tmp_path):
        mgr = AsyncCheckpointManager(str(tmp_path), save_every=1)
        m = TinyModel()
        mgr.save(1, m, block=True)
        os.unlink(mgr.marker_path(1))   # crash between write and commit
        assert mgr.steps() == []
        assert mgr.restore_latest(TinyModel()) is None

    def test_retention_keep_last_plus_keep_every(self, tmp_path):
        mgr = AsyncCheckpointManager(str(tmp_path), keep_last=2,
                                     keep_every=3, save_every=1)
        m = TinyModel()
        for s in range(1, 8):
            mgr.save(s, m, block=True)
        # last two {6,7} plus every multiple of three {3,6}
        assert mgr.steps() == [3, 6, 7]
        files = sorted(os.listdir(str(tmp_path)))
        assert [f for f in files if f.endswith(".npz")] == \
            [f"ckpt_{s:012d}.npz" for s in (3, 6, 7)]

    def test_ckpt_fsck_tool(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import ckpt_fsck
        mgr = AsyncCheckpointManager(str(tmp_path), save_every=1)
        m = TinyModel()
        mgr.save(1, m, block=True)
        mgr.save(2, m, block=True)
        errors, warns = ckpt_fsck.fsck_dir(str(tmp_path))
        assert errors == [] and warns == []
        # uncommitted file: warning, not error
        os.unlink(mgr.marker_path(1))
        errors, warns = ckpt_fsck.fsck_dir(str(tmp_path))
        assert errors == [] and any("no commit marker" in w for w in warns)
        # torn committed file: error
        with open(mgr.path(2), "r+b") as f:
            f.truncate(10)
        errors, _ = ckpt_fsck.fsck_dir(str(tmp_path))
        assert any("size" in e or "sha256" in e for e in errors)

    def test_save_arrays_manifest_catches_missing_member(self, tmp_path):
        p = str(tmp_path / "a.npz")
        checkpoint.save_arrays(
            {"w": np.ones(3, np.float32),
             "__opt__:0": np.zeros(3, np.float32)}, p, {"mark": 111})
        arrays, aux = checkpoint.load_arrays(p)   # intact file loads
        assert aux["mark"] == 111 and set(arrays) == {"w", "__opt__:0"}
        # rebuild the npz minus the moment array but with the original
        # metadata: the member/manifest cross-check must fail loudly
        with np.load(p, allow_pickle=False) as z:
            meta, w = str(z["__meta__"]), z["w"]
        p2 = str(tmp_path / "b.npz")
        np.savez(p2, __meta__=meta, w=w)
        with pytest.raises(ValueError, match="manifest"):
            checkpoint.load_arrays(p2)
        # tampered aux: digest check
        p3 = str(tmp_path / "c.npz")
        np.savez(p3, __meta__=meta.replace("111", "222"), w=w,
                 **{"__opt__:0": np.zeros(3, np.float32)})
        with pytest.raises(ValueError, match="digest"):
            checkpoint.load_arrays(p3)

    def test_apply_rejects_params_opt_mismatch(self, tmp_path):
        m = _mlp(graph=False)
        x, y = _arrays()
        m.train_step(tensor.from_numpy(x[:BS]), tensor.from_numpy(y[:BS]))
        p = str(tmp_path / "s.npz")
        m.save_states(p)
        arrays, aux = checkpoint.load_arrays(p)
        assert any(k.startswith("__opt__:") for k in arrays)
        arrays.pop("__opt__:0")
        with pytest.raises(ValueError, match="mismatch"):
            checkpoint._apply(m, arrays, aux)


# ---------------------------------------------------------------------------
# the orchestrator: preemption, retries, aborts, heartbeat, telemetry
# ---------------------------------------------------------------------------

class TestTrainRunner:
    def test_sigterm_checkpoints_at_step_boundary(self, tmp_path):
        store = str(tmp_path / "records.jsonl")

        def hook(step, outs):
            if step == 1:
                signal.raise_signal(signal.SIGTERM)

        prev = signal.getsignal(signal.SIGTERM)
        r = _tiny_runner(tmp_path, total=6, record_store=store,
                         on_step=hook)
        res = r.run()
        assert res.outcome == "preempted" and res.steps == 2
        assert r.ckpt.steps() == [2]
        assert signal.getsignal(signal.SIGTERM) is prev   # restored
        entry = RunRecord(store).entries()[-1]
        assert entry["kind"] == "train_run"
        assert entry["payload"]["steps"] == 2
        assert entry["payload"]["outcome"] == "preempted"

        m2 = TinyModel()
        r2 = _tiny_runner(tmp_path, model=m2, total=6,
                          record_store=store)
        res2 = r2.run()
        assert res2.resumed_from == 2 and res2.outcome == "completed"
        assert res2.steps == 6
        np.testing.assert_array_equal(m2.w.data, 6 * np.ones(4, np.float32))
        assert RunRecord(store).validate() == []

    def test_transient_failures_retry_with_backoff(self, tmp_path):
        class Flaky(TinyModel):
            fails_left = 2

            def train_step(self, x, y):
                if self.fails_left:
                    self.fails_left -= 1
                    raise RuntimeError("transient device error")
                return super().train_step(x, y)

        sleeps = []
        r = _tiny_runner(tmp_path, model=Flaky(), total=2, max_retries=3,
                         backoff_base=0.01, _sleep=sleeps.append)
        with pytest.warns(UserWarning, match="retrying"):
            res = r.run()
        assert res.outcome == "completed" and res.steps == 2
        assert sleeps == [0.01, 0.02]   # bounded exponential backoff

    def test_repeated_failure_emergency_ckpt_record_fatal(self, tmp_path):
        class Dead(TinyModel):
            def train_step(self, x, y):
                raise RuntimeError("device gone")

        store = str(tmp_path / "records.jsonl")
        fatals = []
        r = _tiny_runner(tmp_path, model=Dead(), total=4, max_retries=1,
                         backoff_base=0.001, _sleep=lambda s: None,
                         record_store=store, on_fatal=fatals.append)
        with pytest.warns(UserWarning, match="retrying"):
            with pytest.raises(TrainAborted):
                r.run()
        assert fatals and "failed after 2 attempt" in fatals[0]
        assert r.ckpt.steps() == [0]     # emergency commit landed
        entry = RunRecord(store).entries()[-1]
        assert entry["payload"]["outcome"] == "aborted"
        assert entry["payload"]["steps"] == 0

    def test_emergency_ckpt_replays_the_failed_steps_batch(self, tmp_path):
        # retry exhaustion draws the batch before failing; the emergency
        # checkpoint must save the PRE-draw cursor so the resumed run
        # trains on that batch instead of skipping it
        seen = []

        class Rec(TinyModel):
            def train_step(self, x, y):
                seen.append(np.asarray(x).copy())
                return super().train_step(x, y)

        class Dies(Rec):
            def train_step(self, x, y):
                if self._step_count >= 2:
                    raise RuntimeError("device gone")
                return super().train_step(x, y)

        r = _tiny_runner(tmp_path, model=Dies(), total=4, max_retries=0,
                         on_fatal=lambda m: None)
        with pytest.raises(TrainAborted):
            r.run()
        m2 = Rec()
        res = _tiny_runner(tmp_path, model=m2, total=4).run()
        assert res.resumed_from == 2 and res.steps == 4
        # the four batches trained on are exactly the uninterrupted
        # sequence: nothing skipped, nothing trained twice
        x, y = _arrays()
        expected = [bx for bx, _ in _loader(x, y)][:4]
        assert len(seen) == 4
        for got, exp in zip(seen, expected):
            np.testing.assert_array_equal(got, exp)

    def test_resume_without_run_state_uses_completed_step_convention(
            self, tmp_path):
        # a checkpoint saved directly through the manager (no RunState)
        # still carries aux["step"] = steps COMPLETED; resume must start
        # at that index, not skip a step
        m = TinyModel()
        for _ in range(3):
            m.train_step(None, None)
        mgr = AsyncCheckpointManager(str(tmp_path / "ck"))
        mgr.save(3, m, block=True)

        m2 = TinyModel()
        r = _tiny_runner(tmp_path, model=m2, total=6)
        with pytest.warns(UserWarning, match="without run_state"):
            res = r.run()
        assert res.start_step == 3 and res.resumed_from == 3
        assert res.steps == 6
        # every step index 3..5 executed exactly once: w = 3 + 3
        np.testing.assert_array_equal(m2.w.data, 6 * np.ones(4, np.float32))

    def test_background_write_failure_takes_fatal_path(self, tmp_path,
                                                       monkeypatch):
        # an ENOSPC surfacing from the writer thread must become a
        # recorded abort (record + on_fatal), not an unrecorded crash
        def boom(arrays, fpath, aux=None):
            raise OSError("No space left on device")

        monkeypatch.setattr(checkpoint, "save_arrays", boom)
        store = str(tmp_path / "records.jsonl")
        fatals = []
        r = _tiny_runner(tmp_path, total=6, save_every=2,
                         record_store=store, on_fatal=fatals.append)
        with pytest.warns(UserWarning, match="emergency checkpoint failed"):
            with pytest.raises(TrainAborted, match="checkpoint write"):
                r.run()
        assert fatals and "No space left" in fatals[0]
        entry = RunRecord(store).entries()[-1]
        assert entry["payload"]["outcome"] == "aborted"
        assert RunRecord(store).validate() == []

    def test_final_save_not_duplicated_on_cadence_boundary(self, tmp_path):
        # total_steps landing exactly on save_every must not re-snapshot
        # the same step after the in-flight cadence save commits
        writes = []
        orig = checkpoint.save_arrays

        def counting(arrays, fpath, aux=None):
            writes.append(fpath)
            return orig(arrays, fpath, aux)

        r = _tiny_runner(tmp_path, total=4, save_every=2)
        import unittest.mock as mock
        with mock.patch.object(checkpoint, "save_arrays", counting):
            res = r.run()
        assert res.outcome == "completed"
        assert len(writes) == len(set(writes)) == 2   # steps 2 and 4, once

    def test_programming_errors_do_not_retry(self, tmp_path):
        class Buggy(TinyModel):
            def train_step(self, x, y):
                raise ValueError("shape bug")

        r = _tiny_runner(tmp_path, model=Buggy(), total=2,
                         on_fatal=lambda m: None)
        with pytest.raises(ValueError, match="shape bug"):
            r.run()

    def test_heartbeat_hang_appends_record_and_fires(self, tmp_path):
        store = str(tmp_path / "records.jsonl")
        fatals = []

        def hook(step, outs):
            if step == 0:
                time.sleep(0.5)   # wedge: no beat while "hung"

        r = _tiny_runner(tmp_path, total=2, record_store=store,
                         on_step=hook, on_fatal=fatals.append)
        r.heartbeat = failure.Heartbeat(
            timeout=0.15, check_every=0.03,
            on_failure=r._heartbeat_failure)
        res = r.run()
        r.__exit__()
        assert r.heartbeat.fired and fatals
        assert "no heartbeat" in fatals[0]
        entry = RunRecord(store).entries()[-1]
        assert entry["payload"]["outcome"] == "hung"
        assert res.steps == 2   # stub "recovered"; run ran to the end

    def test_async_write_overlaps_stepping(self, tmp_path, monkeypatch):
        """The acceptance proof that serialization never blocks the step
        thread: with the writer slowed to 250 ms, whole train.step spans
        land strictly inside a train.ckpt.write span's window."""
        ev = str(tmp_path / "events.jsonl")
        events.configure(path=ev)
        real = checkpoint.save_arrays

        def slow_save(arrays, fpath, aux=None):
            time.sleep(0.25)
            real(arrays, fpath, aux)

        monkeypatch.setattr(checkpoint, "save_arrays", slow_save)
        r = _tiny_runner(tmp_path, total=8, save_every=3,
                         on_step=lambda s, o: time.sleep(0.01))
        res = r.run()
        r.__exit__()
        events.configure()   # close the sink before reading it
        assert res.outcome == "completed"
        spans = [json.loads(ln) for ln in open(ev)]
        spans = [s for s in spans if s["kind"] == "span"]

        def window(s):
            return s["t"] - s["dur_ms"] / 1e3, s["t"]

        writes = [window(s) for s in spans
                  if s["name"] == "train.ckpt.write"]
        steps = [window(s) for s in spans if s["name"] == "train.step"]
        assert writes and steps
        overlapped = sum(
            1 for (s0, s1) in steps
            if any(w0 < s0 and s1 < w1 for (w0, w1) in writes))
        assert overlapped >= 1, (writes, steps)
        # and the step-thread cost (snapshot) stayed far below the
        # serialize cost it was decoupled from
        snaps = [s["dur_ms"] for s in spans
                 if s["name"] == "train.ckpt.snapshot"]
        assert snaps and max(snaps) < 200.0

    def test_preemption_handler_restores_and_reraises_sigint(self):
        p = PreemptionHandler(signals=(signal.SIGTERM,))
        prev = signal.getsignal(signal.SIGTERM)
        with p:
            assert not p.requested
            signal.raise_signal(signal.SIGTERM)
            assert p.requested and p.signum == signal.SIGTERM
        assert signal.getsignal(signal.SIGTERM) is prev

    def test_heartbeat_stop_idempotent_and_daemon(self):
        hb = failure.Heartbeat(timeout=5.0, check_every=0.01)
        hb.stop()              # before start: no-op
        hb.start()
        assert hb._thread.daemon
        hb.stop()
        hb.stop()              # idempotent
        # stop() from the monitor thread itself must not self-join
        stopped = []
        hb2 = failure.Heartbeat(
            timeout=0.05, check_every=0.02,
            on_failure=lambda age, step: (hb2.stop(), stopped.append(1)))
        hb2.start()
        time.sleep(0.3)
        assert stopped == [1] and hb2.fired


# ---------------------------------------------------------------------------
# durable records: schema + lint coverage for the train_run kind
# ---------------------------------------------------------------------------

class TestTrainRunRecords:
    def _payload(self, **over):
        p = {"steps": 100, "wall_s": 12.5, "ckpt_count": 4,
             "resumed_from": -1, "outcome": "completed"}
        p.update(over)
        return p

    def test_entry_roundtrip(self, tmp_path):
        store = RunRecord(str(tmp_path / "r.jsonl"))
        store.append(record.new_entry("train_run", "cpu", True, "cpu",
                                      payload=self._payload()))
        assert store.validate() == []
        assert store.latest(kind="train_run", smoke=True) is not None

    def test_missing_numeric_field_fails_loudly(self):
        p = self._payload()
        del p["ckpt_count"]
        with pytest.raises(SchemaError, match="ckpt_count"):
            record.new_entry("train_run", "cpu", True, "cpu", payload=p)
        with pytest.raises(SchemaError, match="resumed_from"):
            record.new_entry("train_run", "cpu", True, "cpu",
                             payload=self._payload(resumed_from="three"))

    def test_record_check_lints_train_run_lines(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import record_check
        store = RunRecord(str(tmp_path / "runs" / "records.jsonl"))
        store.append(record.new_entry("train_run", "cpu", True, "cpu",
                                      payload=self._payload()))
        assert record_check.check_root(str(tmp_path)) == []
        bad = dict(record.new_entry("train_run", "cpu", True, "cpu",
                                    payload=self._payload()))
        del bad["payload"]["steps"]
        bad["run_id"] = "other"
        with open(store.path, "a") as f:
            f.write(json.dumps(bad) + "\n")
        errors = record_check.check_root(str(tmp_path))
        assert errors and any("steps" in e for e in errors)


# ---------------------------------------------------------------------------
# ZeRO-1: sharded optimizer state must round-trip through the orchestrator
# ---------------------------------------------------------------------------


def test_zero1_opt_state_roundtrips_through_orchestrator(tmp_path):
    """DistOpt(shard_weight_update=True): checkpoints written by the
    orchestrator hold natural-shaped moments, and a resumed run seeds
    the sharded executor without changing the trajectory."""
    x, y = _arrays(seed=1, n=64, dim=16)

    def build():
        parallel.set_mesh(parallel.data_parallel_mesh(8))
        np.random.seed(0)
        tensor.set_seed(0)
        m = models.MLP(perceptron_size=(16,), num_classes=CLASSES)
        m.set_optimizer(opt.DistOpt(opt.Adam(lr=1e-2),
                                    shard_weight_update=True))
        m.compile([tensor.from_numpy(x)], is_train=True, use_graph=True)
        return m

    def run(m, d, total):
        ld = DataLoader(x, y, batch_size=64, seed=3, drop_last=True,
                        use_native=False)
        r = TrainRunner(m, ld, total_steps=total,
                        ckpt=AsyncCheckpointManager(str(tmp_path / d),
                                                    save_every=1))
        res = r.run()
        r.__exit__()
        return res

    m_straight = build()
    run(m_straight, "a", 4)

    m_killed = build()
    run(m_killed, "b", 3)
    del m_killed

    m_resumed = build()
    res = run(m_resumed, "b", 4)
    assert res.resumed_from == 3
    for n, p in _params(m_straight).items():
        np.testing.assert_allclose(p, _params(m_resumed)[n], rtol=2e-4,
                                   atol=1e-6, err_msg=n)
