"""Tests for singa_tpu.obs — the durable run-record store, the schema,
the event/span layer, and the producer protections (the round-5
data-loss regression suite)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import singa_tpu as st
from singa_tpu.obs import events, record, schema
from singa_tpu.obs.record import RunRecord
from singa_tpu.obs.schema import SchemaError

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _chip_entry(run_id="r-chip", **over):
    stages = over.pop("stages", {
        "probe": {"ok": True, "s": 1.0, "result": "tpu"},
        "llama_headline": {"ok": True, "s": 9.0,
                           "result": {"batch": 8, "seq": 1024,
                                      "step_ms": 349.0, "mfu": 0.65,
                                      "tokens_per_s": 23455.6}}})
    return record.new_entry("session", "tpu", False, "TPU v5e",
                            run_id=run_id, stages=stages, **over)


def _smoke_entry(run_id="r-smoke"):
    return record.new_entry(
        "session", "cpu", True, "cpu", run_id=run_id,
        stages={"probe": {"ok": True, "s": 0.1, "result": "cpu"}})


@pytest.fixture(autouse=True)
def _reset_events():
    yield
    events.configure()


class TestRunRecordStore:
    def test_smoke_append_leaves_onchip_line_byte_identical(self, tmp_path):
        """THE round-5 regression: a smoke write must never touch the
        on-chip entry's bytes."""
        store = RunRecord(str(tmp_path / "records.jsonl"))
        store.append(_chip_entry())
        chip_line = store.raw_lines()[0]
        store.append(_smoke_entry())
        lines = store.raw_lines()
        assert len(lines) == 2
        assert lines[0] == chip_line  # byte-for-byte

    def test_smoke_never_shadows_onchip_for_consumers(self, tmp_path):
        store = RunRecord(str(tmp_path / "records.jsonl"))
        store.append(_chip_entry())
        store.append(_smoke_entry())
        latest = store.latest(kind="session")
        assert latest["platform"] == "tpu" and latest["smoke"] is False
        # smoke is reachable only by explicit request
        assert store.latest(kind="session", smoke=True)["platform"] == "cpu"

    def test_same_run_supersedes_its_own_entry_only(self, tmp_path):
        store = RunRecord(str(tmp_path / "records.jsonl"))
        store.append(_chip_entry(run_id="rA"))
        store.append(_chip_entry(run_id="rB"))
        updated = _chip_entry(run_id="rA")
        updated["stages"]["extra"] = {"ok": True, "s": 1.0, "result": "x"}
        store.append(updated)
        entries = store.entries()
        assert len(entries) == 2
        assert "extra" in [e for e in entries if e["run_id"] == "rA"
                           ][0]["stages"]

    def test_append_is_atomic_and_leaves_no_temp_files(self, tmp_path):
        store = RunRecord(str(tmp_path / "records.jsonl"))
        for i in range(5):
            store.append(_chip_entry(run_id=f"r{i}"))
        # only the store + its lock sidecar; no stranded .tmp files
        assert sorted(p.name for p in tmp_path.iterdir()) == \
            [".records.jsonl.lock", "records.jsonl"]
        # every intermediate state was a complete, parseable store
        assert len(store.entries()) == 5
        assert store.validate() == []

    def test_append_refuses_to_write_over_corrupt_store(self, tmp_path):
        p = tmp_path / "records.jsonl"
        p.write_text('{"not a valid entry\n')
        before = p.read_bytes()
        with pytest.raises(SchemaError, match="corrupt store line"):
            RunRecord(str(p)).append(_chip_entry())
        assert p.read_bytes() == before  # untouched

    def test_validate_names_the_missing_field(self, tmp_path):
        p = tmp_path / "records.jsonl"
        e = _chip_entry()
        del e["platform"]
        p.write_text(json.dumps(e) + "\n")
        errs = RunRecord(str(p)).validate()
        assert len(errs) == 1 and "'platform'" in errs[0]

    def test_validate_flags_duplicate_keys(self, tmp_path):
        p = tmp_path / "records.jsonl"
        line = json.dumps(_chip_entry())
        p.write_text(line + "\n" + line + "\n")
        errs = RunRecord(str(p)).validate()
        assert len(errs) == 1 and "duplicate key" in errs[0]

    def test_invalid_entry_rejected_on_append(self, tmp_path):
        store = RunRecord(str(tmp_path / "records.jsonl"))
        bad = _chip_entry()
        bad["smoke"] = "no"  # not a bool
        with pytest.raises(SchemaError, match="'smoke'"):
            store.append(bad)
        assert store.raw_lines() == []


class TestSchema:
    def test_require_names_field_and_context(self):
        with pytest.raises(SchemaError) as ei:
            schema.require({"mfu": 0.27}, "batch", "stage 'resnet50'")
        assert "stage 'resnet50'" in str(ei.value)
        assert "'batch'" in str(ei.value)
        assert ei.value.field == "batch"

    def test_require_rejects_non_dict(self):
        with pytest.raises(SchemaError, match="expected an object"):
            schema.require(None, "batch", "ctx")

    def test_stage_shapes(self):
        schema.validate_stage("s", {"skipped": True})
        schema.validate_stage("s", {"ok": True, "s": 1.0, "result": {}})
        schema.validate_stage("s", {"ok": False, "error": "Boom: x"})
        with pytest.raises(SchemaError, match="'error'"):
            schema.validate_stage("s", {"ok": False})
        with pytest.raises(SchemaError, match="'ok'"):
            schema.validate_stage("s", {"result": 3})

    def test_legacy_session_doc_is_grandfathered(self):
        # the committed r4 record's shape: stages + device, no schema
        # fields — structurally valid
        schema.validate_session_doc(
            {"stages": {"probe": {"ok": True, "s": 1, "result": "tpu"}},
             "device": "TPU7x"})

    def test_v1_session_doc_is_strict(self):
        doc = _chip_entry()
        del doc["created_at"]
        with pytest.raises(SchemaError, match="'created_at'"):
            schema.validate_session_doc(doc)

    def test_bench_doc_null_parsed_allowed_partial_rejected(self):
        base = {"n": 1, "cmd": "python bench.py", "rc": 1, "tail": ""}
        schema.validate_bench_doc(dict(base, parsed=None))
        with pytest.raises(SchemaError, match="'vs_baseline'"):
            schema.validate_bench_doc(dict(base, parsed={
                "metric": "m", "value": 1.0, "unit": "u"}))

    def test_wire_byte_pair_is_linted_when_present(self):
        """ISSUE-10 satellite: train_run/bench payloads carrying the
        quantized-sync wire-byte numerics are linted like the required
        fields — either key alone (or a non-numeric value) is rejected
        with the missing/invalid field NAMED; absent pair stays valid."""
        train = {"steps": 3, "wall_s": 1.0, "ckpt_count": 1,
                 "resumed_from": -1}
        schema.validate_train_run_payload(dict(train))      # pair absent: ok
        ok = dict(train, wire_bytes_compressed=72288,
                  wire_bytes_f32_equiv=279304)
        schema.validate_train_run_payload(ok)
        with pytest.raises(SchemaError, match="wire_bytes_f32_equiv"):
            schema.validate_train_run_payload(
                dict(train, wire_bytes_compressed=72288))
        with pytest.raises(SchemaError, match="wire_bytes_compressed"):
            schema.validate_train_run_payload(
                dict(train, wire_bytes_f32_equiv=279304))
        with pytest.raises(SchemaError, match="must be numeric"):
            schema.validate_train_run_payload(
                dict(ok, wire_bytes_compressed=True))
        # the bench kind goes through the same check via validate_entry
        import time as _time
        entry = {"schema_version": schema.SCHEMA_VERSION, "run_id": "b1",
                 "kind": "bench", "platform": "cpu", "smoke": True,
                 "device": "cpu", "created_at": _time.time(),
                 "payload": {"headline": {},
                             "wire_bytes_compressed": 1}}
        with pytest.raises(SchemaError, match="wire_bytes_f32_equiv"):
            schema.validate_entry(entry)
        entry["payload"]["wire_bytes_f32_equiv"] = 4
        schema.validate_entry(entry)


class TestEvents:
    def test_sinkless_span_is_a_bare_annotation(self, monkeypatch):
        """No sink, no profiler session: span() is the profiler's own
        (inert) TraceAnnotation — no _Span, no emit, no id allocation,
        even inside an active obs.trace context."""
        from jax.profiler import TraceAnnotation
        from singa_tpu.obs import trace
        events.configure()
        assert not events.enabled()
        calls = []
        monkeypatch.setattr(events, "_emit",
                            lambda *a: calls.append(("emit",) + a))
        monkeypatch.setattr(events._Span, "__init__",
                            lambda *a: calls.append(("_Span",) + a))
        monkeypatch.setattr(trace, "new_span_id",
                            lambda: calls.append(("id",)) or "s0")
        with trace.activate("tr-quiet"):
            with events.span("quiet", slot=3) as outer:
                with events.span("quiet.child"):
                    pass
        assert type(outer) is TraceAnnotation
        assert calls == []

    @pytest.mark.parametrize("sink", [False, True])
    def test_span_reaches_the_profiler_under_its_own_name(
            self, tmp_path, host_profile, sink):
        """With or without the JSONL sink, a span inside a profiler
        session (Python tracer off) is an event of the host thread's
        line named by the span's name alone; attributes become stats.
        There is no switch: nothing is configured for this."""
        p = str(tmp_path / "ev.jsonl")
        events.configure(path=p) if sink else events.configure()
        try:
            with host_profile(tmp_path / "xprof", ("obs.",)) as lines:
                with events.span("obs.outer"):
                    with events.span("obs.inner", slot=3):
                        pass
        finally:
            events.configure()
        (line,) = lines
        by_name = {e[0]: e for e in line}
        assert sorted(by_name) == ["obs.inner", "obs.outer"]
        inner, outer = by_name["obs.inner"], by_name["obs.outer"]
        assert outer[1] <= inner[1] and inner[2] <= outer[2]
        assert inner[3]["slot"] == 3
        if sink:
            evs = [json.loads(l) for l in open(p)]
            assert [e["name"] for e in evs] == ["obs.inner", "obs.outer"]
            assert evs[0]["slot"] == 3 and "dur_ms" in evs[0]

    def test_span_counter_gauge_roundtrip(self, tmp_path):
        p = str(tmp_path / "ev.jsonl")
        events.configure(path=p)
        with events.span("work", tag="t"):
            pass
        events.counter("bytes", 4096, axis="data")
        events.gauge("loss", 3.5)
        events.configure()  # close
        evs = [json.loads(l) for l in open(p)]
        assert [e["kind"] for e in evs] == ["span", "counter", "gauge"]
        assert evs[0]["name"] == "work" and "dur_ms" in evs[0]
        assert evs[1]["value"] == 4096 and evs[1]["axis"] == "data"
        assert evs[2]["value"] == 3.5

    def test_span_records_exception_type(self, tmp_path):
        p = str(tmp_path / "ev.jsonl")
        events.configure(path=p)
        with pytest.raises(RuntimeError):
            with events.span("explode"):
                raise RuntimeError("x")
        events.configure()
        (ev,) = [json.loads(l) for l in open(p)]
        assert ev["error"] == "RuntimeError"


class TestConcurrencyRegressions:
    """Forced-interleaving reproductions of the ISSUE 15 conclint
    fixes (two threads + a scheduling hook each): these tests FAIL on
    the pre-fix code — the hook steers the exact window the race
    needs, so the reproduction is deterministic, not statistical."""

    def test_sink_swap_mid_emit_does_not_crash(self, tmp_path,
                                               monkeypatch):
        """obs.events._emit used to read the module-global ``_sink``
        twice (liveness check, then use); a concurrent ``configure()``
        clearing the sink between them crashed the EMITTING thread —
        i.e. the train/serve step loop — with AttributeError.  The fix
        snapshots the reference once; emitting into the just-closed
        sink is a silent no-op.  Hook: ``trace.current_trace_id`` runs
        between the two accesses, so patching it to run the concurrent
        configure() on another thread forces the interleave."""
        import threading

        from singa_tpu.obs import trace as obs_trace

        events.configure(path=str(tmp_path / "ev.jsonl"))
        real = obs_trace.current_trace_id
        swapped = threading.Event()

        def hook():
            t = threading.Thread(
                target=lambda: (events.configure(), swapped.set()))
            t.start()
            assert swapped.wait(5.0), "concurrent configure() wedged"
            t.join(5.0)
            return real()

        monkeypatch.setattr(obs_trace, "current_trace_id", hook)
        # pre-fix: AttributeError ('NoneType' object has no 'emit')
        events.counter("conc.race", 1)
        monkeypatch.setattr(obs_trace, "current_trace_id", real)
        assert events.get_sink() is None    # the swap really landed

    def test_flight_register_during_broadcast_is_serialized(
            self, monkeypatch):
        """obs.flight.broadcast used to iterate the live ``_RECORDERS``
        WeakSet while register() (another thread building an engine)
        could add to it — 'Set changed size during iteration' raised on
        the BROADCASTING thread, inside faults.fire on the step path.
        The fix snapshots the set under a registry lock that register()
        shares.  Hook: an instrumented WeakSet whose iteration pauses
        mid-way while the other thread attempts to register."""
        import threading
        import weakref

        from singa_tpu.obs import flight

        recs = [flight.FlightRecorder(capacity=4) for _ in range(3)]
        mid_iter = threading.Event()
        reg_attempted = threading.Event()

        class SlowIterSet(weakref.WeakSet):
            def __iter__(self):
                first = True
                for x in super().__iter__():
                    if first:
                        first = False
                        mid_iter.set()
                        # give the registering thread its window; on
                        # the fixed code it blocks on the registry
                        # lock, so this deliberately times out
                        reg_attempted.wait(0.3)
                    yield x

        slow = SlowIterSet(recs)
        monkeypatch.setattr(flight, "_RECORDERS", slow)
        late = flight.FlightRecorder(capacity=4)
        reg_done = threading.Event()

        def do_register():
            assert mid_iter.wait(5.0)
            flight.register(late)       # pre-fix: lands mid-iteration
            reg_attempted.set()
            reg_done.set()

        t = threading.Thread(target=do_register)
        t.start()
        # pre-fix: RuntimeError('Set changed size during iteration')
        flight.broadcast("counter", "conc.race")
        t.join(5.0)
        assert reg_done.is_set(), "register() never completed"
        for r in recs:
            assert [e["name"] for e in r.snapshot()] == ["conc.race"]
        # the late ring is subscribed from the next broadcast on
        flight.broadcast("counter", "conc.race2")
        assert [e["name"] for e in late.snapshot()] == ["conc.race2"]


class _TinyMLP(st.model.Model):
    def __init__(self):
        super().__init__()
        self.fc1 = st.layer.Linear(16)
        self.fc2 = st.layer.Linear(4)

    def forward(self, x):
        return self.fc2(st.autograd.relu(self.fc1(x)))

    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = st.autograd.softmax_cross_entropy(out, y)
        self.optimizer(loss)
        return out, loss


class TestHotPathEmission:
    def test_compiled_train_step_emits_spans(self, tmp_path):
        """ISSUE acceptance: span/counter emission from a compiled
        train_step on CPU — compile once, execute per step."""
        p = str(tmp_path / "ev.jsonl")
        events.configure(path=p)
        m = _TinyMLP()
        m.set_optimizer(st.opt.SGD(lr=0.1))
        x = st.tensor.from_numpy(np.random.randn(8, 8).astype(np.float32))
        y = st.tensor.from_numpy(
            np.random.randint(0, 4, (8,)).astype(np.int32))
        m.compile([x], is_train=True, use_graph=True)
        for _ in range(3):
            m.train_step(x, y)
        m.graph.cost_analysis()
        events.configure()
        names = [json.loads(l)["name"] for l in open(p)]
        assert names.count("graph.compile") == 1
        assert names.count("graph.execute") == 3
        assert names.count("model.train_step") == 3
        assert "graph.cost_analysis" in names

    def test_grad_sync_span_and_comm_counters_under_mesh(self, tmp_path):
        try:
            st.parallel.set_mesh(st.parallel.mesh.data_parallel_mesh(8))
        except Exception:
            pytest.skip("8-device mesh unavailable")
        p = str(tmp_path / "ev.jsonl")
        events.configure(path=p)
        try:
            m = _TinyMLP()
            m.set_optimizer(st.opt.DistOpt(st.opt.SGD(lr=0.1)))
            x = st.tensor.from_numpy(
                np.random.randn(16, 8).astype(np.float32))
            y = st.tensor.from_numpy(
                np.random.randint(0, 4, (16,)).astype(np.int32))
            m.compile([x], is_train=True, use_graph=True)
            m.train_step(x, y)
        except AttributeError as e:
            pytest.skip(f"shard_map unavailable in this jax: {e}")
        finally:
            events.configure()
        evs = [json.loads(l) for l in open(p)]
        names = [e["name"] for e in evs]
        assert "opt.grad_sync" in names
        grads = [e for e in evs if e["name"] == "comm.allreduce_grads.bytes"]
        assert grads and grads[0]["value"] > 0
        assert grads[0]["axis"] == "data"

    def test_disabled_emission_does_not_perturb_training(self):
        events.configure()
        m = _TinyMLP()
        m.set_optimizer(st.opt.SGD(lr=0.1))
        x = st.tensor.from_numpy(np.random.randn(8, 8).astype(np.float32))
        y = st.tensor.from_numpy(
            np.random.randint(0, 4, (8,)).astype(np.int32))
        m.compile([x], is_train=True, use_graph=True)
        out, loss = m.train_step(x, y)
        assert np.isfinite(float(loss.to_numpy()))


class TestSmokeSessionRegression:
    """End-to-end acceptance: a smoke-mode tools/tpu_session.py run
    against a dir holding an on-chip record leaves that record
    byte-identical (the r5 data loss can't recur)."""

    def test_smoke_session_cannot_clobber_onchip_record(self, tmp_path):
        onchip = {"stages": {"probe": {"ok": True, "s": 1.0,
                                       "result": "tpu"}},
                  "device": "TPU v5 lite"}
        target = tmp_path / "tpu_session.json"
        target.write_text(json.dumps(onchip, indent=1))
        before = target.read_bytes()
        notes = tmp_path / "PERF_NOTES.md"
        notes.write_text("# on-chip notes\n")
        env = dict(os.environ,
                   SINGA_TPU_SESSION_SMOKE="1",
                   SINGA_TPU_SESSION_ONLY="probe",
                   SINGA_TPU_SESSION_DIR=str(tmp_path),
                   SINGA_TPU_SESSION_BUDGET_S="120",
                   JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "tpu_session.py")],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        # the on-chip record and notes are untouched, byte-for-byte
        assert target.read_bytes() == before
        assert notes.read_text() == "# on-chip notes\n"
        # the smoke run's evidence went to its own snapshot + the store
        smoke_doc = json.loads((tmp_path / "tpu_session.smoke.json")
                               .read_text())
        assert smoke_doc["smoke"] is True
        assert smoke_doc["platform"] == "cpu"
        schema.validate_session_doc(smoke_doc)
        store = RunRecord(str(tmp_path / "runs" / "records.jsonl"))
        assert store.validate() == []
        entry = store.latest(kind="session", smoke=True)
        assert entry is not None and entry["platform"] == "cpu"
        # and the store holds no fake on-chip evidence
        assert store.latest(kind="session", smoke=False) is None

    def test_only_mode_rerun_merges_base_and_preserves_onchip(
            self, tmp_path):
        """Code-review regression: an ONLY-mode rerun must merge FROM
        tpu_session.json (so stages it does not rerun survive), and a
        rerun that resolves to CPU must redirect its write — the
        on-chip record stays byte-identical either way."""
        onchip = {"stages": {
            "probe": {"ok": True, "s": 1.0, "result": "tpu"},
            "llama_headline": {"ok": True, "s": 9.0,
                               "result": {"batch": 8, "mfu": 0.65}}},
            "device": "TPU v5 lite"}
        target = tmp_path / "tpu_session.json"
        target.write_text(json.dumps(onchip, indent=1))
        before = target.read_bytes()
        code = f"""
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location(
    "tpu_session", {os.path.join(REPO, 'tools', 'tpu_session.py')!r})
ts = importlib.util.module_from_spec(spec)
spec.loader.exec_module(ts)
# non-smoke ONLY rerun merges from the BASE record (pre-probe)
assert ts._merge_source_path().endswith("tpu_session.json"), \\
    ts._merge_source_path()
ts._RESULTS.update(json.load(open(ts._merge_source_path())))
# the rerun's probe resolved to CPU: the write must redirect
ts._RESULTS["platform"] = "cpu"
ts._RESULTS["stages"]["probe"] = {{"ok": True, "s": 0.1, "result": "cpu"}}
ts._finish()
"""
        env = dict(os.environ, SINGA_TPU_SESSION_DIR=str(tmp_path),
                   SINGA_TPU_SESSION_ONLY="probe")
        env.pop("SINGA_TPU_SESSION_SMOKE", None)
        r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        assert target.read_bytes() == before  # on-chip untouched
        cpu_doc = json.loads((tmp_path / "tpu_session.cpu.json").read_text())
        # merged: the un-rerun on-chip stage survived into the rerun doc
        assert "llama_headline" in cpu_doc["stages"]

    def test_only_merge_strips_platform_so_failed_probe_stays_smoke(
            self, tmp_path):
        """Code-review regression: a v1 on-chip record carries
        platform='tpu' at top level; an ONLY rerun whose probe FAILS
        must not inherit it — else _finish would overwrite the on-chip
        record and append a falsified non-smoke store entry."""
        onchip = {"schema_version": 1, "run_id": "r6", "kind": "session",
                  "platform": "tpu", "smoke": False,
                  "device": "TPU v5 lite", "created_at": 1.0,
                  "stages": {"probe": {"ok": True, "s": 1.0,
                                       "result": "tpu"}}}
        target = tmp_path / "tpu_session.json"
        target.write_text(json.dumps(onchip, indent=1))
        before = target.read_bytes()
        code = f"""
import importlib.util, json
spec = importlib.util.spec_from_file_location(
    "tpu_session", {os.path.join(REPO, 'tools', 'tpu_session.py')!r})
ts = importlib.util.module_from_spec(spec)
spec.loader.exec_module(ts)
ts._merge_only_results(ts._merge_source_path())
assert "platform" not in ts._RESULTS, ts._RESULTS.keys()
# probe fails: platform never set; the merged stages stay
ts._RESULTS["stages"]["probe"] = {{"ok": False, "error": "RuntimeError: x"}}
assert ts._smoke_like() is True
ts._finish()
"""
        env = dict(os.environ, SINGA_TPU_SESSION_DIR=str(tmp_path),
                   SINGA_TPU_SESSION_ONLY="probe")
        env.pop("SINGA_TPU_SESSION_SMOKE", None)
        r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        assert target.read_bytes() == before  # on-chip untouched
        # the store gained NO fake on-chip entry
        store = RunRecord(str(tmp_path / "runs" / "records.jsonl"))
        assert store.latest(kind="session", smoke=False) is None
        assert store.latest(kind="session", smoke=True) is not None


class TestReadmePerfTable:
    def _run(self, args):
        return subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "readme_perf_table.py"),
             "--print"] + args,
            cwd=REPO, capture_output=True, text=True, timeout=120)

    def test_invalid_record_exits_nonzero_with_named_field(self, tmp_path):
        """ISSUE acceptance: never a raw KeyError — a named-field error
        and a real exit code."""
        bad = {"stages": {"probe": {"ok": True, "s": 1, "result": "tpu"},
                          "resnet50": {"ok": True, "s": 2,
                                       "result": {"mfu": 0.27}}},
               "device": "TPU v5 lite"}
        p = tmp_path / "rec.json"
        p.write_text(json.dumps(bad))
        r = self._run(["--record", str(p)])
        assert r.returncode == 2
        assert "'batch'" in r.stderr
        assert "resnet50" in r.stderr
        assert "KeyError" not in r.stderr and "Traceback" not in r.stderr

    def test_smoke_record_refused_for_readme(self, tmp_path):
        doc = {"stages": {"probe": {"ok": True, "s": 1, "result": "cpu"}},
               "device": "cpu"}
        p = tmp_path / "rec.json"
        p.write_text(json.dumps(doc))
        r = self._run(["--record", str(p)])
        assert r.returncode == 2
        assert "smoke/CPU" in r.stderr

    def test_valid_record_builds_table(self, tmp_path):
        doc = {"stages": {
            "probe": {"ok": True, "s": 1, "result": "tpu"},
            "llama_headline": {"ok": True, "s": 9, "result": {
                "batch": 8, "seq": 1024, "step_ms": 349.0,
                "tokens_per_s": 23455.6, "mfu": 0.65}}},
            "device": "TPU v5 lite"}
        p = tmp_path / "rec.json"
        p.write_text(json.dumps(doc))
        r = self._run(["--record", str(p)])
        assert r.returncode == 0, r.stderr[-2000:]
        assert "Llama 0.9B flagship training" in r.stdout
        assert "23,456 tok/s" in r.stdout


class TestRecordCheck:
    def test_committed_records_are_valid(self):
        """The tier-1 lint itself: every record in the tree validates."""
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import record_check
        errors = record_check.check_root(REPO)
        assert errors == [], "\n".join(errors)

    def test_truncated_record_fails_with_named_error(self, tmp_path):
        (tmp_path / "BENCH_r99.json").write_text(
            '{"n": 9, "cmd": "python bench.py", "rc": 0')  # truncated
        (tmp_path / "tpu_session.json").write_text(
            json.dumps({"stages": {"x": {"ok": False}}}))
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import record_check
        errors = record_check.check_root(str(tmp_path))
        assert len(errors) == 2
        assert any("not valid JSON" in e for e in errors)
        assert any("'error'" in e for e in errors)
