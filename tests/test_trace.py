"""ISSUE 11 — trace contexts, the flight recorder, sink rotation, and
the obsq query layer.  Everything here is host-side Python (no jit
compiles): the serve-engine integration half of the tracing acceptance
lives in tests/test_faults.py on the shared llama engine.
"""

import json
import os
import threading

import numpy as np
import pytest

from singa_tpu.obs import events, flight, record as obs_record, trace
from singa_tpu.utils.failure import Heartbeat
from tools import obsq

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(autouse=True)
def _reset_events():
    yield
    events.configure()


def _read(path):
    return [json.loads(l) for l in open(path)]


# ---------------------------------------------------------------------------
# trace contexts
# ---------------------------------------------------------------------------

class TestTraceContext:
    def test_no_trace_outside_activation(self):
        assert trace.current() is None
        assert trace.current_trace_id() is None

    def test_activation_nests_and_restores(self):
        with trace.activate("outer"):
            assert trace.current_trace_id() == "outer"
            with trace.activate("inner"):
                assert trace.current_trace_id() == "inner"
            assert trace.current_trace_id() == "outer"
        assert trace.current() is None

    def test_events_stamp_trace_and_spans_nest(self, tmp_path):
        p = str(tmp_path / "ev.jsonl")
        events.configure(path=p)
        with trace.activate("tr-x"):
            with events.span("outer"):
                with events.span("inner"):
                    events.counter("c", 1)
        events.counter("naked", 1)
        events.configure()
        evs = _read(p)
        by_name = {e["name"]: e for e in evs}
        assert by_name["c"]["trace"] == "tr-x"
        inner, outer = by_name["inner"], by_name["outer"]
        assert inner["trace"] == outer["trace"] == "tr-x"
        # spans nest via the contextvar: inner's parent is outer's id
        assert inner["parent"] == outer["span"]
        assert "parent" not in outer
        # outside any trace: no trace/span fields at all
        assert "trace" not in by_name["naked"]

    def test_thread_does_not_inherit_but_attach_does(self, tmp_path):
        """The satellite contract: a plain Thread starts trace-less (no
        cross-request leakage is structural); capture/attach opts a
        worker in explicitly — concurrently with the spawner running a
        DIFFERENT trace, each side keeps its own."""
        p = str(tmp_path / "ev.jsonl")
        events.configure(path=p)
        captured = []
        release = threading.Event()

        def bare():
            captured.append(trace.current())

        def adopted(ctx):
            with trace.attach(ctx):
                release.wait(5.0)            # spawner is on trace B now
                events.counter("from.worker", 1)

        with trace.activate("trace-A"):
            t0 = threading.Thread(target=bare)
            t0.start(); t0.join()
            t1 = threading.Thread(target=adopted,
                                  args=(trace.capture(),))
            t1.start()
        with trace.activate("trace-B"):
            events.counter("from.main", 1)
            release.set()
            t1.join()
        events.configure()
        assert captured == [None]            # no implicit inheritance
        by_name = {e["name"]: e for e in _read(p)}
        assert by_name["from.worker"]["trace"] == "trace-A"
        assert by_name["from.main"]["trace"] == "trace-B"

    def test_heartbeat_monitor_explicitly_drops_trace(self, tmp_path):
        """Documented drop: the watchdog's events are engine-scoped,
        never attributed to whichever trace was active at start()."""
        p = str(tmp_path / "ev.jsonl")
        events.configure(path=p)
        seen = []

        def on_failure(age, step):
            seen.append(trace.current())
            events.counter("hb.fired", 1)

        hb = Heartbeat(timeout=0.05, check_every=0.01,
                       on_failure=on_failure)
        with trace.activate("step-trace"):
            hb.start()
        for _ in range(200):
            if hb.fired:
                break
            threading.Event().wait(0.01)
        hb.stop()
        events.configure()
        assert seen == [None]
        (ev,) = [e for e in _read(p) if e["name"] == "hb.fired"]
        assert "trace" not in ev


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_is_bounded_and_ordered(self):
        rec = flight.FlightRecorder(capacity=3)
        for i in range(5):
            rec.note("counter", f"e{i}")
        assert [e["name"] for e in rec.snapshot()] == ["e2", "e3", "e4"]

    def test_notes_stamp_the_active_trace(self):
        rec = flight.FlightRecorder()
        with trace.activate("t-9"):
            rec.note("counter", "x")
        rec.note("counter", "y")
        a, b = rec.snapshot()
        assert a["trace"] == "t-9" and "trace" not in b

    def test_dump_refuses_unregistered_site(self, tmp_path):
        rec = flight.FlightRecorder()
        with pytest.raises(ValueError, match="unknown flight-dump site"):
            rec.dump("serve.typo", str(tmp_path))

    def test_dump_is_atomic_and_parseable(self, tmp_path):
        rec = flight.FlightRecorder()
        rec.note("counter", "a", v=1)
        rec.note("hist", "b", value=2.5)
        path = rec.dump("serve.arena", str(tmp_path), reason="why")
        # no stranded temp files; every line parses (obsq's loader)
        assert [os.path.basename(path)] == sorted(os.listdir(tmp_path))
        evs = obsq.load_events(path)
        assert [e["name"] for e in evs[:2]] == ["a", "b"]
        assert evs[-1]["kind"] == "dump" and evs[-1]["reason"] == "why"

    def test_fault_fires_broadcast_into_registered_rings(self):
        from singa_tpu import faults
        from singa_tpu.faults import FaultPlan, FaultSpec
        rec = flight.register(flight.FlightRecorder())
        plan = FaultPlan([FaultSpec("data.next", "error", at=1)])
        with faults.active(plan):
            with pytest.raises(RuntimeError):
                faults.fire("data.next")
            faults.fire("data.next")     # un-fired call: no broadcast
        fired = [e for e in rec.snapshot()
                 if e["name"] == "fault.injected"]
        assert len(fired) == 1 and fired[0]["site"] == "data.next"


# ---------------------------------------------------------------------------
# JSONL sink rotation (SINGA_OBS_MAX_BYTES satellite)
# ---------------------------------------------------------------------------

class TestSinkRotation:
    def test_rollover_bounds_disk_and_keeps_whole_lines(self, tmp_path):
        p = str(tmp_path / "ev.jsonl")
        events.configure(path=p, max_bytes=400)
        for i in range(50):
            events.counter("soak.event", i, pad="x" * 40)
        events.configure()
        rolled = p + ".1"
        assert os.path.exists(rolled), "rotation never triggered"
        # bounded: live file + one rollover, each within the cap
        assert os.path.getsize(p) <= 400
        assert os.path.getsize(rolled) <= 400
        assert sorted(os.listdir(tmp_path)) == ["ev.jsonl", "ev.jsonl.1"]
        # every retained line is complete (rotation is atomic rename,
        # never a mid-line split), and the newest events are retained
        evs = _read(rolled) + _read(p)
        assert all(e["name"] == "soak.event" for e in evs)
        assert evs[-1]["value"] == 49

    def test_default_is_unbounded(self, tmp_path):
        p = str(tmp_path / "ev.jsonl")
        events.configure(path=p)
        for i in range(100):
            events.counter("e", i)
        events.configure()
        assert not os.path.exists(p + ".1")
        assert len(_read(p)) == 100

    def test_bad_max_bytes_rejected_zero_disables(self, tmp_path):
        with pytest.raises(ValueError, match="max_bytes"):
            events.JsonlSink(str(tmp_path / "e.jsonl"), max_bytes=-1)
        # 0 (the SINGA_OBS_MAX_BYTES "off" spelling) disables rotation
        sink = events.JsonlSink(str(tmp_path / "e.jsonl"), max_bytes=0)
        assert sink.max_bytes is None
        sink.close()


# ---------------------------------------------------------------------------
# histogram percentile determinism under ring eviction (satellite)
# ---------------------------------------------------------------------------

class TestHistogramDeterminism:
    def test_summary_reproducible_after_wrap(self, monkeypatch):
        """Regression: for a FIXED insertion order the p50/p90/p99 are
        identical run-to-run once the bounded ring has wrapped, and
        equal the exact nearest-rank quantiles of the most recent
        window (slot = i % cap — the documented contract)."""
        cap = 16
        monkeypatch.setattr(events, "_HIST_CAP", cap)
        vals = [float(v) for v in
                np.random.RandomState(3).permutation(100)]

        def run():
            h = events._Hist()
            for v in vals:
                h.observe(v)
            return h.summary()

        a, b = run(), run()
        assert a == b                      # deterministic, no RNG
        assert a["count"] == 100 and a["min"] == 0.0 and a["max"] == 99.0
        # the ring holds exactly the most recent `cap` observations
        window = sorted(vals[-cap:])
        for q, key in ((50.0, "p50"), (90.0, "p90"), (99.0, "p99")):
            i = min(cap - 1, max(0, int(round(q / 100.0 * (cap - 1)))))
            assert a[key] == window[i], key

    def test_exact_before_wrap(self, monkeypatch):
        monkeypatch.setattr(events, "_HIST_CAP", 64)
        h = events._Hist()
        for v in range(11):
            h.observe(float(v))
        s = h.summary()
        assert (s["p50"], s["p90"], s["p99"]) == (5.0, 9.0, 10.0)


# ---------------------------------------------------------------------------
# obsq — the query layer
# ---------------------------------------------------------------------------

_FIXTURE_RECORDS = os.path.join(REPO, "tests", "data", "obsq",
                                "records.jsonl")
_FIXTURE_EVENTS = os.path.join(REPO, "tests", "data", "obsq",
                               "events.jsonl")


class TestObsq:
    def test_committed_fixture_slo_check_passes(self, capsys):
        """The exact invocation tools/ci_gate.sh stage 3 runs: the
        committed serve_load fixture is reproducible from its committed
        trace events."""
        rc = obsq.main(["slo", "--check",
                        "--records", _FIXTURE_RECORDS,
                        "--events", _FIXTURE_EVENTS])
        assert rc == 0
        assert "reproducible" in capsys.readouterr().out

    def test_slo_check_catches_a_drifted_record(self, tmp_path, capsys):
        entry = json.loads(open(_FIXTURE_RECORDS).read())
        entry["payload"]["ttft_p99_ms"] = 99.0       # drifted claim
        store = tmp_path / "records.jsonl"
        store.write_text(json.dumps(entry) + "\n")
        rc = obsq.main(["slo", "--check", "--records", str(store),
                        "--events", _FIXTURE_EVENTS])
        assert rc == 1
        assert "ttft_p99_ms" in capsys.readouterr().err

    def test_derive_slo_uses_the_live_estimator(self):
        evs = obsq.load_events(_FIXTURE_EVENTS)
        d = obsq.derive_slo(evs)
        assert d["requests_with_first_token"] == 4
        assert (d["ttft_p50_ms"], d["ttft_p99_ms"]) == (20.0, 30.0)
        assert d["tokens"] == 12
        assert d["tokens_per_s"] == pytest.approx(12.0)

    def test_trace_renders_a_timeline(self, capsys):
        rc = obsq.main(["trace", "fx/r3", "--events", _FIXTURE_EVENTS])
        assert rc == 0
        out = capsys.readouterr().out
        assert "serve.ttft_ms" in out and "tokens=3" in out
        rc = obsq.main(["trace", "fx/nope", "--events", _FIXTURE_EVENTS])
        assert rc == 0
        assert "no events" in capsys.readouterr().out

    @staticmethod
    def _hlo_audit_store(tmp_path, wire_flops):
        """A store of hlo_audit records, one per (wire_bytes, flops)."""
        os.makedirs(str(tmp_path), exist_ok=True)
        store = str(tmp_path / "records.jsonl")
        rr = obs_record.RunRecord(store)
        for i, (wire, flops) in enumerate(wire_flops):
            rr.append(obs_record.new_entry(
                "hlo_audit", "cpu", True, "cpu", run_id=f"a{i}",
                payload={"programs": 5, "drifted": 0, "fusions": 7,
                         "collectives": 2, "while_loops": 1,
                         "flops": flops, "hbm_bytes": 9,
                         "peak_bytes": 9, "wire_bytes": wire,
                         "cost_per_program": {"decode": {"flops": 3}}}))
        return store

    def test_diff_builds_the_trajectory_table(self, tmp_path):
        store = self._hlo_audit_store(
            tmp_path, [(100, 10), (100, 10), (50, 11)])
        header, rows = obsq.diff_rows(store, "hlo_audit", last=2,
                                      fields=["wire_bytes", "flops"])
        assert header == ["run_id", "wire_bytes", "flops"]
        assert rows[0][:1] == ["a1"] and rows[1][:1] == ["a2"]
        assert rows[2][0].startswith("Δ")
        assert rows[2][1] == "-50.0%"       # the wire-bytes move, named
        with pytest.raises(LookupError):
            obsq.diff_rows(store, "serve_load")

    def test_assert_last_green_red_and_trivial(self, tmp_path, capsys):
        store = self._hlo_audit_store(tmp_path, [(100, 20), (100, 25)])
        base = ["diff", "hlo_audit", "--records", store]     # flops +25%
        assert obsq.main(base + ["--assert-last", "flops<=+50%"]) == 0
        assert obsq.main(base + ["--assert-last", "flops<=+10%"]) == 1
        assert "ASSERT FAILED" in capsys.readouterr().err
        assert obsq.main(base + ["--assert-last", "flops>=-10%"]) == 0
        # fewer than two records: trivially green (fresh trajectory)
        one = self._hlo_audit_store(tmp_path / "one", [(100, 20)])
        assert obsq.main(["diff", "hlo_audit", "--records", one,
                          "--assert-last", "flops<=+1%"]) == 0

    def test_assert_last_rejects_bad_spec_and_missing_field(
            self, tmp_path):
        store = self._hlo_audit_store(tmp_path, [(100, 20), (100, 25)])
        with pytest.raises(ValueError, match="FIELD"):
            obsq.assert_last(store, "hlo_audit", "flops < 5")
        # a typo'd field must error, not read as permanently green
        with pytest.raises(ValueError, match="flopz"):
            obsq.assert_last(store, "hlo_audit", "flopz<=+5%")

    def test_assert_last_dotted_field(self, tmp_path):
        store = self._hlo_audit_store(tmp_path, [(100, 20), (100, 25)])
        # top-level and one-dot fields are the supported surface;
        # cost_per_program.* is nested two deep and is not reachable
        assert obsq.assert_last(store, "hlo_audit",
                                "wire_bytes<=+0%") is None
        with pytest.raises(ValueError, match="cost_per_program"):
            obsq.assert_last(store, "hlo_audit",
                             "cost_per_program.decode.flops<=+0%")

    def test_malformed_event_file_fails_loudly(self, tmp_path):
        p = tmp_path / "ev.jsonl"
        p.write_text('{"t": 1, "kind": "counter"}\n{oops\n')
        with pytest.raises(ValueError, match="2"):
            obsq.load_events(str(p))


class TestRecordsAuditFlightRefs:
    def test_missing_and_torn_refs_are_named(self, tmp_path):
        from tools.lint import audit
        store = str(tmp_path / "runs" / "records.jsonl")
        rec = flight.FlightRecorder()
        rec.note("counter", "x")
        path = rec.dump("serve.arena",
                        os.path.join(os.path.dirname(store), "incidents"))
        ref = os.path.relpath(path, os.path.dirname(store))
        good = obs_record.new_entry(
            "incident", "cpu", True, "cpu", run_id="i-good",
            payload={"site": "serve.arena", "fault": "hang", "ref": 1,
                     "outcome": "recovered", "retries": 1,
                     "flight_ref": ref})
        obs_record.RunRecord(store).append(good)
        assert audit.check_records_root(str(tmp_path)) == []
        bad = dict(good, run_id="i-bad",
                   payload=dict(good["payload"],
                                flight_ref="incidents/gone.jsonl"))
        obs_record.RunRecord(store).append(bad)
        errs = audit.check_records_root(str(tmp_path))
        assert len(errs) == 1 and "missing dump" in errs[0]
        # a torn dump file is named too
        with open(path, "a") as f:
            f.write('{"torn\n')
        errs = audit.check_records_root(str(tmp_path))
        assert any("not a valid event line" in e for e in errs)

    def test_schema_rejects_empty_flight_ref(self):
        from singa_tpu.obs import schema
        payload = {"site": "serve.arena", "fault": "x", "ref": 1,
                   "outcome": "recovered", "retries": 0,
                   "flight_ref": ""}
        with pytest.raises(schema.SchemaError, match="flight_ref"):
            schema.validate_incident_payload(payload)
        train = {"steps": 1, "wall_s": 1.0, "ckpt_count": 0,
                 "resumed_from": -1, "flight_ref": 7}
        with pytest.raises(schema.SchemaError, match="flight_ref"):
            schema.validate_train_run_payload(train)
