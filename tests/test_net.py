"""Multi-process disaggregated serving (ISSUE 18 + 19, `serve/net`).

Six layers, cheapest first:

* **framing** — the RPC wire format round-trips headers + payloads
  over a socketpair, stamps the contextvar trace id, and fails loudly
  on torn reads (no processes, no jax programs);
* **deadlines + poisoning** — the per-op RPC deadline table with its
  compile-aware escalation, and the poisoned-socket contract: after
  ONE timeout the connection refuses further RPC instead of misreading
  a late reply as the answer to a newer request (ISSUE 19);
* **elastic policy** — grow/shrink decisions over a duck-typed fake
  router (debounce, budget, committed-share steering);
* **self-healing** — heartbeat liveness, respawn-toward-target, the
  capped backoff and crash-loop breaker, and the respawn-vs-shrink
  race, all forced deterministically over fake workers (ISSUE 19);
* **frozen records** — the committed multi-process ratio-sweep entries
  in runs/records.jsonl carry the transport trio + procs/host_cores
  provenance and hold the structural contract (REAL scaling asserted
  only when `host_cores` made it physically possible), and the
  committed `chaos_campaign` record's invariant summary is re-derived
  from its own seed via tools/chaosd.plan_events — the determinism
  contract, re-asserted forever from the frozen record;
* **live tier** — ONE module-scoped 3-process tier (tiny llama,
  1 prefill + 2 decode — the ROADMAP item-7 budget guard) is reused
  by every live test, in order: bitwise parity, torn-frame chaos,
  resize-abort chaos, elastic drain under load, worker death (now
  healed by a respawn), and a worker-side transport hang declared
  dead at the op deadline and healed the same way.  The full ratio
  sweep, the resize soak and the chaos smoke campaign live in the
  slow lane.
"""

import json
import os
import socket
import tempfile
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from singa_tpu import faults
from singa_tpu.obs import record as obs_record
from singa_tpu.obs import schema
from singa_tpu.obs import trace as obs_trace
from singa_tpu.serve.net import rpc
from singa_tpu.serve.net import supervisor as sup
from singa_tpu.serve.net.elastic import ElasticPolicy, target_decode_share


# ---------------------------------------------------------------------------
# RPC framing (no processes)
# ---------------------------------------------------------------------------

class TestFraming:
    def test_round_trip_with_payload_and_trace(self):
        a, b = socket.socketpair()
        try:
            with obs_trace.activate("tr-net-1"):
                rpc.send_frame(a, {"op": "handoff"}, b"\x00\x01kv")
            hdr, payload = rpc.recv_frame(b)
            assert hdr["op"] == "handoff"
            assert hdr["trace"] == "tr-net-1"
            assert payload == b"\x00\x01kv"
        finally:
            a.close()
            b.close()

    def test_header_only_frame(self):
        a, b = socket.socketpair()
        try:
            rpc.send_frame(a, {"op": "tick", "decode": True})
            hdr, payload = rpc.recv_frame(b)
            assert hdr == {"op": "tick", "decode": True}
            assert payload == b""
        finally:
            a.close()
            b.close()

    def test_peer_hangup_mid_frame_is_loud(self):
        a, b = socket.socketpair()
        try:
            # a well-formed length prefix promising bytes that never come
            a.sendall(b"\x00\x00\x00\x08\x00\x00\x00\x00head")
            a.close()
            with pytest.raises(rpc.RPCError):
                rpc.recv_frame(b)
        finally:
            b.close()

    def test_oversized_length_prefix_is_refused(self):
        a, b = socket.socketpair()
        try:
            import struct
            a.sendall(struct.pack(">II", rpc.MAX_FRAME + 1, 0))
            with pytest.raises(rpc.RPCError):
                rpc.recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_torn_frame_plan_truncates_payload_bytes(self):
        """The send side passes payloads through faults.tear — a
        torn_frame spec halves the bytes while the frame itself stays
        parseable, exactly what the codec digest must catch."""
        a, b = socket.socketpair()
        try:
            plan = faults.FaultPlan.parse(
                "serve.transport=torn_frame:at=1")
            with faults.active(plan):
                rpc.send_frame(a, {"op": "handoff"}, b"x" * 64)
            hdr, payload = rpc.recv_frame(b)
            assert hdr["op"] == "handoff"
            assert payload == b"x" * 32
        finally:
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# per-op deadlines + the poisoned-socket contract (ISSUE 19)
# ---------------------------------------------------------------------------

def _wp(sock=None, op_timeouts=None, compile_timeout_s=300.0):
    """A supervisor-side WorkerProc over a fabric stub — just the two
    fields :meth:`WorkerProc.op_timeout` and the RPC wrappers read."""
    fab = SimpleNamespace(
        op_timeouts={**sup._OP_TIMEOUTS, **(op_timeouts or {})},
        compile_timeout_s=compile_timeout_s)
    return sup.WorkerProc("d0", "decode", SimpleNamespace(), sock, fab)


class TestOpDeadlines:
    def test_table_resolves_per_op(self):
        w = _wp()
        assert w.op_timeout("heartbeat") == sup._OP_TIMEOUTS["heartbeat"]
        assert w.op_timeout("health") == sup._OP_TIMEOUTS["health"]
        assert w.op_timeout("shutdown") == sup._OP_TIMEOUTS["shutdown"]
        # a liveness probe must be ORDERS faster than a tick deadline —
        # that asymmetry is what makes hang detection snappy
        assert w.op_timeout("heartbeat") < sup._OP_TIMEOUTS["tick"]

    def test_unknown_op_keeps_the_blanket_deadline(self):
        assert _wp().op_timeout("no-such-op") == sup._DEFAULT_TIMEOUT_S

    def test_tick_escalates_until_warm(self):
        """jit compiles happen on a worker's first dispatches, NOT at
        ready — early ticks get the compile budget, then the deadline
        drops to the steady-state table value."""
        w = _wp()
        assert w.op_timeout("tick") == 300.0
        w.ok_ticks = sup._WARMUP_TICKS - 1
        assert w.op_timeout("tick") == 300.0
        w.ok_ticks = sup._WARMUP_TICKS
        assert w.op_timeout("tick") == sup._OP_TIMEOUTS["tick"]

    def test_first_handoff_escalates(self):
        w = _wp()
        assert w.op_timeout("handoff") == 300.0
        w.ok_handoffs = 1
        assert w.op_timeout("handoff") == sup._OP_TIMEOUTS["handoff"]

    def test_per_tier_override_wins_in_steady_state(self):
        w = _wp(op_timeouts={"tick": 7.0}, compile_timeout_s=9.0)
        assert w.op_timeout("tick") == 9.0      # still compile-aware
        w.ok_ticks = sup._WARMUP_TICKS
        assert w.op_timeout("tick") == 7.0

    def test_heartbeat_never_escalates(self):
        """Warmth is irrelevant to a header-only probe: a FRESH worker
        that hangs must still be declared dead on the fast deadline."""
        w = _wp()
        assert w.ok_ticks == 0
        assert w.op_timeout("heartbeat") == sup._OP_TIMEOUTS["heartbeat"]


class TestPoisonedSocket:
    """The ISSUE-19 regression: a timed-out socket may sit mid-frame,
    so the first WorkerDied poisons the connection — every later use
    fails fast and the stale bytes are NEVER parsed as a fresh reply."""

    def test_timeout_poisons_and_late_reply_is_never_misread(self):
        a, b = socket.socketpair()
        try:
            w = _wp(sock=a, op_timeouts={"tick": 0.2},
                    compile_timeout_s=0.2)
            with pytest.raises(sup.WorkerDied):
                w.call({"op": "tick"})          # peer never replies
            assert w.poisoned
            # the reply lands LATE — exactly the stale frame a naive
            # retry would misread as its own answer
            rpc.send_frame(b, {"op": "tick", "ok": True})
            t0 = time.monotonic()
            with pytest.raises(sup.WorkerDied, match="poisoned"):
                w.call({"op": "tick"})
            assert time.monotonic() - t0 < 0.1  # fail-fast, no read
            # proof the poisoned path never touched the socket: the
            # stale frame is still sitting in the buffer, unconsumed
            hdr, _ = rpc.recv_frame(a, timeout=1.0)
            assert hdr == {"op": "tick", "ok": True}
        finally:
            a.close()
            b.close()

    def test_send_and_recv_refuse_a_poisoned_connection(self):
        a, b = socket.socketpair()
        try:
            w = _wp(sock=a)
            w.poisoned = True
            with pytest.raises(sup.WorkerDied, match="poisoned"):
                w.send({"op": "tick"})
            with pytest.raises(sup.WorkerDied, match="poisoned"):
                w.recv(timeout=0.1)
        finally:
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# elastic policy over a fake router (no processes)
# ---------------------------------------------------------------------------

class _FakeWorker:
    def __init__(self, load=0):
        self.alive = True
        self.load = load


class _FakeRouter:
    def __init__(self, n_prefill, n_decode, *, pending=0, parked=0,
                 loads=0):
        self.prefill = [_FakeWorker(loads) for _ in range(n_prefill)]
        self.decode = [_FakeWorker() for _ in range(n_decode)]
        self.pending = pending
        self.parked = parked
        self.model_key = None


class TestElasticPolicy:
    def test_parked_prefills_grow_decode(self):
        pol = ElasticPolicy(check_every=1, patience=1, max_total=4,
                            decode_share=0.5)
        r = _FakeRouter(1, 1, pending=3, parked=2)
        assert pol.decide(r) == {"n_decode": 2}

    def test_at_budget_trades_prefill_for_decode_below_share(self):
        pol = ElasticPolicy(check_every=1, patience=1, max_total=4,
                            decode_share=0.6)
        r = _FakeRouter(3, 1, pending=3, parked=2)   # total at budget
        assert pol.decide(r) == {"n_prefill": 2, "n_decode": 2}

    def test_deep_prefill_queues_grow_prefill(self):
        pol = ElasticPolicy(check_every=1, patience=1, max_total=4,
                            decode_share=0.5)
        r = _FakeRouter(1, 1, pending=5, loads=4)    # queued > 2*n_p
        assert pol.decide(r) == {"n_prefill": 2}

    def test_idle_shrinks_toward_the_committed_share(self):
        pol = ElasticPolicy(check_every=1, patience=1, max_total=4,
                            decode_share=0.5)
        r = _FakeRouter(1, 2, pending=0)
        assert pol.decide(r) == {"n_decode": 1}
        r = _FakeRouter(2, 1, pending=0)
        assert pol.decide(r) == {"n_prefill": 1}

    def test_debounce_needs_patience_consecutive_checks(self):
        pol = ElasticPolicy(check_every=1, patience=2, max_total=4,
                            decode_share=0.5)
        r = _FakeRouter(1, 1, pending=3, parked=1)
        assert pol.decide(r) is None          # first sighting: wait
        assert pol.decide(r) == {"n_decode": 2}
        # the signal clearing resets the debounce
        assert pol.decide(_FakeRouter(1, 1, pending=3)) is None
        assert pol.decide(r) is None

    def test_min_per_pool_is_a_floor(self):
        pol = ElasticPolicy(check_every=1, patience=1, max_total=4,
                            decode_share=0.5)
        r = _FakeRouter(1, 1, pending=0)
        assert pol.decide(r) is None          # nothing above the floor

    def test_bad_budget_is_rejected(self):
        with pytest.raises(ValueError):
            ElasticPolicy(min_per_pool=0)
        with pytest.raises(ValueError):
            ElasticPolicy(min_per_pool=2, max_total=3)

    def test_target_share_defaults_sanely(self):
        assert 0.0 <= target_decode_share("no-such-model") <= 1.0


# ---------------------------------------------------------------------------
# self-healing over fake workers (ISSUE 19; no processes)
# ---------------------------------------------------------------------------

class _HealProc:
    def __init__(self):
        self.killed = False

    def kill(self):
        self.killed = True

    def wait(self, timeout=None):
        return 0

    def poll(self):
        return 0 if self.killed else None


class _HealWorker:
    """Duck-typed WorkerProc for router-level healing tests: alive,
    warmed, and answering every RPC — until told not to."""

    def __init__(self, name, role, *, heartbeat_ok=True):
        self.name, self.role = name, role
        self.alive = True
        self.load = 0
        self.pid = 1000
        self.model_key = "fake"
        self.platform = self.device_kind = "cpu"
        self.poisoned = False
        self.last_ok = time.monotonic()
        self.ok_ticks = 99
        self.ok_handoffs = 9
        self.wrids = {}
        self.proc = _HealProc()
        self.sock = SimpleNamespace(close=lambda: None)
        self.heartbeat_ok = heartbeat_ok
        self.ops = []
        self.fabric = None                      # set by _mini_router

    def call(self, header, payload=b"", *, timeout=None):
        self.ops.append(header["op"])
        if header["op"] == "heartbeat" and not self.heartbeat_ok:
            raise sup.WorkerDied(
                f"worker {self.name}: probe timed out")
        self.last_ok = time.monotonic()
        return {"ok": True}, b""


def _mini_router(n_prefill=1, n_decode=2, *, spawn_many=None, **kw):
    """A real ProcRouter over fake workers and a fabric stub — the
    whole self-healing state machine (liveness, respawn, backoff,
    breaker, adoption) runs for real; only processes are fake."""
    seq = {"n": 100}

    def next_name(role):
        seq["n"] += 1
        return f"{role[0]}{seq['n']}"

    fab = SimpleNamespace(
        op_timeouts=dict(sup._OP_TIMEOUTS), compile_timeout_s=300.0,
        spawn_timeout_s=5.0, next_name=next_name,
        spawn_many=spawn_many or (lambda specs: []),
        close=lambda: None)
    pw = [_HealWorker(f"p{i}", "prefill") for i in range(n_prefill)]
    dw = [_HealWorker(f"d{i}", "decode") for i in range(n_decode)]
    for w in pw + dw:
        w.fabric = fab
    return sup.ProcRouter(pw, dw, **kw)


def _await_staged(router, role, n, deadline_s=5.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        if router.heal_state()["staged"][role] >= n:
            return
        time.sleep(0.01)
    raise AssertionError(f"nothing staged: {router.heal_state()}")


class TestHeartbeatLiveness:
    def test_quiet_hung_worker_is_probed_and_funneled(self):
        """The host half of the Heartbeat contract: a worker whose
        last successful RPC is stale gets a probe, and a probe failure
        converges on the SAME WorkerDied funnel as a crash — its
        process is terminated even though the pid still existed."""
        r = _mini_router(respawn=False)
        d1 = r.decode[1]
        d1.heartbeat_ok = False
        d1.last_ok = time.monotonic() - 10.0
        with pytest.warns(UserWarning, match="died"):
            r._check_liveness()
        assert d1.alive is False
        assert d1.proc.killed                   # hang ≠ crash: SIGKILL
        assert r.metrics.worker_deaths == 1
        assert d1.ops == ["heartbeat"]

    def test_busy_workers_are_not_probed(self):
        r = _mini_router(respawn=False)
        r._check_liveness()                     # everyone fresh
        assert all(w.ops == [] for w in r.workers())

    def test_healthy_quiet_worker_survives_the_probe(self):
        r = _mini_router(respawn=False)
        d1 = r.decode[1]
        d1.last_ok = time.monotonic() - 10.0
        r._check_liveness()
        assert d1.alive and d1.ops == ["heartbeat"]
        assert r.metrics.worker_deaths == 0


class TestRespawn:
    def test_death_respawns_toward_target_and_adopts(self):
        created = []

        def spawn_many(specs):
            ws = [_HealWorker(name, role) for name, role in specs]
            created.extend(ws)
            return ws

        r = _mini_router(spawn_many=spawn_many)
        with pytest.warns(UserWarning, match="died"):
            r._worker_death(r.decode[1], "chaos kill")
        _await_staged(r, "decode", 1)
        for t in r._spawn_threads:
            t.join(timeout=5.0)
        r._prune()
        r._adopt_staged()
        assert [w.name for w in r.decode if w.alive] == \
            ["d0", created[0].name]
        assert r.metrics.respawns == 1
        assert r.heal_state()["alive"]["decode"] == 2

    def test_failed_respawn_backs_off_exponentially_capped(self):
        r = _mini_router(respawn_backoff_s=0.5,
                         respawn_backoff_cap_s=4.0)
        seen = []
        for _ in range(5):
            with pytest.warns(UserWarning, match="backs off"):
                r._respawn_failed("decode", RuntimeError("spawn lost"))
            seen.append(r._respawn_not_before["decode"]
                        - time.monotonic())
        # 0.5 -> 1 -> 2 -> 4 -> 4: doubling until the cap holds it
        for want, got in zip((0.5, 1.0, 2.0, 4.0, 4.0), seen):
            assert got == pytest.approx(want, abs=0.2)
        # a backed-off role is skipped by the respawn tick until due
        r._respawn_tick()
        assert r.heal_state()["spawning"]["decode"] == 0

    def test_breaker_opens_after_k_deaths_and_resize_resets(self):
        """K deaths of one role inside the window → the crash-loop
        breaker opens, respawn stops (the tier degrades to survivors),
        and only an EXPLICIT resize hands the role a clean slate."""
        scheduled = []
        r = _mini_router(n_decode=3, breaker_k=3, breaker_window_s=60.0)
        r._respawn = lambda role, n: scheduled.append((role, n))
        for i in range(3):
            with pytest.warns(UserWarning):
                r._worker_death(r.decode[i], f"chaos kill {i}")
        assert r.breaker_state()["decode"] is True
        assert r.metrics.crashloops == 1
        # only the pre-breaker deaths scheduled spawns
        assert scheduled == [("decode", 1), ("decode", 2)]
        r._respawn_tick()                       # breaker holds it shut
        assert scheduled == [("decode", 1), ("decode", 2)]
        grown = []
        r._grow = lambda role, n: grown.append((role, n))
        assert r.resize(n_decode=1) is True
        assert r.breaker_state()["decode"] is False
        assert r._death_times["decode"] == []
        assert grown == [("decode", 1)]

    def test_prune_removes_dead_workers_from_the_pool(self):
        r = _mini_router(respawn=False)
        with pytest.warns(UserWarning, match="died"):
            r._worker_death(r.decode[0], "chaos kill")
        assert len(r.decode) == 2
        r._prune()
        assert [w.name for w in r.decode] == ["d1"]


class TestRespawnShrinkRace:
    def test_shrink_during_inflight_respawn_dismisses_the_surplus(self):
        """Forced interleaving (the ISSUE-19 race): a respawn spawn is
        parked mid-flight on an Event, an elastic shrink moves the
        target underneath it, and the newcomer must be DISMISSED at
        adoption — no double-adopt past the target, no orphan."""
        started, release = threading.Event(), threading.Event()
        created = []

        def spawn_many(specs):
            started.set()
            assert release.wait(10.0), "race test wedged"
            ws = [_HealWorker(name, role) for name, role in specs]
            created.extend(ws)
            return ws

        r = _mini_router(spawn_many=spawn_many)
        with pytest.warns(UserWarning, match="died"):
            r._worker_death(r.decode[1], "chaos kill")
        assert started.wait(5.0), "death scheduled no respawn"
        # the spawn is in flight; now the shrink wins the race
        assert r.resize(n_decode=1) is False    # nothing to do NOW —
        assert r._target["decode"] == 1         # but the goal moved
        release.set()
        for t in r._spawn_threads:
            t.join(timeout=5.0)
        _await_staged(r, "decode", 1)
        r._prune()
        r._adopt_staged()
        assert [w.name for w in r.decode if w.alive] == ["d0"]
        assert r.metrics.respawns == 0          # never adopted
        (newcomer,) = created
        assert newcomer.alive is False          # dismissed cleanly,
        assert "shutdown" in newcomer.ops       # not orphaned

    def test_resize_grow_counts_inflight_spawns(self):
        """The dual guard: a grow that races an in-flight respawn must
        dedupe against spawning+staged, not spawn a second worker."""
        started, release = threading.Event(), threading.Event()

        def spawn_many(specs):
            started.set()
            assert release.wait(10.0)
            return [_HealWorker(name, role) for name, role in specs]

        r = _mini_router(spawn_many=spawn_many)
        with pytest.warns(UserWarning, match="died"):
            r._worker_death(r.decode[1], "chaos kill")
        assert started.wait(5.0)
        grown = []
        r._grow = lambda role, n: grown.append((role, n))
        assert r.resize(n_decode=2) is False    # 1 alive + 1 spawning
        assert grown == []                      # already on its way
        release.set()
        for t in r._spawn_threads:
            t.join(timeout=5.0)


# ---------------------------------------------------------------------------
# schema: the transport trio
# ---------------------------------------------------------------------------

def _base_serve_load():
    return {f: 1 for f in schema._SERVE_LOAD_FIELDS}


class TestTransportTrioSchema:
    def test_absent_trio_is_valid(self):
        schema.validate_serve_load_payload(_base_serve_load())

    def test_full_trio_is_valid(self):
        p = _base_serve_load()
        p.update(handoff_wire_bytes=801421, handoff_ser_ms_p99=322.5,
                 resizes=0)
        schema.validate_serve_load_payload(p)

    def test_partial_trio_is_rejected(self):
        for f in schema._SERVE_TRANSPORT_FIELDS:
            p = _base_serve_load()
            p[f] = 1
            with pytest.raises(schema.SchemaError):
                schema.validate_serve_load_payload(p)

    def test_non_numeric_trio_field_is_rejected(self):
        p = _base_serve_load()
        p.update(handoff_wire_bytes="many", handoff_ser_ms_p99=1.0,
                 resizes=0)
        with pytest.raises(schema.SchemaError):
            schema.validate_serve_load_payload(p)


# ---------------------------------------------------------------------------
# obsq: per-process sink merge
# ---------------------------------------------------------------------------

class TestObsqSinkMerge:
    def test_glob_merges_per_process_sinks_in_time_order(self, tmp_path):
        from tools import obsq

        sup = tmp_path / "ev.jsonl"
        wrk = tmp_path / "ev.jsonl.d0-mp0"
        sup.write_text(json.dumps(
            {"t": 1.0, "kind": "counter", "name": "serve.route",
             "trace": "q1"}) + "\n")
        wrk.write_text(json.dumps(
            {"t": 2.0, "kind": "counter", "name": "serve.token",
             "trace": "q1"}) + "\n")
        paths = obsq.expand_event_paths([str(tmp_path / "ev.jsonl*")])
        assert sorted(paths) == sorted([str(sup), str(wrk)])
        evs = obsq.load_events(*paths)
        assert [e["name"] for e in evs] == ["serve.route", "serve.token"]
        out = obsq.render_trace(evs, "q1")
        assert "serve.route" in out and "serve.token" in out

    def test_empty_glob_is_loud(self):
        from tools import obsq
        with pytest.raises(ValueError):
            obsq.expand_event_paths(["/nonexistent/dir/ev.jsonl*"])

    def test_literal_paths_pass_through(self):
        from tools import obsq
        assert obsq.expand_event_paths(["a.jsonl", "b.jsonl"]) == \
            ["a.jsonl", "b.jsonl"]


# ---------------------------------------------------------------------------
# the committed multi-process sweep records (frozen data, tier-1)
# ---------------------------------------------------------------------------

def _mp_sweep_groups(store_path):
    groups = {}
    for e in obs_record.RunRecord(store_path).entries():
        if e["kind"] != "serve_load":
            continue
        p = e.get("payload", {})
        if p.get("mp_sweep_id"):
            groups.setdefault(p["mp_sweep_id"], []).append(p)
    return {k: v for k, v in groups.items() if len(v) >= 2}


class TestCommittedMpSweep:
    def test_committed_mp_sweep_holds_the_structural_contract(self):
        """ISSUE-18 acceptance, the always-true half: every committed
        multi-process sweep point completed its whole workload with
        real bytes over the wire, carries the schema'd transport trio
        and the procs/host_cores provenance, and the points share one
        workload."""
        groups = _mp_sweep_groups(os.path.join(REPO, "runs",
                                               "records.jsonl"))
        assert groups, ("no committed multi-process ratio-sweep "
                        "records (tools/loadgen.py --procs "
                        "--ratio-sweep)")
        for pts in groups.values():
            assert len({p["requests"] for p in pts}) == 1
            for p in pts:
                schema.validate_serve_load_payload(p)
                assert p["completed"] == p["requests"], p
                assert p["handoffs"] >= 1
                assert p["handoff_wire_bytes"] > 0
                assert p["handoff_ser_ms_p99"] > 0
                assert p["procs"] == (p["prefill_workers"]
                                      + p["decode_workers"])
                assert p["host_cores"] >= 1
                assert p["tokens_per_s"] > 0
                # sweep_id stays absent: the in-process direction
                # assertion (tests/test_disagg.py) must never adopt
                # points measured across process boundaries
                assert not p.get("sweep_id")

    def test_scaling_is_asserted_only_where_cores_allow(self):
        """The core-aware half: on a host with at least as many cores
        as the largest tier, tokens/s must not DROP as processes are
        added (that is what the wire buys); on a smaller host the
        workers time-slice, so only a no-collapse band holds — the
        record's own host_cores field decides which claim it can
        support."""
        groups = _mp_sweep_groups(os.path.join(REPO, "runs",
                                               "records.jsonl"))
        for pts in groups.values():
            pts = sorted(pts, key=lambda p: p["procs"])
            lo, hi = pts[0], pts[-1]
            cores = min(p["host_cores"] for p in pts)
            if cores >= hi["procs"]:
                assert hi["tokens_per_s"] >= 0.9 * lo["tokens_per_s"], (
                    f"{hi['procs']} procs on {cores} cores delivered "
                    f"{hi['tokens_per_s']} tok/s vs {lo['tokens_per_s']} "
                    f"at {lo['procs']} procs — pool size bought "
                    f"nothing")
            else:
                # time-sliced: more processes may only pay overhead,
                # but the tier must not collapse
                assert hi["tokens_per_s"] >= lo["tokens_per_s"] / 8.0


# ---------------------------------------------------------------------------
# the chaos campaign: plan determinism, schema, the frozen record
# ---------------------------------------------------------------------------

class TestChaosPlan:
    def test_schedule_is_a_pure_function_of_the_seed(self):
        from tools import chaosd
        assert chaosd.plan_events(19, 6) == chaosd.plan_events(19, 6)
        assert chaosd.plan_events(19, 6) != chaosd.plan_events(20, 6)
        # a longer schedule extends, never rewrites, a shorter one
        assert chaosd.plan_events(19, 8)[:6] == chaosd.plan_events(19, 6)

    def test_events_carry_their_kind_specific_fields(self):
        from tools import chaosd
        for ev in chaosd.plan_events(3, 64):
            assert ev["kind"] in chaosd.EVENT_KINDS
            if ev["kind"] in ("kill", "hang"):
                assert ev["role"] in ("prefill", "decode")
            elif ev["kind"] == "fault":
                assert ev["plan"] in chaosd.FAULT_PLANS
            else:
                assert ev["decode"] in (1, 2)

    def test_composition_accounts_for_every_event(self):
        from tools import chaosd
        events = chaosd.plan_events(5, 32)
        comp = chaosd.composition(events)
        assert sorted(comp) == sorted(chaosd.EVENT_KINDS)
        assert sum(comp.values()) == 32


def _chaos_payload():
    return {"seed": 19, "events": 6, "kills": 2, "hangs": 1,
            "fault_plans": 1, "resizes": 2, "respawns": 3,
            "reroutes": 1, "worker_deaths": 3, "requests": 28,
            "completed": 28, "bitwise_ok": True}


class TestChaosCampaignSchema:
    def test_full_payload_is_valid(self):
        schema.validate_chaos_campaign_payload(_chaos_payload())

    def test_missing_count_is_rejected(self):
        for f in ("seed", "respawns", "worker_deaths", "completed"):
            p = _chaos_payload()
            del p[f]
            with pytest.raises(schema.SchemaError):
                schema.validate_chaos_campaign_payload(p)

    def test_bitwise_ok_must_be_a_strict_bool(self):
        """The headline claim is a verdict, not a count: an int 1 (or
        a missing field) must not lint as 'every stream matched'."""
        p = _chaos_payload()
        p["bitwise_ok"] = 1
        with pytest.raises(schema.SchemaError):
            schema.validate_chaos_campaign_payload(p)
        del p["bitwise_ok"]
        with pytest.raises(schema.SchemaError):
            schema.validate_chaos_campaign_payload(p)


class TestFrozenChaosCampaign:
    def test_committed_campaign_reasserts_from_its_own_seed(self):
        """ISSUE-19 acceptance: the committed chaos_campaign record's
        event counts are RE-DERIVED from its seed via plan_events —
        the schedule is recomputable forever, so the frozen record
        keeps making its claim checkable — and the invariant summary
        holds: every stream bitwise, every death healed by at least
        one adopted respawn, and the flight evidence still resolves."""
        from tools import chaosd
        store = os.path.join(REPO, "runs", "records.jsonl")
        ents = [e for e in obs_record.RunRecord(store).entries()
                if e["kind"] == "chaos_campaign"]
        assert ents, ("no committed chaos_campaign record "
                      "(python -m tools.chaosd --store "
                      "runs/records.jsonl)")
        for e in ents:
            p = e["payload"]
            schema.validate_chaos_campaign_payload(p)
            comp = chaosd.composition(
                chaosd.plan_events(p["seed"], p["events"]))
            assert p["kills"] == comp["kill"]
            assert p["hangs"] == comp["hang"]
            assert p["fault_plans"] == comp["fault"]
            assert p["resizes"] == comp["resize"]
            assert p["bitwise_ok"] is True
            assert p["completed"] == p["requests"] > 0
            assert p["worker_deaths"] >= p["kills"]
            assert p["respawns"] >= 1
            ref = p.get("flight_ref")
            assert ref, "campaign committed no flight evidence"
            assert os.path.exists(os.path.join(
                os.path.dirname(store), ref)), ref


# ---------------------------------------------------------------------------
# obsq: the incidents subcommand (ISSUE 19)
# ---------------------------------------------------------------------------

class TestObsqIncidents:
    def _store(self, tmp_path, *, link=True):
        from singa_tpu.obs import flight as obs_flight

        store = str(tmp_path / "records.jsonl")
        rec = obs_flight.FlightRecorder()
        with obs_trace.activate("tr-inc-1"):
            rec.note("error", "serve.worker_dead", worker="d0")
        ref = obs_flight.dump_for_store(rec, "serve.respawn", store,
                                        "test dump")
        assert ref and ref.startswith("incidents" + os.sep)
        if link:
            entry = obs_record.new_entry(
                "incident", "cpu", True, "cpu", run_id="t-inc-0",
                payload={"site": "serve.respawn", "fault": "respawn",
                         "ref": "d1", "outcome": "respawned",
                         "retries": 0, "flight_ref": ref})
            obs_record.RunRecord(store).append(entry)
        return store, ref

    def test_rows_render_site_trace_and_backlink(self, tmp_path):
        from tools import obsq
        store, ref = self._store(tmp_path)
        header, rows = obsq.incidents_rows(store)
        assert header == ["dump", "site", "timestamp", "trace",
                          "linked"]
        (row,) = rows
        assert row[0] == os.path.basename(ref)
        assert row[1] == "serve.respawn"
        assert "tr-inc-1" in row[3]
        assert row[4] == "yes"

    def test_unlinked_dump_is_called_out(self, tmp_path):
        from tools import obsq
        store, _ = self._store(tmp_path, link=False)
        _, rows = obsq.incidents_rows(store)
        assert rows[0][4] == "NO"

    def test_missing_incidents_dir_is_loud(self, tmp_path):
        from tools import obsq
        store = str(tmp_path / "records.jsonl")
        with pytest.raises(OSError):
            obsq.incidents_rows(store)


# ---------------------------------------------------------------------------
# the live 3-process tier (module-scoped; ROADMAP item-7 budget guard)
# ---------------------------------------------------------------------------

_N_PROMPTS = 4
_MAX_NEW = 6


def _prompts(vocab):
    rng = np.random.RandomState(23)
    return [rng.randint(0, vocab, (int(n),)).astype(np.int32)
            for n in (5, 9, 12, 7)][:_N_PROMPTS]


@pytest.fixture(scope="module")
def mp_tier():
    """ONE spawn for every live test in this module: a 1 prefill + 2
    decode process tier (3 child processes — the budget ceiling), a
    single-engine reference stream set, and a record store the drain
    test's incident lands in.  Tests run in definition order and the
    destructive ones (drain, kill) come last."""
    from singa_tpu.serve import ServeEngine
    from tools.loadgen import _build_model, _build_proc_tier

    m = _build_model()
    prompts = _prompts(m.cfg.vocab_size)
    eng = ServeEngine(m, num_slots=4, max_len=32, block_size=8)
    ref = [eng.submit(p, max_new_tokens=_MAX_NEW) for p in prompts]
    eng.run_until_idle()
    ref_toks = [h.tokens for h in ref]
    eng.close()

    tmp = tempfile.mkdtemp(prefix="singa-net-test-")
    store = os.path.join(tmp, "records.jsonl")
    args = SimpleNamespace(num_slots=4, max_len=32, block_size=8,
                           num_blocks=None, max_queue=None, spec_k=0,
                           no_share=False)
    tier = _build_proc_tier(1, 2, args, store)
    try:
        yield SimpleNamespace(tier=tier, prompts=prompts,
                              ref_toks=ref_toks, store=store)
    finally:
        tier.close()


def _serve_all(tier, prompts):
    handles = [tier.submit(p, max_new_tokens=_MAX_NEW) for p in prompts]
    tier.run_until_idle(max_steps=500)
    return [h.tokens for h in handles]


def _settle_heal(tier, deadline_s=240.0):
    """Step the tier until the self-healing layer has converged: no
    spawn in flight, nothing staged, every role back at target (or
    given up via the breaker) — what a chaos driver polls between
    events (tools/chaosd._settle)."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        tier.step()
        hs = tier.heal_state()
        if (not any(hs["spawning"].values())
                and not any(hs["staged"].values())
                and all(hs["alive"][r] >= hs["target"][r]
                        or hs["breaker"][r]
                        for r in ("prefill", "decode"))):
            return hs
        time.sleep(0.05)
    raise AssertionError(f"tier did not heal: {tier.heal_state()}")


class TestLiveTier:
    def test_streams_bitwise_identical_across_processes(self, mp_tier):
        got = _serve_all(mp_tier.tier, mp_tier.prompts)
        assert got == mp_tier.ref_toks
        assert mp_tier.tier.metrics.handoffs >= 1
        assert mp_tier.tier.metrics.wire_bytes > 0

    def test_torn_frame_is_rejected_and_replayed_bitwise(self, mp_tier):
        """Chaos: tear the first inject payload (supervisor-side
        serve.transport fires recv-extract then send-inject per
        handoff, so at=2 is the inject).  The codec digest must refuse
        the torn package — it is NEVER injected — and the replay path
        must finish every stream bitwise."""
        m = mp_tier.tier.metrics
        torn0, rer0 = m.torn_frames, m.reroutes
        plan = faults.FaultPlan.parse("serve.transport=torn_frame:at=2")
        with faults.active(plan):
            got = _serve_all(mp_tier.tier, mp_tier.prompts)
        assert got == mp_tier.ref_toks
        assert m.torn_frames == torn0 + 1
        assert m.reroutes >= rer0 + 1

    def test_injected_resize_fault_aborts_atomically(self, mp_tier):
        tier = mp_tier.tier
        n_p, n_d = len(tier.prefill), len(tier.decode)
        plan = faults.FaultPlan.parse("serve.resize=error:at=1")
        with faults.active(plan):
            assert tier.resize(n_decode=n_d + 1) is False
        assert tier.metrics.resizes_aborted >= 1
        assert (len(tier.prefill), len(tier.decode)) == (n_p, n_d)
        assert tier.metrics.resizes == 0

    def test_scale_down_under_load_drains_bitwise_with_incident(
            self, mp_tier):
        """ISSUE-18 acceptance: shrink the decode pool under load.
        Every in-flight stream must complete bitwise (the drained
        worker's requests replay), and the drain must commit an
        incident record at site serve.resize whose flight_ref resolves
        to a real dump."""
        tier = mp_tier.tier
        handles = [tier.submit(p, max_new_tokens=_MAX_NEW)
                   for p in mp_tier.prompts]
        for _ in range(3):                      # get streams in flight
            tier.step()
        assert tier.resize(n_decode=1) is True
        tier.run_until_idle(max_steps=500)
        assert [h.tokens for h in handles] == mp_tier.ref_toks
        assert len(tier.decode) == 1
        assert tier.metrics.resizes == 1
        incidents = [e for e in
                     obs_record.RunRecord(mp_tier.store).entries()
                     if e["kind"] == "incident"
                     and e["payload"].get("site") == "serve.resize"]
        assert incidents, "drain committed no serve.resize incident"
        ref = incidents[-1]["payload"].get("flight_ref")
        assert ref, incidents[-1]["payload"]
        dump = os.path.join(os.path.dirname(mp_tier.store), ref)
        assert os.path.exists(dump), dump

    def test_worker_death_mid_flight_replays_bitwise_and_respawns(
            self, mp_tier):
        """ISSUE-19 acceptance, the crash half: SIGKILL the (only)
        decode worker mid-stream.  In-flight streams replay bitwise on
        the survivors IMMEDIATELY (nothing waits on the slow spawn),
        then the replacement is adopted at a step boundary — pool back
        at target — with a ``serve.respawn`` incident whose flight_ref
        resolves to a real dump."""
        tier = mp_tier.tier
        deaths0 = tier.metrics.worker_deaths
        respawns0 = tier.metrics.respawns
        handles = [tier.submit(p, max_new_tokens=_MAX_NEW)
                   for p in mp_tier.prompts]
        for _ in range(3):
            tier.step()
        tier.decode[0].proc.kill()              # the last decode worker
        tier.run_until_idle(max_steps=500)
        assert [h.tokens for h in handles] == mp_tier.ref_toks
        assert tier.metrics.worker_deaths == deaths0 + 1
        hs = _settle_heal(tier)
        assert hs["alive"]["decode"] == hs["target"]["decode"] == 1
        assert tier.metrics.respawns == respawns0 + 1
        incidents = [e for e in
                     obs_record.RunRecord(mp_tier.store).entries()
                     if e["kind"] == "incident"
                     and e["payload"].get("site") == "serve.respawn"]
        assert incidents, "respawn committed no serve.respawn incident"
        ref = incidents[-1]["payload"].get("flight_ref")
        assert ref, incidents[-1]["payload"]
        assert os.path.exists(os.path.join(
            os.path.dirname(mp_tier.store), ref)), ref

    @pytest.mark.slow  # warm round + deadline wait + respawn spawn
    def test_worker_side_transport_hang_is_declared_dead_and_healed(
            self, mp_tier):
        """ISSUE-19 acceptance, the hang half: a ``serve.transport``
        hang installed INSIDE the decode worker (the chaos RPC seam)
        wedges its KV payload frames — the process stays perfectly
        alive, which is exactly the hang-≠-crash case.  The supervisor
        must declare it dead at the per-op deadline (never the 60s
        hang), replay bitwise on survivors, and heal through the SAME
        respawn path as a crash."""
        tier = mp_tier.tier
        # warm the freshly-respawned decode worker first — also proves
        # post-heal parity — so steady-state deadlines apply below
        assert _serve_all(tier, mp_tier.prompts) == mp_tier.ref_toks
        victim = next(w for w in tier.decode if w.alive)
        assert victim.ok_handoffs >= 1 and \
            victim.ok_ticks >= sup._WARMUP_TICKS
        deaths0 = tier.metrics.worker_deaths
        respawns0 = tier.metrics.respawns
        saved = dict(tier.fabric.op_timeouts)
        tier.fabric.op_timeouts.update(handoff=6.0, tick=8.0)
        try:
            rep, _ = victim.call(
                {"op": "chaos",
                 "plan": "serve.transport=hang:at=1,delay=60"})
            assert rep.get("ok"), rep
            handles = [tier.submit(p, max_new_tokens=_MAX_NEW)
                       for p in mp_tier.prompts]
            t0 = time.monotonic()
            tier.run_until_idle(max_steps=500)
            detect_s = time.monotonic() - t0
            assert [h.tokens for h in handles] == mp_tier.ref_toks
            assert tier.metrics.worker_deaths == deaths0 + 1
            assert detect_s < 60.0, (
                f"death took {detect_s:.1f}s — the deadline never "
                f"fired, the tier just outwaited the hang")
            hs = _settle_heal(tier)
            assert hs["alive"]["decode"] == hs["target"]["decode"]
            assert tier.metrics.respawns == respawns0 + 1
        finally:
            tier.fabric.op_timeouts.clear()
            tier.fabric.op_timeouts.update(saved)


# ---------------------------------------------------------------------------
# slow lane: the full mp ratio sweep + the elastic resize soak
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestMpSlowLane:
    def test_live_mp_ratio_sweep_commits_structural_records(
            self, tmp_path):
        from tools import loadgen

        store = str(tmp_path / "records.jsonl")
        rc = loadgen.main(["--procs", "--ratio-sweep", "1:1,1:2",
                           "--requests", "12", "--rate", "30",
                           "--deadline", "30", "--store", store])
        assert rc == 0
        groups = _mp_sweep_groups(store)
        assert len(groups) == 1
        (pts,) = groups.values()
        assert len(pts) == 2
        for p in pts:
            schema.validate_serve_load_payload(p)
            assert p["completed"] == p["requests"]
            assert p["handoff_wire_bytes"] > 0

    def test_chaos_smoke_campaign_commits_a_reassertable_record(
            self, tmp_path):
        """The CI chaos stage end to end (1 kill + 1 hang against a
        live 2-process tier), plus the record contract: the committed
        campaign entry validates and its flight evidence resolves."""
        from tools import chaosd

        store = str(tmp_path / "records.jsonl")
        assert chaosd.smoke(store=store) == 0
        ents = [e for e in obs_record.RunRecord(store).entries()
                if e["kind"] == "chaos_campaign"]
        assert len(ents) == 1
        p = ents[0]["payload"]
        schema.validate_chaos_campaign_payload(p)
        assert p["bitwise_ok"] is True
        assert p["completed"] == p["requests"]
        assert p["worker_deaths"] >= 2 and p["respawns"] >= 2
        ref = p.get("flight_ref")
        assert ref and os.path.exists(
            os.path.join(os.path.dirname(store), ref))

    def test_elastic_policy_resizes_a_live_tier_bitwise(self):
        """Resize soak: an ElasticPolicy-driven tier under sustained
        load grows the decode pool from backpressure and shrinks on
        idle, with every stream bitwise identical to the single-engine
        reference."""
        from singa_tpu.serve import ServeEngine
        from tools.loadgen import _build_model, _build_proc_tier

        m = _build_model()
        prompts = _prompts(m.cfg.vocab_size) * 3
        eng = ServeEngine(m, num_slots=4, max_len=32, block_size=8,
                          max_queue=32)
        ref = [eng.submit(p, max_new_tokens=_MAX_NEW) for p in prompts]
        eng.run_until_idle()
        ref_toks = [h.tokens for h in ref]
        eng.close()

        args = SimpleNamespace(num_slots=2, max_len=32, block_size=8,
                               num_blocks=None, max_queue=32, spec_k=0,
                               no_share=False)
        pol = ElasticPolicy(check_every=2, patience=1, max_total=3,
                            decode_share=0.5)
        tier = _build_proc_tier(1, 1, args, None, policy=pol)
        try:
            handles = [tier.submit(p, max_new_tokens=_MAX_NEW)
                       for p in prompts]
            tier.run_until_idle(max_steps=1000)
            got = [h.tokens for h in handles]
            assert got == ref_toks
            # idle ticks after the burst let the shrink side fire too
            for _ in range(8):
                tier.step()
        finally:
            tier.close()
