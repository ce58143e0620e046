"""Tests for tools/lint (singalint) — the AST invariant linter.

Every rule gets a violating and a clean fixture snippet; the suppression
contract (reason REQUIRED) and the JSON output schema are pinned; and
the tier-1 gate at the bottom asserts the repo itself is clean, which is
what makes every invariant self-enforcing for future PRs.

Everything here is pure-AST (no jax, no subprocesses) — the whole file
must stay well under 5 s.
"""

import json
import os

import pytest

from tools.lint import (
    CODE_SUPPRESSION,
    RULES,
    lint_source,
    render_json,
    run_paths,
)
from tools.lint.__main__ import main as lint_main

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def codes_of(findings):
    return [f.code for f in findings]


def lint(src, code):
    """Run exactly one rule over a dedented snippet."""
    import textwrap
    return lint_source(textwrap.dedent(src), codes=[code])


# ---------------------------------------------------------------------------
# rule catalogue
# ---------------------------------------------------------------------------

def test_catalogue_covers_the_invariants():
    assert set(RULES) >= {"SGL001", "SGL002", "SGL003",
                          "SGL005", "SGL006", "SGL007", "SGL008",
                          "SGL009", "SGL010", "SGL011", "SGL012",
                          "SGL013", "SGL015", "SGL017"}
    # SGL004 (thread-seam) is RETIRED: folded into SGL010 (conclint);
    # the code stays reserved as a documented alias that fails loudly
    assert "SGL004" not in RULES
    for code, cls in RULES.items():
        assert cls.code == code and cls.name and cls.description


# ---------------------------------------------------------------------------
# SGL001 jit-purity
# ---------------------------------------------------------------------------

class TestJitPurity:
    def test_fires_on_plain_import_form(self):
        # `import singa_tpu.obs.events` canonicalizes at the use site
        out = lint("""
            import jax
            import singa_tpu.obs.events

            @jax.jit
            def step(x):
                singa_tpu.obs.events.counter("serve.steps", 1)
                return x + 1
        """, "SGL001")
        assert codes_of(out) == ["SGL001"]

    def test_fires_on_obs_event_inside_jit(self):
        out = lint("""
            import jax
            from singa_tpu.obs import events

            @jax.jit
            def step(x):
                events.counter("serve.steps", 1)
                return x + 1
        """, "SGL001")
        assert codes_of(out) == ["SGL001"]
        assert "events.counter" in out[0].message

    def test_fires_one_helper_level_deep(self):
        out = lint("""
            import time
            import jax

            def helper(x):
                time.time()
                return x

            @jax.jit
            def step(x):
                return helper(x)
        """, "SGL001")
        assert codes_of(out) == ["SGL001"]

    def test_fires_via_partial_jit_and_fault_site(self):
        out = lint("""
            from functools import partial
            import jax
            from singa_tpu import faults

            @partial(jax.jit, static_argnums=(1,))
            def step(x, n):
                faults.fire("train.step")
                return x
        """, "SGL001")
        assert codes_of(out) == ["SGL001"]

    def test_clean_on_local_variable_named_like_a_module(self):
        # a local dict named `record` is not obs.record
        out = lint("""
            import jax

            @jax.jit
            def f(x):
                record = {"a": x}
                return record.get("a")
        """, "SGL001")
        assert out == []

    def test_fires_inside_applied_partial_factory(self):
        out = lint("""
            from functools import partial
            import jax
            from singa_tpu.obs import events

            def _step(x, n):
                events.counter("serve.steps", 1)
                return x

            step = partial(jax.jit, static_argnums=(1,))(_step)
        """, "SGL001")
        assert codes_of(out) == ["SGL001"]

    def test_clean_when_effects_are_outside_jit(self):
        out = lint("""
            import jax
            from singa_tpu.obs import events

            @jax.jit
            def step(x):
                return x + 1

            def run(x):
                y = step(x)
                events.counter("serve.steps", 1)
                return y
        """, "SGL001")
        assert out == []


# ---------------------------------------------------------------------------
# SGL002 donation-safety
# ---------------------------------------------------------------------------

class TestDonationSafety:
    def test_fires_on_read_after_donate(self):
        out = lint("""
            import jax

            def _step(arena, x):
                return arena + x

            step = jax.jit(_step, donate_argnums=(0,))

            def run(arena, x):
                out = step(arena, x)
                return arena.sum()
        """, "SGL002")
        assert codes_of(out) == ["SGL002"]
        assert "'arena'" in out[0].message

    def test_clean_when_result_is_used(self):
        out = lint("""
            import jax

            def _step(arena, x):
                return arena + x

            step = jax.jit(_step, donate_argnums=(0,))

            def run(arena, x):
                arena = step(arena, x)
                return arena.sum()
        """, "SGL002")
        assert out == []

    def test_rebinding_resurrects_the_name(self):
        out = lint("""
            import jax

            step = jax.jit(lambda a: a, donate_argnums=(0,))

            def run(arena, make):
                step(arena)
                arena = make()
                return arena.sum()
        """, "SGL002")
        assert out == []


# ---------------------------------------------------------------------------
# SGL003 recompile-hazard
# ---------------------------------------------------------------------------

class TestRecompileHazard:
    def test_fires_on_jit_in_loop(self):
        out = lint("""
            import jax

            def bench(xs):
                outs = []
                for x in xs:
                    f = jax.jit(lambda a: a + 1)
                    outs.append(f(x))
                return outs
        """, "SGL003")
        assert codes_of(out) == ["SGL003"]

    def test_fires_on_partial_jit_in_loop(self):
        out = lint("""
            from functools import partial
            import jax

            def bench(xs, fn):
                outs = []
                for x in xs:
                    f = partial(jax.jit, static_argnums=(1,))(fn)
                    outs.append(f(x, 1))
                return outs
        """, "SGL003")
        assert codes_of(out) == ["SGL003"]

    def test_fires_on_shape_branch_inside_jit(self):
        out = lint("""
            import jax

            @jax.jit
            def f(x):
                if x.shape[0] > 2:
                    return x * 2
                return x
        """, "SGL003")
        assert codes_of(out) == ["SGL003"]

    def test_clean_hoisted_jit_and_outside_shape_branch(self):
        out = lint("""
            import jax

            f = jax.jit(lambda a: a + 1)

            def bench(xs):
                return [f(x) for x in xs]

            def dispatch(x):
                if x.shape[0] > 2:
                    return f(x)
                return x
        """, "SGL003")
        assert out == []


# ---------------------------------------------------------------------------
# SGL010 conc-shared-state (conclint; supersedes the retired SGL004 —
# its fixtures are folded in below, re-coded)
# ---------------------------------------------------------------------------

class TestSharedState:
    def test_fires_on_unguarded_write_from_thread_target(self):
        out = lint("""
            import threading

            class Worker:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def _run(self):
                    self.count = 1
        """, "SGL010")
        assert codes_of(out) == ["SGL010"]
        assert "self.count" in out[0].message

    def test_fires_transitively_via_submit(self):
        # the closure is TRANSITIVE (deeper than SGL004's one level):
        # _commit is two self-call hops from the submit target, which
        # is exactly the ckpt writer's real shape
        out = lint("""
            class Writer:
                def save(self):
                    self._pending = self._executor.submit(self._write)

                def _write(self):
                    self._commit()

                def _commit(self):
                    self.committed = True
        """, "SGL010")
        assert codes_of(out) == ["SGL010"]
        assert "self.committed" in out[0].message

    def test_fires_on_unguarded_read_paired_with_locked_write(self):
        # NEW vs SGL004: a background read outside the lock every
        # writer takes can observe torn/stale state
        out = lint("""
            import threading

            class Counter:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def bump(self):
                    with self._lock:
                        self.n += 1

                def start(self):
                    threading.Thread(target=self._watch).start()

                def _watch(self):
                    return self.n
        """, "SGL010")
        assert codes_of(out) == ["SGL010"]
        assert "unguarded read of self.n" in out[0].message

    def test_conditional_heartbeat_callback_is_a_domain(self):
        # the ServeEngine shape SGL004 missed: on_failure wired through
        # an IfExp — both branches are concurrency domains
        out = lint("""
            from singa_tpu.utils.failure import Heartbeat

            class Engine:
                def run(self, recover):
                    self.hb = Heartbeat(
                        timeout=5.0,
                        on_failure=(self._hb if recover
                                    else self._user_cb))

                def _hb(self, age, step):
                    self.hung = True
        """, "SGL010")
        assert codes_of(out) == ["SGL010"]
        assert "self.hung" in out[0].message

    def test_signal_handler_is_a_domain(self):
        out = lint("""
            import signal

            class Handler:
                def install(self):
                    signal.signal(signal.SIGTERM, self._handle)

                def _handle(self, signum, frame):
                    self.signum = signum
        """, "SGL010")
        assert codes_of(out) == ["SGL010"]

    def test_bare_annotation_is_not_a_write(self):
        out = lint("""
            import threading

            class Worker:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def _run(self):
                    self.buf: list
        """, "SGL010")
        assert out == []

    def test_clean_when_lock_guarded_or_mediated_or_init_only(self):
        out = lint("""
            import threading

            class Worker:
                def __init__(self, cfg):
                    self.cfg = cfg
                    self._flag = threading.Event()

                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def _run(self):
                    with self._lock:
                        self.count = 1
                    self._flag.set()          # Event-mediated
                    return self.cfg           # init-only read
        """, "SGL010")
        assert out == []

    def test_clock_is_not_a_lock(self):
        # 'clock' contains 'lock' but is not a guard
        out = lint("""
            import threading

            class Worker:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def _run(self):
                    with self._clock:
                        self.count = 1
        """, "SGL010")
        assert codes_of(out) == ["SGL010"]

    def test_fires_on_heartbeat_callback(self):
        out = lint("""
            from singa_tpu.utils.failure import Heartbeat

            class Runner:
                def run(self):
                    self.hb = Heartbeat(timeout=5.0,
                                        on_failure=self._on_hang)

                def _on_hang(self, age, step):
                    self.hung = True
        """, "SGL010")
        assert codes_of(out) == ["SGL010"]


# ---------------------------------------------------------------------------
# SGL011 conc-lock-order / SGL012 blocking-under-lock / SGL013
# wait-predicate (conclint)
# ---------------------------------------------------------------------------

class TestLockOrder:
    CYCLE = """
        import threading

        class AB:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()

            def fwd(self):
                with self._a_lock:
                    with self._b_lock:
                        return 1

            def rev(self):
                with self._b_lock:
                    self._take_a()

            def _take_a(self):
                with self._a_lock:
                    return 2
    """

    def test_fires_on_opposite_order_across_call_edges(self):
        out = lint(self.CYCLE, "SGL011")
        assert codes_of(out) == ["SGL011"]
        assert "deadlock" in out[0].message

    def test_clean_when_order_is_consistent(self):
        consistent = self.CYCLE.replace(
            "with self._b_lock:\n                    self._take_a()",
            "self._take_a()")
        assert consistent != self.CYCLE                # replace landed
        out = lint(consistent, "SGL011")
        assert out == []

    def test_multi_item_with_is_an_ordered_acquisition(self):
        # `with a, b:` acquires left to right — reversing that order in
        # a nested form elsewhere is the same textbook deadlock
        out = lint("""
            import threading

            class AB:
                def __init__(self):
                    self._a_lock = threading.Lock()
                    self._b_lock = threading.Lock()

                def fwd(self):
                    with self._a_lock, self._b_lock:
                        return 1

                def rev(self):
                    with self._b_lock:
                        with self._a_lock:
                            return 2
        """, "SGL011")
        assert codes_of(out) == ["SGL011"]


class TestBlockingUnderLock:
    def test_fires_one_helper_level_deep(self):
        out = lint("""
            import time

            class Sink:
                def emit(self):
                    with self._lock:
                        self._slow()

                def _slow(self):
                    time.sleep(1.0)
        """, "SGL012")
        assert codes_of(out) == ["SGL012"]
        assert "time.sleep" in out[0].message
        assert "self._slow" in out[0].message

    def test_thread_join_fires_but_str_join_does_not(self):
        out = lint("""
            class S:
                def run(self, parts, sep, t):
                    with self._mu:
                        x = ",".join(parts)
                        y = sep.join(parts)
                        t.join()
                    return x + y
        """, "SGL012")
        assert codes_of(out) == ["SGL012"]
        assert "t.join()" in out[0].message

    def test_clean_outside_the_lock(self):
        out = lint("""
            import time

            class S:
                def run(self):
                    with self._lock:
                        self.n += 1
                    time.sleep(0.1)
                    open("/tmp/x").close()
        """, "SGL012")
        assert out == []


class TestWaitPredicate:
    def test_event_wait_without_timeout_fires(self):
        out = lint("""
            import threading

            done = threading.Event()

            def waiter():
                done.wait()
        """, "SGL013")
        assert codes_of(out) == ["SGL013"]
        assert "timeout" in out[0].message

    def test_condition_wait_outside_while_fires(self):
        out = lint("""
            import threading

            class Q:
                def __init__(self):
                    self._cv = threading.Condition()

                def pop(self):
                    with self._cv:
                        self._cv.wait(1.0)
        """, "SGL013")
        assert codes_of(out) == ["SGL013"]
        assert "while" in out[0].message

    def test_clean_with_timeout_and_predicate_loop(self):
        out = lint("""
            import threading

            class Q:
                def __init__(self):
                    self._stop = threading.Event()
                    self._cv = threading.Condition()

                def run(self):
                    while not self._stop.wait(0.5):
                        pass

                def pop(self):
                    with self._cv:
                        while not self.items:
                            self._cv.wait(1.0)
        """, "SGL013")
        assert out == []


# ---------------------------------------------------------------------------
# SGL004 retirement: a documented alias that fails loudly
# ---------------------------------------------------------------------------

class TestSGL004Retirement:
    def test_old_suppression_fails_loudly_with_migration_hint(self):
        # the dangerous outcome would be the old comment silently
        # suppressing NOTHING while still looking authoritative
        out = lint_source(
            "import threading\n"
            "class W:\n"
            "    def start(self):\n"
            "        threading.Thread(target=self._run).start()\n"
            "    def _run(self):\n"
            "        self.n = 1  # singalint: disable=SGL004 latch\n")
        assert set(codes_of(out)) == {CODE_SUPPRESSION, "SGL010"}
        hint = [f for f in out if f.code == CODE_SUPPRESSION][0]
        assert "retired" in hint.message and "SGL010" in hint.message

    def test_migrated_suppression_silences_sgl010(self):
        out = lint_source(
            "import threading\n"
            "class W:\n"
            "    def start(self):\n"
            "        threading.Thread(target=self._run).start()\n"
            "    def _run(self):\n"
            "        self.n = 1  # singalint: disable=SGL010 latch-once"
            " bool, single writer\n")
        assert out == []

    def test_select_sgl004_errors_with_hint(self, capsys):
        with pytest.raises(SystemExit):
            lint_main(["--select", "SGL004", "x.py"])
        assert "SGL010" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# SGL005 wall-clock
# ---------------------------------------------------------------------------

class TestWallClock:
    def test_fires_on_time_time(self):
        out = lint("""
            import time

            def age(t0):
                return time.time() - t0
        """, "SGL005")
        assert codes_of(out) == ["SGL005"]

    def test_fires_on_datetime_now_and_today(self):
        """ISSUE 9 satellite: datetime.now()/today() hide the same
        jumpy wall clock behind an object — same rule, same
        required-reason suppression contract."""
        out = lint("""
            import datetime
            from datetime import datetime as dt

            def started():
                return datetime.datetime.now()

            def day():
                return dt.today()
        """, "SGL005")
        assert codes_of(out) == ["SGL005", "SGL005"]
        assert "datetime.now()" in out[0].message
        assert "datetime.today()" in out[1].message

    def test_datetime_suppression_requires_reason(self):
        ok = lint(
            "import datetime\n"
            "t = datetime.datetime.now()  # singalint: disable=SGL005 "
            "human-readable log timestamp, never subtracted\n", "SGL005")
        assert ok == []
        bare = lint_source(
            "import datetime\n"
            "t = datetime.datetime.now()  # singalint: disable=SGL005\n")
        assert CODE_SUPPRESSION in codes_of(bare)

    def test_clean_on_monotonic_and_perf_counter(self):
        out = lint("""
            import time
            import datetime

            def age(t0):
                return time.monotonic() - t0

            def cost(t0):
                return time.perf_counter() - t0

            def parse(s):
                # constructors/parsers are not clock reads
                return datetime.datetime.fromisoformat(s)
        """, "SGL005")
        assert out == []


# ---------------------------------------------------------------------------
# SGL006 obs-kind / SGL007 fault-site (registry-backed)
# ---------------------------------------------------------------------------

class TestRegistryRules:
    def test_unknown_record_kind_fires(self):
        out = lint("""
            from singa_tpu.obs import record

            entry = record.new_entry("bogus_kind", "cpu", True, "cpu")
        """, "SGL006")
        assert codes_of(out) == ["SGL006"]
        assert "bogus_kind" in out[0].message

    def test_registered_record_kind_is_clean(self):
        out = lint("""
            from singa_tpu.obs import record

            entry = record.new_entry("bench", "cpu", True, "cpu")
        """, "SGL006")
        assert out == []

    def test_unknown_fault_site_fires(self):
        out = lint("""
            from singa_tpu import faults

            faults.fire("no.such.site")
        """, "SGL007")
        assert codes_of(out) == ["SGL007"]
        assert "no.such.site" in out[0].message

    def test_registered_fault_site_is_clean(self):
        out = lint("""
            from singa_tpu import faults

            faults.fire("ckpt.write", step=1)
        """, "SGL007")
        assert out == []

    def test_disagg_sites_are_registered(self):
        """ISSUE 12: the tier's handoff + routing seams are real
        registry entries — plans/dumps naming them lint clean."""
        out = lint("""
            from singa_tpu import faults

            faults.fire("serve.handoff", rid=1, src="p0", dst="d0")
            faults.fire("serve.router", tenant="acme", slo="batch")
        """, "SGL007")
        assert out == []

    def test_spec_verify_site_is_registered(self):
        """ISSUE 13: the speculative verify seam is a real registry
        entry — plans/dumps naming it lint clean, typos fire."""
        out = lint("""
            from singa_tpu import faults

            faults.fire("serve.verify", attempt=0, active=4)
        """, "SGL007")
        assert out == []
        out = lint("""
            from singa_tpu import faults

            faults.fire("serve.verfy", attempt=0)
        """, "SGL007")
        assert codes_of(out) == ["SGL007"]
        assert "serve.verfy" in out[0].message

    def test_spill_site_is_registered(self):
        """ISSUE 17: the spill tier's write/prefetch seam is a real
        registry entry — plans/chaos tests naming it lint clean, typos
        fire."""
        out = lint("""
            from singa_tpu import faults

            faults.fire("serve.spill", op="spill", block=3)
            faults.fire("serve.spill", op="prefetch")
        """, "SGL007")
        assert out == []
        out = lint("""
            from singa_tpu import faults

            faults.fire("serve.spil", op="prefetch")
        """, "SGL007")
        assert codes_of(out) == ["SGL007"]
        assert "serve.spil" in out[0].message

    def test_net_sites_are_registered(self):
        """ISSUE 18: the multi-process tier's wire + elastic-resize
        seams are real registry entries — and ``faults.tear`` (the
        torn-frame injector) is scanned exactly like fire/corrupt."""
        out = lint("""
            from singa_tpu import faults

            faults.fire("serve.transport", dir="send", frames=1)
            faults.fire("serve.resize", prefill=2, decode=1)
            wire = faults.tear("serve.transport", wire)
        """, "SGL007")
        assert out == []

    def test_typoed_tear_site_fires(self):
        """A torn-frame chaos plan naming an unregistered site would
        tear nothing — the tear() spelling is linted too."""
        out = lint("""
            from singa_tpu import faults

            wire = faults.tear("serve.transprot", wire)
        """, "SGL007")
        assert codes_of(out) == ["SGL007"]
        assert "serve.transprot" in out[0].message

    def test_typoed_disagg_site_fires(self):
        out = lint("""
            from singa_tpu import faults

            faults.fire("serve.handof", rid=1)
        """, "SGL007")
        assert codes_of(out) == ["SGL007"]
        assert "serve.handof" in out[0].message

    def test_keyword_form_is_checked_too(self):
        out = lint("""
            from singa_tpu import faults

            faults.fire(site="no.such.site")
        """, "SGL007")
        assert codes_of(out) == ["SGL007"]

    def test_unloadable_registry_is_a_finding_not_a_pass(self, tmp_path,
                                                         monkeypatch):
        """A renamed/broken schema.py must fail the gate, not silently
        disable SGL006/SGL007."""
        from tools.lint import rules
        monkeypatch.setattr(rules, "_REPO_ROOT", str(tmp_path))
        monkeypatch.setattr(rules, "_KINDS_CACHE", {})
        monkeypatch.setattr(rules, "_SITES_CACHE", {})
        out = lint("""
            from singa_tpu.obs import record
            from singa_tpu import faults

            entry = record.new_entry("bench", "cpu", True, "cpu")
            faults.fire("ckpt.write")
        """, "SGL006")
        assert codes_of(out) == ["SGL006"]
        assert "could not be loaded" in out[0].message
        out = lint("""
            from singa_tpu import faults

            faults.fire("ckpt.write")
        """, "SGL007")
        assert codes_of(out) == ["SGL007"]
        assert "could not be loaded" in out[0].message


# ---------------------------------------------------------------------------
# SGL009 flight-site (registry-backed, ISSUE 11)
# ---------------------------------------------------------------------------

class TestFlightSite:
    def test_typoed_dump_site_fires(self):
        out = lint("""
            class Engine:
                def boom(self):
                    self.flight.dump("serve.typo", "runs/incidents")
        """, "SGL009")
        assert codes_of(out) == ["SGL009"]
        assert "serve.typo" in out[0].message

    def test_helper_form_and_keyword_form_are_checked(self):
        out = lint("""
            class Runner:
                def a(self):
                    self._flight_dump("train.typo", "msg")
                def b(self):
                    self.flight.dump(site="also.typo", directory="d")
        """, "SGL009")
        assert codes_of(out) == ["SGL009", "SGL009"]

    def test_registered_sites_are_clean(self):
        # injection sites AND the incident-only seams both validate
        # (serve.verify: the ISSUE 13 speculative seam; serve.spill:
        # the ISSUE 17 memory-hierarchy seam)
        out = lint("""
            class Engine:
                def ok(self):
                    self.flight.dump("serve.prefill", "runs/incidents")
                    self.flight.dump("serve.verify", "runs/incidents")
                    self.flight.dump("serve.arena", "runs/incidents")
                    self.flight.dump("serve.spill", "runs/incidents")
                    self._flight_dump("train.fatal", "msg")
        """, "SGL009")
        assert out == []

    def test_net_dump_sites_are_clean_and_typos_fire(self):
        """ISSUE 18: the multi-process tier's incident dumps (torn
        transfers at serve.transport, drains at serve.resize) name
        registered sites; typos fire."""
        out = lint("""
            class Supervisor:
                def ok(self):
                    self.flight.dump("serve.transport", "runs/incidents")
                    self._flight_dump("serve.resize", "drain")
        """, "SGL009")
        assert out == []
        out = lint("""
            class Supervisor:
                def boom(self):
                    self._flight_dump("serve.trasport", "msg")
        """, "SGL009")
        assert codes_of(out) == ["SGL009"]
        assert "serve.trasport" in out[0].message

    def test_unrelated_dump_calls_are_ignored(self):
        out = lint("""
            import json

            def save(obj, f):
                json.dump(obj, f)          # nothing says 'flight'
                pickle.dump("whatever", f)
        """, "SGL009")
        assert out == []

    def test_unloadable_registry_is_a_finding_not_a_pass(self, tmp_path,
                                                         monkeypatch):
        from tools.lint import rules
        monkeypatch.setattr(rules, "_REPO_ROOT", str(tmp_path))
        monkeypatch.setattr(rules, "_SITES_CACHE", {})
        monkeypatch.setattr(rules, "_INCIDENT_CACHE", {})
        out = lint("""
            class Engine:
                def boom(self):
                    self.flight.dump("serve.arena", "runs/incidents")
        """, "SGL009")
        assert codes_of(out) == ["SGL009"]
        assert "could not be loaded" in out[0].message


# ---------------------------------------------------------------------------
# SGL008 host-sync hazard
# ---------------------------------------------------------------------------

class TestHostSync:
    def test_fires_on_asarray_in_engine_step(self):
        out = lint("""
            import numpy as np

            class FooEngine:
                def step(self):
                    toks = np.asarray(self._toks)
                    return toks
        """, "SGL008")
        assert codes_of(out) == ["SGL008"]
        assert "np.asarray" in out[0].message
        assert "FooEngine.step()" in out[0].message

    def test_fires_one_helper_level_deep(self):
        out = lint("""
            import jax

            class BarRunner:
                def run(self):
                    self._emit()

                def _emit(self):
                    jax.device_get(self.loss)
        """, "SGL008")
        assert codes_of(out) == ["SGL008"]
        assert "called from run()" in out[0].message

    def test_fires_on_item_and_float_in_step_region(self):
        out = lint("""
            class BazRunner:
                def _step_once(self, x):
                    a = x.item()
                    b = float(self.loss)
                    return a + b
        """, "SGL008")
        assert codes_of(out) == ["SGL008", "SGL008"]

    def test_clean_outside_hot_regions_and_classes(self):
        # a cold method on a hot class, and a hot-named method on a
        # cold class, are both out of scope
        out = lint("""
            import numpy as np

            class FooEngine:
                def snapshot(self):
                    return np.asarray(self._toks)

            class Helper:
                def step(self):
                    return np.asarray(self.buf)
        """, "SGL008")
        assert out == []

    def test_suppression_with_reason_is_honored(self):
        out = lint("""
            import numpy as np

            class FooEngine:
                def step(self):
                    return np.asarray(self._toks)  # singalint: disable=SGL008 one num_slots-int fetch per tick is the designed sync
        """, "SGL008")
        assert out == []


# ---------------------------------------------------------------------------
# suppression contract
# ---------------------------------------------------------------------------

class TestSuppressions:
    def test_suppression_with_reason_is_honored(self):
        out = lint_source(
            "import time\n"
            "t = time.time()  # singalint: disable=SGL005 epoch "
            "timestamp for cross-host correlation\n")
        assert out == []

    def test_suppression_without_reason_is_a_finding(self):
        out = lint_source(
            "import time\n"
            "t = time.time()  # singalint: disable=SGL005\n")
        assert CODE_SUPPRESSION in codes_of(out)

    def test_suppression_of_unknown_code_is_a_finding(self):
        out = lint_source("x = 1  # singalint: disable=SGL942 because\n")
        assert codes_of(out) == [CODE_SUPPRESSION]
        assert "SGL942" in out[0].message

    def test_suppression_only_covers_its_own_line(self):
        out = lint_source(
            "import time\n"
            "a = time.time()  # singalint: disable=SGL005 fine here\n"
            "b = time.time()\n")
        assert codes_of(out) == ["SGL005"]
        assert out[0].line == 3

    def test_suppression_inside_string_literal_is_ignored(self):
        out = lint_source(
            'doc = "# singalint: disable=SGL005"\n'
            "import time\n"
            "t = time.time()\n")
        assert codes_of(out) == ["SGL005"]


# ---------------------------------------------------------------------------
# output formats + CLI
# ---------------------------------------------------------------------------

class TestOutputAndCli:
    def test_json_output_schema(self):
        findings = lint_source("import time\nt = time.time()\n",
                               path="x.py")
        doc = json.loads(render_json(findings))
        assert doc["version"] == 1
        assert doc["count"] == len(findings) == 1
        f = doc["findings"][0]
        assert set(f) == {"path", "line", "col", "code", "message"}
        assert f["path"] == "x.py" and f["code"] == "SGL005"

    def test_syntax_error_is_a_finding_not_a_crash(self):
        out = lint_source("def broken(:\n")
        assert codes_of(out) == ["SGL999"]

    def test_cli_exit_codes_and_select(self, tmp_path, capsys):
        bad = tmp_path / "bad.py"
        bad.write_text("import time\nt = time.time()\n")
        clean = tmp_path / "clean.py"
        clean.write_text("import time\nt = time.monotonic()\n")
        assert lint_main([str(clean)]) == 0
        assert lint_main([str(bad)]) == 1
        assert lint_main(["--select", "SGL001", str(bad)]) == 0
        out = capsys.readouterr().out
        assert "singalint: clean" in out
        with pytest.raises(SystemExit):
            lint_main(["--select", "SGL942", str(bad)])

    def test_cli_rejects_paths_matching_no_files(self, tmp_path):
        # a typo'd/renamed dir expanding to zero files must not exit 0
        with pytest.raises(SystemExit):
            lint_main([str(tmp_path / "no_such_dir")])
        with pytest.raises(SystemExit):
            lint_main([str(tmp_path)])  # exists, but has no .py files
        # the API behind the repo-is-clean gate refuses too
        with pytest.raises(ValueError):
            run_paths([str(tmp_path / "no_such_dir")])

    def test_cli_audit_modes_reject_lint_paths(self):
        # silently dropping the paths would be a false-clean signal
        with pytest.raises(SystemExit):
            lint_main(["singa_tpu", "--records"])
        with pytest.raises(SystemExit):
            lint_main(["singa_tpu", "--ckpt", "somedir"])
        with pytest.raises(SystemExit):
            lint_main(["--records", "--ckpt", "somedir"])
        with pytest.raises(SystemExit):
            lint_main(["singa_tpu", "--hlo"])
        with pytest.raises(SystemExit):
            lint_main(["--hlo", "--records"])
        with pytest.raises(SystemExit):
            lint_main(["singa_tpu", "--proc"])
        with pytest.raises(SystemExit):
            lint_main(["--proc", "--conc"])

    def test_cli_select_covers_audit_modes(self, tmp_path, monkeypatch):
        """--select enumerates/filters audit modes alongside SGL codes:
        mode names apply to the bare full-audit invocation only, and
        ckpt (which needs its DIR) points at --ckpt."""
        bad = tmp_path / "bad.py"
        bad.write_text("import time\nt = time.time()\n")
        # a mode name mixed with explicit lint paths is a usage error
        with pytest.raises(SystemExit):
            lint_main(["--select", "hlo", str(bad)])
        with pytest.raises(SystemExit):
            lint_main(["--select", "ckpt"])
        # bare --select records runs just that audit (stubbed: jax)
        from tools.lint import __main__ as cli
        seen = []
        monkeypatch.setattr(cli.audit, "records_main",
                            lambda root: seen.append(root) or 0)
        assert lint_main(["--select", "records"]) == 0
        assert seen == [cli.audit._REPO_ROOT]

    def test_bare_invocation_runs_static_and_hlo(self, monkeypatch,
                                                 capsys):
        """`python -m tools.lint` with no paths and no mode flags is
        the full audit: static rules over the repo trees AND the HLO
        gate (stubbed here — the real gate runs in test_hlo_audit.py),
        exit code ORed across both halves."""
        from tools.lint import __main__ as cli
        from tools.lint import hlo as hlo_mod
        calls = []

        def fake_hlo_main(update=False, json_out=False, structure=True,
                          cost_gate=True, **kw):
            calls.append((json_out, structure, cost_gate))
            return 0

        monkeypatch.setattr(hlo_mod, "hlo_main", fake_hlo_main)
        monkeypatch.setattr(
            cli, "run_paths",
            lambda paths, codes=None: [] if [p for p in paths] else [])
        assert lint_main([]) == 0
        # bare run: ONE hlo_main call covering structure AND cost —
        # the shared-lowering contract at the CLI layer
        assert calls == [(False, True, True)]
        assert "singalint: clean" in capsys.readouterr().out
        # --select routes the gate halves through the same single call
        calls.clear()
        assert lint_main(["--select", "cost"]) == 0
        assert calls == [(False, False, True)]
        calls.clear()
        assert lint_main(["--select", "hlo"]) == 0
        assert calls == [(False, True, False)]
        capsys.readouterr()
        # a failing gate fails the full audit even when static is clean
        monkeypatch.setattr(hlo_mod, "hlo_main",
                            lambda **kw: 1)
        assert lint_main([]) == 1

    def test_cli_records_root_resolution(self, monkeypatch):
        """Bare --records means repo root; an explicit '.' means cwd
        (audit.records_main is stubbed — it imports jax)."""
        from tools.lint import __main__ as cli
        seen = []
        monkeypatch.setattr(cli.audit, "records_main",
                            lambda root: seen.append(root) or 0)
        assert lint_main(["--records"]) == 0
        assert lint_main(["--records", "."]) == 0
        assert seen == [cli.audit._REPO_ROOT, "."]

    def test_cli_list_rules(self, capsys):
        """The front door is discoverable from --list-rules alone:
        every SGL rule, every audit mode, every HLO/COST metric code."""
        from tools.lint.cost import COST_CODES
        from tools.lint.hlo import HLO_CODES
        assert lint_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for code in RULES:
            assert code in out
        for mode in ("records", "ckpt", "conc", "proc", "hlo", "cost"):
            assert f"\n  {mode}" in out
        for code in HLO_CODES:
            assert code in out
        for code in COST_CODES:
            assert code in out
        # the compiler-owned metrics and the runtime-attribution gate
        # are gone, not hidden
        for gone in ("HLO002", "HLO006", "HLO007", "COST003", "COST006",
                     "PERF0"):
            assert gone not in out
        # conclint: the thread-model gate code and the retired alias
        assert "SGL014" in out
        assert "SGL004" in out and "retired" in out
        # proclint: the process-model + RPC-protocol gate codes
        assert "SGL016" in out
        assert "SGL019" in out

    def test_cli_json(self, tmp_path, capsys):
        bad = tmp_path / "bad.py"
        bad.write_text("import time\nt = time.time()\n")
        assert lint_main(["--json", str(bad)]) == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["count"] == 1


# ---------------------------------------------------------------------------
# conclint: the committed thread-model baseline (SGL014)
# ---------------------------------------------------------------------------

class TestThreadModel:
    ROOTED = """
import threading


class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0

    def start(self):
        threading.Thread(target=self._run).start()

    def _run(self):
        with self._lock:
            self.n += 1
"""

    def _tree(self, tmp_path, src=None):
        pkg = tmp_path / "singa_tpu"
        pkg.mkdir(exist_ok=True)
        (pkg / "w.py").write_text(src or self.ROOTED)
        (tmp_path / "tools").mkdir(exist_ok=True)
        (tmp_path / "tools" / "t.py").write_text("X = 1\n")
        return [str(pkg), str(tmp_path / "tools")]

    def test_discovery_finds_roots_and_classifies_shared(self, tmp_path):
        from tools.lint import conc
        model = conc.discover_model(self._tree(tmp_path),
                                    root=str(tmp_path))
        assert model["roots"] == {"singa_tpu/w.py::Worker._run": "thread"}
        assert model["shared"] == {
            "singa_tpu/w.py::Worker._lock": "mediated",
            "singa_tpu/w.py::Worker.n": "lock-guarded"}

    def test_baseline_update_round_trip(self, tmp_path):
        from tools.lint import conc
        paths = self._tree(tmp_path)
        base = str(tmp_path / "model.json")
        # no baseline: the gate fails loudly, never silently passes
        missing = conc.gate_findings(paths=paths, baseline_path=base,
                                     root=str(tmp_path))
        assert [f.code for f in missing] == ["SGL014"]
        assert "no committed thread-model baseline" in missing[0].message
        # update writes the model and prints the reviewed diff ...
        diff = conc.update_model_baseline(paths=paths,
                                          baseline_path=base,
                                          root=str(tmp_path))
        assert "+ root singa_tpu/w.py::Worker._run: thread" in diff
        # ... after which the gate is clean, and a no-op re-update says so
        assert conc.gate_findings(paths=paths, baseline_path=base,
                                  root=str(tmp_path)) == []
        assert "unchanged" in conc.update_model_baseline(
            paths=paths, baseline_path=base, root=str(tmp_path))

    def test_new_thread_root_fails_loudly(self, tmp_path):
        from tools.lint import conc
        paths = self._tree(tmp_path)
        base = str(tmp_path / "model.json")
        conc.update_model_baseline(paths=paths, baseline_path=base,
                                   root=str(tmp_path))
        # an UNREGISTERED Thread(target=) appears -> loud, named finding
        (tmp_path / "singa_tpu" / "w.py").write_text(
            self.ROOTED + """

class Sneaky:
    def go(self):
        threading.Thread(target=self._bg).start()

    def _bg(self):
        pass
""")
        out = conc.gate_findings(paths=paths, baseline_path=base,
                                 root=str(tmp_path))
        assert [f.code for f in out] == ["SGL014"]
        assert "NEW thread root" in out[0].message
        assert "Sneaky._bg" in out[0].message
        assert "--update-baselines" in out[0].message

    def test_deleted_baseline_entry_fails_loudly(self, tmp_path):
        """The acceptance shape: removing a committed root's entry (a
        hand-edit, or a stale baseline) fails until the reviewed
        re-baseline runs."""
        import json as _json

        from tools.lint import conc
        paths = self._tree(tmp_path)
        base = str(tmp_path / "model.json")
        conc.update_model_baseline(paths=paths, baseline_path=base,
                                   root=str(tmp_path))
        doc = _json.loads(open(base).read())
        doc["roots"].pop("singa_tpu/w.py::Worker._run")
        open(base, "w").write(_json.dumps(doc))
        out = conc.gate_findings(paths=paths, baseline_path=base,
                                 root=str(tmp_path))
        assert [f.code for f in out] == ["SGL014"]
        assert "NEW thread root" in out[0].message
        # and the reviewed update flow clears it
        conc.update_model_baseline(paths=paths, baseline_path=base,
                                   root=str(tmp_path))
        assert conc.gate_findings(paths=paths, baseline_path=base,
                                  root=str(tmp_path)) == []

    def test_classification_drift_fails_loudly(self, tmp_path):
        from tools.lint import conc
        paths = self._tree(tmp_path)
        base = str(tmp_path / "model.json")
        conc.update_model_baseline(paths=paths, baseline_path=base,
                                   root=str(tmp_path))
        # the guard vanishes: lock-guarded -> unguarded must be loud
        (tmp_path / "singa_tpu" / "w.py").write_text(
            self.ROOTED.replace("        with self._lock:\n"
                                "            self.n += 1",
                                "        self.n += 1"))
        out = conc.gate_findings(paths=paths, baseline_path=base,
                                 root=str(tmp_path))
        # two honest findings: n's classification drifted, and the now
        # unused _lock dropped out of the cross-thread table
        assert set(f.code for f in out) == {"SGL014"}
        assert any("lock-guarded -> unguarded" in f.message
                   for f in out)

    def test_stale_root_in_baseline_fails_loudly(self, tmp_path):
        from tools.lint import conc
        paths = self._tree(tmp_path)
        base = str(tmp_path / "model.json")
        conc.update_model_baseline(paths=paths, baseline_path=base,
                                   root=str(tmp_path))
        (tmp_path / "singa_tpu" / "w.py").write_text("Y = 2\n")
        out = conc.gate_findings(paths=paths, baseline_path=base,
                                 root=str(tmp_path))
        codes = [f.code for f in out]
        assert codes and set(codes) == {"SGL014"}
        assert any("was not discovered" in f.message for f in out)


def test_ci_gate_picks_up_conclint_with_no_stage_renumbering():
    """tools/ci_gate.sh stage 1 is the bare `python -m tools.lint`
    full audit, which now includes the conc thread-model gate — so
    conclint rides in with NO extra stage (ISSUE 15 satellite): the
    script declares a contiguous ladder (1/10..10/10 since ISSUE 19's
    chaos-smoke stage) and its stage-1 command is still the bare
    invocation."""
    sh = open(os.path.join(REPO, "tools", "ci_gate.sh")).read()
    for n in range(1, 11):
        assert f"stage {n}/10" in sh, \
            f"stage {n}/10 vanished/renumbered"
    assert "stage 11" not in sh
    stage1 = sh.split("stage 2/10")[0]
    assert "python -m tools.lint || exit 10" in stage1
    # the chaos stage rides the ladder with its own exit code
    assert "python -m tools.chaosd --smoke || exit 18" in sh
    # and the bare invocation really runs the conc gate (CLI contract)
    from tools.lint.__main__ import _AUDIT_MODES
    assert "conc" in _AUDIT_MODES


# ---------------------------------------------------------------------------
# proclint (SGL015/SGL016/SGL017/SGL019) — the process-mesh audit
# ---------------------------------------------------------------------------

class TestResourceLifecycle:
    """SGL015: acquire/release pairing on the exception path."""

    def test_never_released_socket(self):
        out = lint("""
            import socket

            def probe(host):
                s = socket.socket()
                s.connect(host)
                return 1
            """, "SGL015")
        assert codes_of(out) == ["SGL015"]
        assert "never released in probe()" in out[0].message

    def test_straight_line_release_only(self):
        out = lint("""
            import socket

            def probe(host):
                s = socket.socket()
                s.connect(host)
                s.close()
            """, "SGL015")
        assert codes_of(out) == ["SGL015"]
        assert "released only on the straight-line path" \
            in out[0].message

    def test_discarded_popen_result(self):
        out = lint("""
            import subprocess

            def fire(cmd):
                subprocess.Popen(cmd, env={})
            """, "SGL015")
        assert codes_of(out) == ["SGL015"]
        assert "result discarded" in out[0].message

    def test_self_attr_with_no_releasing_method(self):
        out = lint("""
            import socket

            class Hub:
                def __init__(self):
                    self.sock = socket.socket()
            """, "SGL015")
        assert codes_of(out) == ["SGL015"]
        assert "no method of Hub releases it" in out[0].message

    def test_temp_dir_leak(self):
        out = lint("""
            import tempfile

            def scratch(do):
                d = tempfile.mkdtemp()
                do(d)
            """, "SGL015")
        assert codes_of(out) == ["SGL015"]
        assert "temp dir" in out[0].message

    def test_clean_try_finally(self):
        assert lint("""
            import socket

            def probe(host):
                s = socket.socket()
                try:
                    s.connect(host)
                finally:
                    s.close()
            """, "SGL015") == []

    def test_clean_with_block(self):
        assert lint("""
            import socket

            def probe(host):
                with socket.socket() as s:
                    s.connect(host)
            """, "SGL015") == []

    def test_clean_owning_class_release(self):
        assert lint("""
            import socket

            class Hub:
                def __init__(self):
                    self.sock = socket.socket()

                def close(self):
                    self.sock.close()
            """, "SGL015") == []

    def test_clean_registered_cleanup(self):
        assert lint("""
            import atexit
            import tempfile

            def scratch(use, cleanup):
                d = tempfile.mkdtemp()
                atexit.register(cleanup, d)
                return use(d)
            """, "SGL015") == []

    def test_clean_escape_to_ledger(self):
        assert lint("""
            import subprocess

            class Pool:
                def spawn(self, cmd):
                    p = subprocess.Popen(cmd, env={})
                    self.procs.append(p)
            """, "SGL015") == []

    def test_clean_helper_release_on_except_path(self):
        # the one-helper-level closure: self._reap releases its param
        assert lint("""
            import subprocess

            class Pool:
                def spawn(self, cmd):
                    p = subprocess.Popen(cmd, env={})
                    try:
                        self._adopt(p)
                    except Exception:
                        self._reap([p])
                        raise

                def _adopt(self, p):
                    self.procs.append(p)

                def _reap(self, procs):
                    for q in procs:
                        q.kill()
                        q.wait()
            """, "SGL015") == []

    def test_clean_wait_consumed_in_place(self):
        assert lint("""
            import subprocess

            def run(cmd):
                subprocess.Popen(cmd, env={}).wait()
            """, "SGL015") == []

    def test_suppression_with_reason_honored(self):
        assert lint("""
            import socket

            def probe(host):
                s = socket.socket()  # singalint: disable=SGL015 probe socket is process-lifetime by design
                s.connect(host)
            """, "SGL015") == []


class TestEnvContract:
    """SGL017: the child-env scrub seam around subprocess.Popen."""

    def test_popen_without_env_double_fires(self):
        out = lint("""
            import subprocess

            def fire(cmd):
                return subprocess.Popen(cmd)
            """, "SGL017")
        assert codes_of(out) == ["SGL017"]
        assert "without a scrubbed env=" in out[0].message

    def test_dropped_scrub_is_a_named_finding(self):
        # the seeded regression: the scrub seam lost two of its pops
        out = lint("""
            import os
            import subprocess

            def fire(cmd):
                env = dict(os.environ)
                env.pop("SINGA_OBS", None)
                return subprocess.Popen(cmd, env=env)
            """, "SGL017")
        assert codes_of(out) == ["SGL017"]
        assert "does not scrub" in out[0].message
        assert "SINGA_FAULTS" in out[0].message

    def test_env_write_outside_seam(self):
        out = lint("""
            import os

            def arm(plan):
                os.environ["SINGA_FAULTS"] = plan
            """, "SGL017")
        assert codes_of(out) == ["SGL017"]
        assert "outside the child-env scrub seam" in out[0].message

    def test_clean_loop_form_scrub_seam(self):
        # the supervisor's actual seam shape
        assert lint("""
            import os
            import subprocess

            def fire(cmd):
                env = dict(os.environ)
                for k in ("SINGA_FAULTS", "SINGA_FAULTS_SEED",
                          "SINGA_OBS"):
                    env.pop(k, None)
                return subprocess.Popen(cmd, env=env)
            """, "SGL017") == []

    def test_clean_write_inside_seam(self):
        # the seam itself MAY set fault vars — that is what it is for
        assert lint("""
            import os

            def child_env(plan):
                env = dict(os.environ)
                for k in ("SINGA_FAULTS", "SINGA_FAULTS_SEED",
                          "SINGA_OBS"):
                    env.pop(k, None)
                env["SINGA_FAULTS"] = plan
                return env
            """, "SGL017") == []

    def test_clean_scratch_dict_env(self):
        # a from-scratch literal env inherits nothing
        assert lint("""
            import subprocess

            def fire(cmd):
                return subprocess.Popen(cmd, env={"PATH": "/usr/bin"})
            """, "SGL017") == []

    def test_clean_helper_seam(self):
        assert lint("""
            import os
            import subprocess

            class Fab:
                def _child_env(self):
                    env = dict(os.environ)
                    for k in ("SINGA_FAULTS", "SINGA_FAULTS_SEED",
                              "SINGA_OBS"):
                        env.pop(k, None)
                    return env

                def spawn(self, cmd):
                    return subprocess.Popen(
                        cmd, env=self._child_env())
            """, "SGL017") == []


_PROTO_WORKER = '''\
class Worker:
    def _op_submit(self, hdr):
        return {"ok": True}

    def _op_tick(self, hdr):
        return {"ok": True}

    def serve(self, op, hdr):
        if op == "shutdown":
            return {"ok": True}
        return getattr(self, "_op_" + op)(hdr)
'''

_PROTO_WORKER_ONE_SIDED = _PROTO_WORKER + '''

class WorkerWithDeadOp(Worker):
    def _op_submit(self, hdr):
        return {"ok": True}

    def _op_resize(self, hdr):
        return {"ok": True}
'''

_PROTO_DRIVER = '''\
_OP_TIMEOUTS = {"submit": 5.0, "tick": 1.0, "shutdown": 3.0}


def drive(w):
    w.call({"op": "submit"})
    w.send({"op": "tick"})
    w.call({"op": "shutdown"})
'''


class TestRpcProtocol:
    """SGL016: dispatch table vs. call sites vs. _OP_TIMEOUTS."""

    def _proto(self, tmp_path, worker, driver):
        from tools.lint import proc
        (tmp_path / "worker.py").write_text(worker)
        (tmp_path / "driver.py").write_text(driver)
        return proc.protocol_findings(paths=[str(tmp_path)],
                                      root=str(tmp_path))

    def test_conformant_protocol_is_clean(self, tmp_path):
        assert self._proto(tmp_path, _PROTO_WORKER,
                           _PROTO_DRIVER) == []

    def test_one_sided_handled_op_fails_loudly(self, tmp_path):
        # the seeded regression: a handler nobody calls
        out = self._proto(tmp_path, _PROTO_WORKER_ONE_SIDED,
                          _PROTO_DRIVER)
        assert out and set(codes_of(out)) == {"SGL016"}
        assert any("'resize'" in f.message and
                   "never sent" in f.message for f in out)
        # ...and the same op is missing its deadline row
        assert any("'resize'" in f.message and
                   "no _OP_TIMEOUTS deadline entry" in f.message
                   for f in out)

    def test_called_but_unhandled_op(self, tmp_path):
        out = self._proto(
            tmp_path, _PROTO_WORKER,
            _PROTO_DRIVER + '\n\ndef extra(w):\n'
            '    w.call({"op": "status"})\n')
        assert codes_of(out) == ["SGL016"]
        assert "no worker handler" in out[0].message

    def test_handled_op_without_deadline(self, tmp_path):
        out = self._proto(
            tmp_path, _PROTO_WORKER,
            _PROTO_DRIVER.replace('"tick": 1.0, ', ""))
        assert codes_of(out) == ["SGL016"]
        assert "'tick'" in out[0].message
        assert "no _OP_TIMEOUTS deadline entry" in out[0].message

    def test_stale_deadline_row(self, tmp_path):
        out = self._proto(
            tmp_path, _PROTO_WORKER,
            _PROTO_DRIVER.replace('"submit": 5.0',
                                  '"submit": 5.0, "flush": 2.0'))
        assert codes_of(out) == ["SGL016"]
        assert "'flush'" in out[0].message
        assert "names an op no worker handles" in out[0].message

    def test_codec_version_skew(self, tmp_path):
        out = self._proto(tmp_path, '''\
MAGIC = b"SGKV"
WIRE_VERSION = 2


def encode_pkg(x):
    return MAGIC + bytes([WIRE_VERSION]) + x


def decode_pkg(data):
    if data[:4] != MAGIC:
        raise ValueError("bad magic")
    version = data[4]
    if version != 1:
        raise ValueError("bad version")
    return data[5:]
''', "")
        assert codes_of(out) == ["SGL016"]
        assert "wire-version skew" in out[0].message

    def test_codec_magic_skew(self, tmp_path):
        out = self._proto(tmp_path, '''\
def encode_pkg(x):
    return b"SGKV" + x


def decode_pkg(data):
    if data[:4] != b"SGKW":
        raise ValueError("bad magic")
    return data[4:]
''', "")
        assert codes_of(out) == ["SGL016"]
        assert "magic skew" in out[0].message

    def test_codec_shared_constants_clean(self, tmp_path):
        assert self._proto(tmp_path, '''\
MAGIC = b"SGKV"
WIRE_VERSION = 2


def encode_pkg(x):
    return MAGIC + bytes([WIRE_VERSION]) + x


def decode_pkg(data):
    if data[:4] != MAGIC:
        raise ValueError("bad magic")
    version = data[4]
    if version != WIRE_VERSION:
        raise ValueError("bad version")
    return data[5:]
''', "") == []


class TestProcessModel:
    """SGL019: the committed process-model baseline gate."""

    FABRIC = '''\
import os
import signal
import socket
import subprocess


class Fabric:
    def __init__(self):
        self.listener = socket.socket()
        self.procs = []

    def spawn(self, cmd):
        env = dict(os.environ)
        for k in ("SINGA_FAULTS", "SINGA_FAULTS_SEED", "SINGA_OBS"):
            env.pop(k, None)
        p = subprocess.Popen(cmd, env=env)
        self.procs.append(p)
        conn, _ = self.listener.accept()
        return conn

    def reap(self, p):
        p.kill()
        p.wait(timeout=5.0)
        self.procs.remove(p)

    def pause(self, p):
        os.kill(p.pid, signal.SIGSTOP)

    def close(self):
        self.listener.close()
'''

    def _ptree(self, tmp_path):
        (tmp_path / "singa_tpu").mkdir()
        (tmp_path / "tools").mkdir()
        (tmp_path / "singa_tpu" / "w.py").write_text(self.FABRIC)
        (tmp_path / "tools" / "t.py").write_text(
            "def boot(fabric):\n    fabric.spawn_many(2)\n")
        return [str(tmp_path / "singa_tpu"), str(tmp_path / "tools")]

    def test_discovery(self, tmp_path):
        from tools.lint import proc
        paths = self._ptree(tmp_path)
        model = proc.discover_model(paths=paths, root=str(tmp_path))
        assert model["roots"] == {
            "singa_tpu/w.py::Fabric.spawn": "popen",
            "tools/t.py::boot": "spawn-call"}
        # the kill next to its wait is reaped; the bare SIGSTOP is not
        assert model["signals"] == {
            "singa_tpu/w.py::Fabric.reap": "SIGKILL",
            "singa_tpu/w.py::Fabric.pause": "SIGSTOP!noreap"}
        assert model["reaps"] == {
            "singa_tpu/w.py::Fabric.reap": "ledger+wait"}
        assert model["sockets"] == {
            "singa_tpu/w.py::Fabric.__init__": "socket",
            "singa_tpu/w.py::Fabric.spawn": "accept"}
        assert model["hash"] == proc.model_hash(model)

    def test_missing_baseline_fails_loudly(self, tmp_path):
        from tools.lint import proc
        paths = self._ptree(tmp_path)
        out = proc.gate_findings(
            paths=paths, baseline_path=str(tmp_path / "model.json"),
            root=str(tmp_path))
        assert codes_of(out) == ["SGL019"]
        assert "no committed process-model baseline" in out[0].message
        assert "--update-baselines" in out[0].message

    def test_baseline_round_trip(self, tmp_path):
        from tools.lint import proc
        paths = self._ptree(tmp_path)
        base = str(tmp_path / "model.json")
        diff = proc.update_model_baseline(
            paths=paths, baseline_path=base, root=str(tmp_path))
        assert "+ root singa_tpu/w.py::Fabric.spawn: popen" in diff
        assert "+ signal singa_tpu/w.py::Fabric.pause: " \
               "SIGSTOP!noreap" in diff
        assert proc.gate_findings(paths=paths, baseline_path=base,
                                  root=str(tmp_path)) == []
        # a second update with no tree change is a no-op
        assert "process model unchanged" in proc.update_model_baseline(
            paths=paths, baseline_path=base, root=str(tmp_path))

    def test_new_spawn_root_fails_loudly(self, tmp_path):
        from tools.lint import proc
        paths = self._ptree(tmp_path)
        base = str(tmp_path / "model.json")
        proc.update_model_baseline(paths=paths, baseline_path=base,
                                   root=str(tmp_path))
        (tmp_path / "singa_tpu" / "w.py").write_text(
            self.FABRIC + "\n\nclass Sneaky:\n"
            "    def go(self, cmd):\n"
            "        self.p = subprocess.Popen(cmd, env={})\n")
        out = proc.gate_findings(paths=paths, baseline_path=base,
                                 root=str(tmp_path))
        assert out and set(codes_of(out)) == {"SGL019"}
        assert any("NEW process root" in f.message and
                   "Sneaky.go" in f.message and
                   "--update-baselines" in f.message for f in out)

    def test_deleted_reap_site_fails_loudly(self, tmp_path):
        # the seeded regression: the kill keeps firing but its reap
        # (and the ledger removal) are gone — zombie processes
        from tools.lint import proc
        paths = self._ptree(tmp_path)
        base = str(tmp_path / "model.json")
        proc.update_model_baseline(paths=paths, baseline_path=base,
                                   root=str(tmp_path))
        (tmp_path / "singa_tpu" / "w.py").write_text(
            self.FABRIC.replace("        p.wait(timeout=5.0)\n"
                                "        self.procs.remove(p)\n", ""))
        out = proc.gate_findings(paths=paths, baseline_path=base,
                                 root=str(tmp_path))
        assert out and set(codes_of(out)) == {"SGL019"}
        # the kill LOST its reap path: a value change, not silence
        assert any("SIGKILL -> SIGKILL!noreap" in f.message
                   for f in out)
        # and the reap site itself vanished from the mesh
        assert any("was not discovered" in f.message and
                   "zombie" in f.message for f in out)

    def test_hand_edited_baseline_fails_loudly(self, tmp_path):
        from tools.lint import proc
        paths = self._ptree(tmp_path)
        base = str(tmp_path / "model.json")
        proc.update_model_baseline(paths=paths, baseline_path=base,
                                   root=str(tmp_path))
        doc = json.load(open(base))
        doc["signals"] = {}    # edit sections, keep the stale hash
        json.dump(doc, open(base, "w"))
        out = proc.gate_findings(paths=paths, baseline_path=base,
                                 root=str(tmp_path))
        assert codes_of(out) == ["SGL019"]
        assert "hand-edited" in out[0].message

    def test_schema_mismatch_fails_loudly(self, tmp_path):
        from tools.lint import proc
        paths = self._ptree(tmp_path)
        base = str(tmp_path / "model.json")
        proc.update_model_baseline(paths=paths, baseline_path=base,
                                   root=str(tmp_path))
        doc = json.load(open(base))
        doc["schema"] = 99
        json.dump(doc, open(base, "w"))
        out = proc.gate_findings(paths=paths, baseline_path=base,
                                 root=str(tmp_path))
        assert codes_of(out) == ["SGL019"]
        assert "schema" in out[0].message


def test_cli_proc_gate_drives_exit_codes(tmp_path, monkeypatch,
                                         capsys):
    """`python -m tools.lint --proc` end to end: missing baseline ->
    exit 1 with SGL019; `--update-baselines` writes the reviewed
    model; a one-sided RPC op -> exit 1 with SGL016."""
    from tools.lint import proc
    (tmp_path / "singa_tpu").mkdir()
    (tmp_path / "tools").mkdir()
    (tmp_path / "singa_tpu" / "worker.py").write_text(_PROTO_WORKER)
    (tmp_path / "singa_tpu" / "driver.py").write_text(_PROTO_DRIVER)
    monkeypatch.setattr(proc, "_REPO_ROOT", str(tmp_path))
    monkeypatch.setattr(proc, "MODEL_PATH",
                        str(tmp_path / "model.json"))
    assert lint_main(["--proc"]) == 1
    out = capsys.readouterr().out
    assert "SGL019" in out and "proclint:" in out
    assert lint_main(["--proc", "--update-baselines"]) == 0
    out = capsys.readouterr().out
    assert "process model" in out and "model.json" in out
    assert lint_main(["--proc"]) == 0
    assert "clean" in capsys.readouterr().out
    (tmp_path / "singa_tpu" / "worker.py").write_text(
        _PROTO_WORKER_ONE_SIDED)
    assert lint_main(["--proc"]) == 1
    out = capsys.readouterr().out
    assert "SGL016" in out and "resize" in out


def test_ci_gate_picks_up_proclint_with_no_stage_renumbering():
    """proclint rides ci_gate stage 1 (the bare full audit) with NO
    extra stage (ISSUE 20 satellite): the ladder is still 1/10..10/10
    and the stage-1 comment names the process-mesh gate."""
    sh = open(os.path.join(REPO, "tools", "ci_gate.sh")).read()
    for n in range(1, 11):
        assert f"stage {n}/10" in sh, \
            f"stage {n}/10 vanished/renumbered"
    assert "stage 11" not in sh
    stage1 = sh.split("stage 2/10")[0]
    assert "python -m tools.lint || exit 10" in stage1
    assert "proclint" in stage1
    from tools.lint.__main__ import _AUDIT_MODES
    assert "proc" in _AUDIT_MODES


def test_chaosd_and_serve_net_covered_by_wallclock_and_fault_rules():
    """SGL005 (unbounded waits) and SGL007 (fault-seam hygiene)
    explicitly cover the chaos driver and the serve/net tier (ISSUE 20
    satellite) — and both are clean."""
    findings = run_paths(
        [os.path.join(REPO, "tools", "chaosd.py"),
         os.path.join(REPO, "singa_tpu", "serve", "net")],
        codes=["SGL005", "SGL007"])
    assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# the tier-1 gate: the repo itself is clean
# ---------------------------------------------------------------------------

def test_repo_is_clean():
    """`python -m tools.lint singa_tpu tools` exits 0 on this tree —
    every invariant the rules encode is self-enforcing from here on.
    A finding here means: fix the violation, or suppress it inline WITH
    A REASON (see docs/static-analysis.md for the policy)."""
    findings = run_paths([os.path.join(REPO, "singa_tpu"),
                          os.path.join(REPO, "tools")])
    assert findings == [], "\n".join(f.render() for f in findings)


def test_repo_thread_model_is_clean():
    """The committed tools/lint/data/conc/model.json matches the
    tree's discovered thread mesh exactly: every concurrency domain
    and cross-thread attribute in HEAD has been reviewed.  A finding
    here means: review the new/changed domain, then run
    `python -m tools.lint --conc --update-baselines` and commit the
    diff it prints (docs/static-analysis.md, "Concurrency audit")."""
    from tools.lint import conc
    findings = conc.gate_findings()
    assert findings == [], "\n".join(f.render() for f in findings)


def test_repo_process_model_is_clean():
    """The committed tools/lint/data/proc/model.json matches the
    tree's discovered process mesh exactly — every spawn site, signal
    send, reap site, and socket in HEAD has been reviewed — and the
    RPC protocol's three views (dispatch table, call sites,
    _OP_TIMEOUTS) agree.  A finding here means: review the change,
    then run `python -m tools.lint --proc --update-baselines` and
    commit the diff it prints (docs/static-analysis.md,
    "Process-mesh audit")."""
    from tools.lint import proc
    findings = proc.audit_findings()
    assert findings == [], "\n".join(f.render() for f in findings)
