"""The engine's account of its own host time (ISSUE 36;
`serve/metrics.py::HostAccount`, `obs/events.py::watch_gc`) — tier-1,
CPU, at each model's `tiny()`.

What is held here:
  * every Python stretch between two runtime calls is exposed (the
    device queue stood empty, with the cause that emptied it) or
    covered; exposed stretches closed by a decode dispatch are the
    ticks that neither ran ahead nor were dispatched behind an
    admission's chunk; a first token that lands behind its tick leaves
    no `admit` stretch, one that lands at once does (ISSUE 37); exposed
    + covered fit inside the turns;
  * an idle engine and a turn that compiled count nothing;
  * a slow turn keeps its wall, Python, CPU and collector time and the
    boundaries its longest stretch lay between, the last 32 of them;
  * `watch_gc()` measures the collector and changes nothing about it;
  * with no sink and no record store nothing is written.
"""

import gc
import json
import os
import time

import numpy as np
import pytest

from singa_tpu.obs import events
from singa_tpu.serve import ServeEngine
from singa_tpu.serve import metrics as serve_metrics
from singa_tpu.serve.metrics import HostAccount

HISTS = ("serve.exposed_ms.finish", "serve.exposed_ms.admit",
         "serve.exposed_ms.other", "serve.covered_ms",
         "serve.dispatch_ms", "serve.turn_ms",
         "serve.turn_ms.admitting", "serve.tick_ahead",
         "serve.turn_gc_ms", "serve.slow_turn_ms", "py.gc_pause_ms")


def _prompts(n, lens, vocab, seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, (lens[i % len(lens)],)).astype(np.int32)
            for i in range(n)]


def _counts(prefix=""):
    return {n: (events.histogram_summary(n) or {"count": 0})["count"]
            for n in HISTS if n.startswith(prefix)}


def _warm(model, **kw):
    """An engine whose two programs have compiled, idle again."""
    eng = ServeEngine(model, num_slots=3, max_len=48, block_size=8, **kw)
    eng.submit(_prompts(1, [5], 64)[0], max_new_tokens=3)
    eng.run_until_idle()
    return eng


@pytest.fixture(scope="module", params=["llama", "moe", "zaya", "granite"])
def served(request, tiny_model):
    """(engine, the account's totals and the histograms' counts before
    and after six requests of mixed lengths, all ended by length,
    through three slots, the counters' deltas)."""
    eng = _warm(tiny_model(request.param))
    vocab = eng.model.cfg.vocab_size
    s0, c0 = eng.metrics.snapshot(), _counts()
    hs = [eng.submit(p, max_new_tokens=3 + 2 * i) for i, p in
          enumerate(_prompts(6, [5, 11, 17], vocab))]
    eng.run_until_idle()
    assert all(h.finish_reason == "length" for h in hs)
    return eng, s0, eng.metrics.snapshot(), c0, _counts()


def _delta(s0, s1, key):
    a, b = s0["host"][key], s1["host"][key]
    if isinstance(a, dict):
        return {k: b[k] - a[k] for k in a}
    return b - a


class TestStretches:
    def test_exposed_by_a_decode_dispatch_are_the_ticks_not_ahead(
            self, served):
        _, s0, s1, _, _ = served
        """A tick dispatched behind an admission's chunk closes a
        covered stretch (the chunk is on the device), ahead or not.
        Here every request ends by length, so every admitting turn finds
        nothing in flight and its tick is not ahead."""
        _, s0, s1, _, _ = served
        ticks = s1["decode_ticks"] - s0["decode_ticks"]
        ahead = s1["decode_ticks_ahead"] - s0["decode_ticks_ahead"]
        assert 0 < ahead < ticks
        behind = _delta(s0, s1, "turns_admitting")
        assert 0 < behind < ticks - ahead
        assert _delta(s0, s1, "exposed_by_decode") == ticks - ahead - behind

    def test_no_admit_stretch_a_first_token_lands_behind_its_tick(
            self, served):
        _, s0, s1, c0, c1 = served
        admitted = s1["admitted"] - s0["admitted"]
        assert admitted == 6
        assert s1["first_tokens_behind_tick"] \
            - s0["first_tokens_behind_tick"] == admitted
        assert _delta(s0, s1, "exposed_n")["admit"] == 0
        assert c1["serve.exposed_ms.admit"] == c0["serve.exposed_ms.admit"]

    def test_a_finish_stretch_each_time_a_finishing_tick_landed(
            self, served):
        """Rule 4 (docs/serving.md): a tick that ends a request by
        length lands in its own step, and the queue it leaves empty is
        called `finish`: once a finishing tick whose successor (or the
        tick after) was dispatched, not once a request (two may end on
        one tick, and the last leaves an idle engine)."""
        _, s0, s1, _, _ = served
        n = _delta(s0, s1, "exposed_n")
        assert 1 <= n["finish"] <= 5 and n["other"] == 0

    def test_exposed_and_covered_fit_inside_the_turns(self, served):
        _, s0, s1, _, _ = served
        exposed = sum(_delta(s0, s1, "exposed_s").values())
        covered, turn = _delta(s0, s1, "covered_s"), _delta(s0, s1, "turn_s")
        assert exposed > 0 and covered > 0
        assert exposed + covered <= turn
        assert 0 <= _delta(s0, s1, "exposed_caller_s") <= exposed
        # the time inside dispatch calls is the runtime's, no stretch's
        calls = _delta(s0, s1, "dispatch_s")
        assert calls > 0
        assert exposed + covered + calls <= turn * (1 + 1e-9)

    def test_each_observation_is_published_once(self, served):
        _, s0, s1, c0, c1 = served
        d = {n: c1[n] - c0[n] for n in HISTS}
        n = _delta(s0, s1, "exposed_n")
        assert [d["serve.exposed_ms." + c] for c in ("finish", "admit",
                                                     "other")] \
            == [n["finish"], n["admit"], n["other"]]
        turns = _delta(s0, s1, "turns")
        assert d["serve.turn_ms"] == turns > 0
        assert d["serve.turn_ms.admitting"] == \
            _delta(s0, s1, "turns_admitting") > 0
        ticks = s1["decode_ticks"] - s0["decode_ticks"]
        assert d["serve.tick_ahead"] == ticks
        assert d["serve.covered_ms"] == d["serve.dispatch_ms"] == \
            s1["decode_ticks_ahead"] - s0["decode_ticks_ahead"]

    def test_an_idle_engine_counts_nothing(self, served):
        eng, _, s1, _, c1 = served
        for _ in range(4):
            assert eng.step() == 0
        time.sleep(0.01)
        eng.step()
        snap = eng.metrics.snapshot()
        assert snap["host"] == s1["host"]
        assert _counts("serve.") == {n: c for n, c in c1.items()
                                     if n.startswith("serve.")}
        assert snap["steps"] == s1["steps"] + 5

    def test_a_polled_idle_engine_reads_nothing_for_the_account(
            self, served, monkeypatch):
        """No turn opens for an engine with nothing queued or running:
        neither the thread's CPU clock nor a jit-cache count is read."""
        eng = served[0]
        assert eng.metrics.host.resting and not eng.pending

        def unread(*a):
            raise AssertionError("read for an idle engine")

        monkeypatch.setattr(serve_metrics.time, "thread_time", unread)
        monkeypatch.setattr(eng, "spec_compiled_counts", unread)
        for _ in range(3):
            assert eng.step() == 0
        assert eng.metrics.host.resting

    def test_the_programs_did_not_change(self, served):
        eng = served[0]
        assert eng.compiled_counts() == (1, 1)


@pytest.fixture(scope="module")
def llama(tiny_model):
    return tiny_model("llama")


def test_a_turn_that_compiled_is_in_no_total(llama):
    """The warm-up's first dispatches compile inside `_dispatch`: that
    turn is in no total and no histogram, and the next one is."""
    eng = ServeEngine(llama, num_slots=2, max_len=32, block_size=8)
    c0 = _counts("serve.")              # the collector runs as it will
    h = eng.submit(_prompts(1, [5], 64)[0], max_new_tokens=4)
    eng.step()                          # prefill and decode compile
    assert eng.compiled_counts() == (1, 1)
    eng.step()                          # its entry ends the first turn
    host = eng.metrics.snapshot()["host"]
    assert host["turns"] == 0 and host["turn_s"] == 0.0
    assert sum(host["exposed_n"].values()) == 0
    assert _counts("serve.") == c0
    while eng.pending:
        eng.step()
    assert h.finish_reason == "length"
    host = eng.metrics.snapshot()["host"]
    # ticks 2 and 3 of three; the first, not ahead, went with its turn
    assert host["turns"] == 2 and host["exposed_by_decode"] == 0
    assert _counts()["serve.tick_ahead"] - c0["serve.tick_ahead"] == 2


def test_an_admission_beside_a_tick_in_flight_is_covered(llama):
    """A request that ends by EOS frees its slot a landing late, so its
    successor is admitted with a tick in flight.  Its chunk, the tick
    behind it (ahead of the unlanded one) and the fetch of its first
    token all meet a device with work: no `admit` stretch, and the
    exposed stretches closed by a decode dispatch are the ticks not
    ahead less those dispatched behind an admission's chunk."""
    eng = _warm(llama)
    p, q = _prompts(2, [6, 9], 64, seed=3)
    ref = eng.submit(p, max_new_tokens=8)
    eng.run_until_idle()
    eos = ref.tokens[3]
    s0 = eng.metrics.snapshot()
    h = eng.submit(p, max_new_tokens=8, eos_id=eos)
    keep = eng.submit(q, max_new_tokens=12)
    eng.step()
    eng.step()
    late = eng.submit(q[:5], max_new_tokens=3)
    for _ in range(3):
        eng.submit(q[:7], max_new_tokens=2)     # a queue behind the slots
    beside = behind_not_ahead = 0
    while eng.pending:          # one loop: `run_until_idle` starts anew
        flying, n = bool(eng._flying), eng.metrics.admitted
        eng.step()
        if eng.metrics.admitted > n:
            beside += flying
            behind_not_ahead += not flying
    assert h.finish_reason == "eos" and keep.done and late.done
    assert beside >= 1 and behind_not_ahead >= 1
    s1 = eng.metrics.snapshot()
    ticks = s1["decode_ticks"] - s0["decode_ticks"]
    ahead = s1["decode_ticks_ahead"] - s0["decode_ticks_ahead"]
    # the two steps before the loop: the first admitted, nothing flying
    assert _delta(s0, s1, "exposed_by_decode") == \
        ticks - ahead - behind_not_ahead - 1
    assert _delta(s0, s1, "exposed_n")["admit"] == 0
    assert s1["first_tokens_behind_tick"] - s0["first_tokens_behind_tick"] \
        == s1["admitted"] - s0["admitted"] == 6


def test_a_driver_that_takes_over_starts_the_clock_anew(llama):
    """Between a caller's last `step()` and `run_until_idle()` (or
    `drain()`, `close()`) the caller did something else, here for 80 ms
    with requests pending: that is no turn of either loop, no slow turn
    and no exposed stretch."""
    eng = _warm(llama)
    h = eng.submit(_prompts(1, [6], 64)[0], max_new_tokens=8)
    for _ in range(3):
        eng.step()
    before = eng.metrics.snapshot()["host"]
    time.sleep(0.08)
    eng.close()
    assert h.finish_reason == "length"
    host = eng.metrics.snapshot()["host"]
    assert host["slow_turns_total"] == 0
    assert host["turn_s"] - before["turn_s"] < 0.08
    assert max(host["exposed_s"].values()) < 0.08


# -- slow turns ------------------------------------------------------------

def _one_slow_turn(llama, on_token):
    eng = _warm(llama)
    before = eng.metrics.snapshot()["host"]
    c0 = _counts()
    fired = []

    def cb(tok, h):
        if len(h.tokens) == 3 and not fired:
            fired.append(1)
            on_token()

    eng.submit(_prompts(1, [7], 64)[0], max_new_tokens=6, on_token=cb)
    eng.run_until_idle()
    host = eng.metrics.snapshot()["host"]
    assert fired and host["slow_turns_total"] - before["slow_turns_total"] == 1
    (story,) = host["slow_turns"]
    assert sorted(story) == ["chunks", "cpu_ms", "gc_ms", "phase",
                             "python_ms", "t", "wall_ms"]
    assert story["wall_ms"] >= story["python_ms"] >= 120.0
    assert host["slow_turn_ms"] == pytest.approx(story["python_ms"] - 50.0)
    assert host["slow_turn_cpu_ms"] == story["cpu_ms"]
    # the callback runs in the landing's delivery, before step() returns
    assert story["phase"] == "decode.fetch>return"
    assert _counts()["serve.slow_turn_ms"] - c0["serve.slow_turn_ms"] == 1
    (note,) = [e for e in eng.flight.snapshot()
               if e["name"] == "serve.slow_turn"]
    assert note["phase"] == story["phase"]
    return story, c0


def test_a_turn_that_slept_was_off_the_cpu(llama):
    story, _ = _one_slow_turn(llama, lambda: time.sleep(0.12))
    assert story["cpu_ms"] < 0.5 * story["python_ms"]
    assert story["gc_ms"] == 0.0


def test_a_turn_that_spun_was_on_the_cpu(llama):
    def spin():
        end = time.thread_time() + 0.12
        while time.thread_time() < end:
            pass

    story, _ = _one_slow_turn(llama, spin)
    assert story["cpu_ms"] >= 120.0


def test_a_turn_the_collector_paused_says_so(llama):
    heap = []
    for _ in range(200_000):            # cycles a full pass has to walk
        a, b = [], []
        a.append(b), b.append(a)
        heap.append(a)

    def collect():
        gc.collect()
        time.sleep(0.06)                # the pause alone may be short

    story, c0 = _one_slow_turn(llama, collect)
    del heap
    assert story["gc_ms"] >= 1.0
    c1 = _counts()
    assert c1["py.gc_pause_ms"] > c0["py.gc_pause_ms"]
    assert c1["serve.turn_gc_ms"] == c0["serve.turn_gc_ms"] + 1


class _Clock:
    """`time` for `serve/metrics.py`, by hand."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now

    def thread_time(self):
        return self.now / 4

    def time(self):
        return 1.7e9 + self.now


def _turn(acct, clock, python_s, call_s=0.002):
    acct.step_in((1, 1, 0, 0))
    acct.call_in("decode.dispatch")
    clock.now += call_s
    acct.call_out("decode.dispatch")
    acct.tick(True)
    clock.now += python_s
    acct.step_out(None)


def test_the_list_keeps_the_last_32(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(serve_metrics, "time", clock)
    acct = HostAccount()
    for i in range(40):
        _turn(acct, clock, 0.060 + i * 0.001)
        _turn(acct, clock, 0.004)       # and an ordinary one between
    acct.step_in((1, 1, 0, 0))
    snap = acct.snapshot()
    assert snap["slow_turns_total"] == 40 and len(snap["slow_turns"]) == 32
    assert [round(s["python_ms"]) for s in snap["slow_turns"]] == \
        list(range(68, 100))
    assert snap["slow_turn_ms"] == pytest.approx(
        sum(10 + i for i in range(40)))
    assert snap["turns"] == 80
    last = snap["slow_turns"][-1]
    assert last["phase"] == "decode.dispatch>return"
    assert last["wall_ms"] == pytest.approx(101.0)
    assert last["cpu_ms"] == pytest.approx(101.0 / 4)
    assert (last["gc_ms"], last["chunks"]) == (0.0, 0)


def test_the_account_by_hand(monkeypatch):
    """Exposed and covered seconds, the caller's part, a turn left out
    and the stretch that reaches into it, on a clock moved by hand."""
    clock = _Clock()
    monkeypatch.setattr(serve_metrics, "time", clock)
    acct = HostAccount()

    def step(body, sizes=(1, 1, 0, 0), gap=0.001):
        acct.step_in(sizes)
        body()
        acct.step_out(None)
        clock.now += gap                # the caller's code

    def call(name, seconds, cause=None):
        acct.call_in(name)
        clock.now += seconds
        acct.call_out(name, cause)

    def finishing():                    # a tick ahead, then both landed
        clock.now += 0.0005
        call("decode.dispatch", 0.001)
        acct.tick(True)
        call("decode.fetch", 0.002)
        call("decode.fetch", 0.003, "finish")
        clock.now += 0.0007

    def admitting():
        clock.now += 0.0003
        call("prefill.dispatch", 0.001)
        call("prefill.dispatch", 0.001)
        call("prefill.fetch", 0.004, "admit")
        clock.now += 0.0002
        call("decode.dispatch", 0.001)
        acct.tick(False)
        clock.now += 0.0001

    step(finishing)
    step(admitting)                         # a program compiles in it,
    step(finishing, sizes=(1, 2, 0, 0))     # which the next entry shows
    step(admitting, sizes=(1, 2, 0, 0))
    acct.step_in((1, 2, 0, 0))
    snap = acct.snapshot()
    # turns 1, 3 and 4 count.  Turn 2 went, with the `finish` stretch
    # that turn 1 opened and it closed, and with its own `admit`
    assert snap["turns"] == 3 and snap["turns_admitting"] == 1
    assert snap["exposed_n"] == {"finish": 1, "admit": 1, "other": 0}
    # finish: 0.7 ms to the return, 1 ms of the caller's, 0.3 ms on
    assert snap["exposed_s"]["finish"] == pytest.approx(0.002)
    assert snap["exposed_caller_s"] == pytest.approx(0.001)
    assert snap["exposed_s"]["admit"] == pytest.approx(0.0002)
    assert snap["exposed_by_decode"] == 1
    # covered: turn 3's 0.5 ms before its tick; turn 4's 0.1 ms after
    # its tick and the caller's 1 ms; turn 1 began with a stopped clock
    assert snap["covered_s"] == pytest.approx(0.0005 + 0.0011)
    assert snap["turn_s"] == pytest.approx(2 * 0.0082 + 0.0086)
    # inside dispatch calls: two finishing turns' tick, one admitting
    # turn's two chunks and its tick
    assert snap["dispatch_s"] == pytest.approx(2 * 0.001 + 3 * 0.001)
    assert sum(snap["exposed_s"].values()) + snap["covered_s"] \
        <= snap["turn_s"]


# -- the collector ---------------------------------------------------------

def test_watch_gc_installs_one_callback_and_touches_nothing():
    threshold, enabled = gc.get_threshold(), gc.isenabled()
    events.watch_gc()
    n = len(gc.callbacks)
    events.watch_gc()
    assert len(gc.callbacks) == n
    assert gc.callbacks.count(events._on_gc) == 1
    assert (gc.get_threshold(), gc.isenabled()) == (threshold, enabled)


def test_a_pause_is_observed_and_charged_to_its_thread():
    import threading
    events.watch_gc()
    heap = []
    for _ in range(100_000):
        a, b = [], []
        a.append(b), b.append(a)
        heap.append(a)
    c0, ms0 = _counts()["py.gc_pause_ms"], events.gc_pause_ms()
    elsewhere = []
    t = threading.Thread(target=lambda: elsewhere.append(
        (gc.collect(), events.gc_pause_ms())))
    t.start(), t.join()
    assert events.gc_pause_ms() == ms0          # the other thread's
    assert elsewhere[0][1] >= 1.0
    gc.collect()
    del heap
    assert events.gc_pause_ms() >= ms0 + 1.0
    assert _counts()["py.gc_pause_ms"] >= c0 + 2
    assert events.histogram_summary("py.gc_pause_ms")["max"] >= 1.0


def test_the_call_back_notes_and_the_next_reader_observes(tmp_path):
    """Nothing is written and no lock is taken inside the collector:
    a pause's sink line appears when `gc_pause_ms()` is next read."""
    import threading
    events.watch_gc()
    heap = []
    for _ in range(100_000):
        a, b = [], []
        a.append(b), b.append(a)
        heap.append(a)
    events.gc_pause_ms()                        # whatever was pending
    path = str(tmp_path / "ev.jsonl")
    events.configure(path=path)
    try:
        plain = type(threading.Lock())
        assert type(events._hist_lock) is plain
        assert type(events.get_sink()._lock) is plain
        ms0 = events.gc_pause_ms()
        gc.collect()
        del heap
        assert len(events._gc_pending) >= 1
        assert "py.gc_pause_ms" not in open(path).read()
        assert events.gc_pause_ms() >= ms0 + 1.0
        assert not events._gc_pending
    finally:
        events.configure()
    lines = [json.loads(l) for l in open(path)]
    assert [e["generation"] for e in lines
            if e["name"] == "py.gc_pause_ms"] == [2]


def test_a_full_pass_is_a_span_of_the_profile(host_profile, tmp_path):
    events.watch_gc()
    with host_profile(tmp_path, ("py.gc",)) as lines:
        gc.collect(0)                   # a young pass has no span
        gc.collect()
    (line,) = lines
    assert [(name, stats.get("generation")) for name, _, _, stats in line] \
        in ([("py.gc", 2)], [("py.gc", "2")])
    assert line[0][2] > line[0][1]


# -- the sink, and no sink -------------------------------------------------

def test_sink_lines_carry_cause_and_chunks_after_the_dispatch(
        llama, tmp_path):
    eng = _warm(llama)
    path = str(tmp_path / "ev.jsonl")
    events.configure(path=path)
    try:
        fired = []

        def cb(tok, h):
            if len(h.tokens) == 2 and not fired:
                fired.append(time.sleep(0.06))

        for i, p in enumerate(_prompts(3, [5, 20], 64)):
            eng.submit(p, max_new_tokens=4 + 2 * i, on_token=cb)
        # a first token that ends its request lands at once, behind no
        # tick: the one `admit` stretch left (ISSUE 37)
        eng.submit(_prompts(1, [9], 64, seed=11)[0], max_new_tokens=1)
        eng.run_until_idle()
    finally:
        events.configure()
    evs = [json.loads(l) for l in open(path)]
    exposed = [e for e in evs if e["name"].startswith("serve.exposed_ms.")]
    assert {e["name"] for e in exposed} == {"serve.exposed_ms.finish",
                                            "serve.exposed_ms.admit"}
    assert [e["name"] for e in exposed].count("serve.exposed_ms.admit") == 1
    for e in exposed:
        assert e["kind"] == "hist" and e["value"] > 0
        assert e["name"].endswith("." + e["cause"])
        assert e["chunks"] >= 0 and e["by"].endswith(".dispatch")
        # written after the dispatch that ended it, not inside it
        before = evs[:evs.index(e)]
        assert any(b["kind"] == "span" and b["name"] ==
                   "serve." + e["by"] for b in before)
    assert any(e["chunks"] >= 1 for e in evs if e["name"] ==
               "serve.turn_ms.admitting")
    (slow,) = [e for e in evs if e["name"] == "serve.slow_turn"]
    assert slow["kind"] == "gauge" and slow["value"] >= 60.0
    assert slow["phase"] == "decode.fetch>return"
    assert {"wall_ms", "cpu_ms", "gc_ms", "chunks"} <= set(slow)


def test_with_no_sink_and_no_store_nothing_is_written(
        llama, tmp_path, monkeypatch):
    """The accounting, a slow turn, its flight note and a collector
    pause write no file (tests/test_faults.py holds the same of the
    rest of the engine)."""
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("HOME", str(tmp_path))
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    assert events.get_sink() is None
    eng = _warm(llama)
    assert eng.record_store is None
    fired = []

    def cb(tok, h):
        if not fired:
            fired.append(gc.collect())
            time.sleep(0.06)

    eng.submit(_prompts(1, [9], 64)[0], max_new_tokens=4, on_token=cb)
    eng.submit(_prompts(1, [5], 64)[0], max_new_tokens=3)
    eng.run_until_idle()
    host = eng.metrics.snapshot()["host"]
    assert host["slow_turns_total"] == 1 and host["turns"] > 3
    assert any(e["name"] == "serve.slow_turn"
               for e in eng.flight.snapshot())
    assert os.listdir(tmp_path) == []


def test_what_went_with_the_unread_key():
    """`accept_rate_hist` had no reader; the rate and the global
    histogram stay."""
    snap = serve_metrics.ServeMetrics().snapshot()
    assert "accept_rate_hist" not in snap
    # seconds inside fetches had no reader either: a turn keeps them
    # (its Python time is its wall less its calls), no total does
    assert "fetch_s" not in snap["host"]
    assert snap["accept_rate"] is None and "host" in snap
