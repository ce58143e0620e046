"""Continuous-batching serving engine (singa_tpu.serve, ISSUE 2;
paged KV arena + prefix sharing, ISSUE 6) — tier-1 CPU coverage on
LlamaConfig.tiny().

The invariants under test are the subsystem's contract:
  * greedy decode through the engine is token-identical to
    GenerateMixin.generate for the same prompts — including through
    chunked prefill, prefix-cache sharing and preemption;
  * exactly TWO compiled programs per (model, num_slots, max_len,
    block_size) — submitting, evicting, growing block tables and
    reusing blocks never recompiles (asserted via the jit cache size);
  * prefix-cache refcounts drain to zero, and evicting a referenced
    shared block is impossible (asserted in the pool);
  * admission control rejects loudly when the queue is full, and
    admits on free BLOCKS, not just free slots;
  * deadlines evict both queued and running requests;
  * serving metrics flow through the shared obs sink, and the
    histogram primitive's summary semantics hold.
"""

import contextlib
import json

import numpy as np
import pytest

from singa_tpu import models, tensor
from singa_tpu.obs import events
from singa_tpu.serve import QueueFull, ServeEngine
from tools.lint.hlo import assert_program_count


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
    """Shared engine for the stateless-between-runs tests (each test
    must drain it: run_until_idle leaves every slot free again)."""
    return ServeEngine(llama, num_slots=4, max_len=32, block_size=8)


def _prompts(n, lens, vocab=256, seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, (lens[i % len(lens)],)).astype(np.int32)
            for i in range(n)]


#: every phase span of an engine step (docs/observability.md); the
#: benchmark's idle metrics key on the `serve.` prefix of these names
STEP_SPANS = ("serve.expire", "serve.admit.probe", "serve.admit",
              "serve.admit.claim", "serve.prefill", "serve.prefill.stage",
              "serve.prefill.dispatch", "serve.prefill.fetch",
              "serve.admit.finish", "serve.grow", "serve.decode",
              "serve.decode.dispatch", "serve.decode.fetch",
              "serve.deliver", "serve.step.tail")


@pytest.fixture(scope="module")
def step_trace(llama, host_profile, tmp_path_factory):
    """A tiny engine stepped under a profiler session with the Python
    tracer off and NO sink, as the benchmark's traced window runs it.
    Returns the (name, start_ns, end_ns) `serve.*` and `bench.*` events
    of every host line that holds one, the `_Span`s built meanwhile,
    and the engine's program counts after."""
    import jax
    assert events.get_sink() is None
    eng = ServeEngine(llama, num_slots=2, max_len=32, block_size=8)
    eng.submit(_prompts(1, [5])[0], max_new_tokens=2)
    eng.run_until_idle()                      # compile outside the session
    built, span_cls = [], events._Span
    events._Span = lambda *a: built.append(a) or span_cls(*a)
    try:
        with host_profile(tmp_path_factory.mktemp("xprof"),
                          ("serve.", "bench.")) as lines:
            hs = [eng.submit(p, max_new_tokens=4)
                  for p in _prompts(3, [12, 5], seed=9)]
            while eng.pending:
                with jax.profiler.TraceAnnotation("bench.step"):
                    eng.step()
    finally:
        events._Span = span_cls
    assert all(h.done for h in hs)
    return ([[e[:3] for e in evs] for evs in lines], built,
            eng.compiled_counts())


class TestStepSpansInTheProfile:
    def test_one_host_line_no_span_objects_no_new_program(self, step_trace):
        lines, built, counts = step_trace
        assert len(lines) == 1      # the thread that called step()
        assert built == []          # no sink: annotations only
        assert counts == (1, 1)     # spans add no device work

    @pytest.mark.parametrize("name", STEP_SPANS)
    def test_span_lies_inside_a_serve_step(self, step_trace, name):
        (line,), _, _ = step_trace
        steps = [(s, e) for n, s, e in line if n == "serve.step"]
        mine = [(s, e) for n, s, e in line if n == name]
        assert steps and mine, f"{name} is not on the host line"
        for s, e in mine:
            assert any(s0 <= s and e <= e0 for s0, e0 in steps), name

    def test_every_step_is_inside_the_callers_annotation(self, step_trace):
        """`serve.step` nests in the benchmark's `bench.step` on the
        same line, and its direct children tile it: the names a
        midpoint can fall on."""
        (line,), _, _ = step_trace
        outer = [(s, e) for n, s, e in line if n == "bench.step"]
        steps = [(s, e) for n, s, e in line if n == "serve.step"]
        assert len(outer) == len(steps) > 0
        for s, e in steps:
            assert any(s0 <= s and e <= e0 for s0, e0 in outer)
        top = ("serve.expire", "serve.admit.probe", "serve.admit",
               "serve.grow", "serve.decode", "serve.deliver",
               "serve.step.tail")
        s0, e0 = steps[-1]          # a decode-only step: no admission
        kids = sorted((s, e) for n, s, e in line
                      if n in top and s0 <= s and e <= e0)
        assert kids[0][0] >= s0 and kids[-1][1] <= e0
        assert all(a[1] <= b[0] for a, b in zip(kids, kids[1:]))


class TestGreedyEquivalence:
    def test_single_request_matches_generate(self, llama, engine):
        prompt = _prompts(1, [8])[0]
        ref = llama.generate(prompt[None], max_new_tokens=10)[0, 8:]
        h = engine.submit(prompt, max_new_tokens=10)
        engine.run_until_idle()
        np.testing.assert_array_equal(ref, np.asarray(h.tokens))
        np.testing.assert_array_equal(
            h.result(), np.concatenate([prompt, ref]))
        # the tolerance form of the same check, used on the chip where
        # bf16 breaks bitwise identity: 0 for a greedy stream, large
        # once a token is not the arg-max
        seq = h.result()
        assert llama.greedy_margin(seq, prompt.size) == 0.0
        seq[-3] = (seq[-3] + 1) % llama.cfg.vocab_size
        assert llama.greedy_margin(seq, prompt.size) > 1e-3

    def test_mixed_lengths_concurrent_match_generate(self, llama, engine):
        """Six requests of four distinct prompt lengths decode
        concurrently (slots at different positions inside one compiled
        step) and every stream equals its sequential reference."""
        prompts = _prompts(6, [3, 5, 8, 11])
        refs = [llama.generate(p[None], max_new_tokens=9)[0, p.size:]
                for p in prompts]
        hs = [engine.submit(p, max_new_tokens=9) for p in prompts]
        engine.run_until_idle()
        for ref, h in zip(refs, hs):
            np.testing.assert_array_equal(ref, np.asarray(h.tokens))

    def test_param_dtype_bf16_matches_generate_bf16(self, llama):
        """One-time bf16 weight cast (the TPU decode configuration):
        the arena follows the cast dtype and the streams still match
        generate(param_dtype=bf16)."""
        import jax.numpy as jnp
        prompt = _prompts(1, [6], seed=11)[0]
        ref = llama.generate(prompt[None], max_new_tokens=8,
                             param_dtype=jnp.bfloat16)[0, 6:]
        eng = ServeEngine(llama, num_slots=2, max_len=24, block_size=8,
                          param_dtype=jnp.bfloat16)
        assert eng.pool.caches[0][0].dtype == jnp.bfloat16
        h = eng.submit(prompt, max_new_tokens=8)
        eng.run_until_idle()
        np.testing.assert_array_equal(ref, np.asarray(h.tokens))

    def test_gpt2_engine_matches_generate(self):
        """The engine is model-generic: GPT-2's learned-position path
        (per-row position grids in forward_cached) serves too."""
        tensor.set_seed(0)
        m = models.GPT2(models.GPT2Config.tiny())
        m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32))],
                  is_train=False, use_graph=False)
        prompts = _prompts(3, [4, 6, 9])
        refs = [m.generate(p[None], max_new_tokens=6)[0, p.size:]
                for p in prompts]
        eng = ServeEngine(m, num_slots=2, max_len=24, block_size=8)
        hs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run_until_idle()
        for ref, h in zip(refs, hs):
            np.testing.assert_array_equal(ref, np.asarray(h.tokens))


class TestCompileDiscipline:
    def test_exactly_two_programs_and_slot_reuse(self, engine, llama):
        """Mixed lengths, multiple admission waves, EOS-free slot churn:
        the jit caches must hold exactly ONE entry per program — no
        shape ever leaks into a recompile — and every slot returns to
        the free list."""
        for wave in range(2):
            hs = [engine.submit(p, max_new_tokens=5)
                  for p in _prompts(6, [2, 4, 7, 9], seed=wave)]
            engine.run_until_idle()
            assert all(h.done for h in hs)
        assert_program_count(engine, (1, 1))
        assert engine.pool.free_count == engine.pool.num_slots

    def test_eos_eviction_frees_slot_without_recompile(self, llama,
                                                       engine):
        prompt = _prompts(1, [6])[0]
        ref = llama.generate(prompt[None], max_new_tokens=8)[0, 6:]
        eos = int(ref[2])
        # the greedy stream stops at the FIRST occurrence of eos (which
        # may be earlier than index 2 if the value repeats), eos kept
        k = int(np.where(ref == eos)[0][0])
        h = engine.submit(prompt, max_new_tokens=8, eos_id=eos)
        engine.run_until_idle()
        assert h.finish_reason == "eos"
        assert h.tokens == [int(t) for t in ref[:k + 1]]
        assert engine.pool.free_count == engine.pool.num_slots
        assert_program_count(engine, (1, 1))


class TestAdmissionControl:
    def test_queue_full_rejects(self, engine):
        """The shared engine's queue (max_queue = 2*num_slots = 8) caps
        un-stepped submissions; the 9th is rejected loudly, and
        draining re-opens admission."""
        rej0, adm0 = engine.metrics.rejected, engine.metrics.admitted
        ps = _prompts(9, [4])
        for p in ps[:8]:
            engine.submit(p, max_new_tokens=3)
        with pytest.raises(QueueFull):
            engine.submit(ps[8], max_new_tokens=3)
        assert engine.metrics.rejected - rej0 == 1
        # draining the queue re-opens admission
        engine.run_until_idle()
        h = engine.submit(ps[8], max_new_tokens=3)
        engine.run_until_idle()
        assert h.done and h.finish_reason == "length"
        assert engine.metrics.admitted - adm0 == 9

    def test_oversized_requests_refused_at_the_door(self, engine, llama):
        with pytest.raises(ValueError, match="max_len"):
            engine.submit(np.zeros(10, np.int32), max_new_tokens=30)
        # the PR 2 prefill_len cap is GONE: chunked prefill serves any
        # prompt that leaves room for its token budget under max_len
        long_p = _prompts(1, [27], seed=21)[0]
        ref = llama.generate(long_p[None], max_new_tokens=5)[0, 27:]
        h = engine.submit(long_p, max_new_tokens=5)
        engine.run_until_idle()
        np.testing.assert_array_equal(ref, np.asarray(h.tokens))

    def test_deadline_evicts_queued_and_running(self, engine):
        import time
        dl0 = engine.metrics.evicted.get("deadline", 0)
        # running request whose deadline will pass mid-stream
        h_run = engine.submit(_prompts(1, [4])[0], max_new_tokens=28,
                              deadline_s=0.2)
        # queued request already expired before it can be admitted
        # (expire_queued runs BEFORE admission inside step())
        h_q = engine.submit(_prompts(1, [5], seed=9)[0], max_new_tokens=4,
                            deadline_s=-1.0)
        engine.step()                   # drops h_q, admits h_run
        assert h_q.done and h_q.finish_reason == "deadline"
        assert not h_q.tokens
        engine.step()                   # a couple of live decode ticks
        engine.step()
        time.sleep(0.25)                # ... then the deadline passes
        engine.step()                   # eviction tick
        assert h_run.done and h_run.finish_reason == "deadline"
        assert 0 < len(h_run.tokens) < 28, \
            "deadline must cut the stream short, after first tokens"
        assert engine.pool.free_count == engine.pool.num_slots
        assert engine.metrics.evicted.get("deadline", 0) - dl0 == 2

    def test_max_new_tokens_validated(self, engine):
        with pytest.raises(ValueError, match="max_new_tokens"):
            engine.submit(np.zeros(4, np.int32), max_new_tokens=0)


class TestStreamingAndMetrics:
    def test_on_token_streams_in_order(self, llama, engine):
        seen = []
        prompt = _prompts(1, [7], seed=3)[0]
        h = engine.submit(prompt, max_new_tokens=6,
                          on_token=lambda t, hd: seen.append(
                              (t, len(hd.tokens))))
        engine.run_until_idle()
        assert [t for t, _ in seen] == h.tokens
        assert [n for _, n in seen] == list(range(1, 7))

    def test_obs_sink_carries_serve_events(self, engine, tmp_path):
        path = str(tmp_path / "serve_events.jsonl")
        events.configure(path=path)
        try:
            hs = [engine.submit(p, max_new_tokens=4)
                  for p in _prompts(3, [4, 6])]
            engine.run_until_idle()
        finally:
            events.configure()          # disable; close the sink
        assert all(h.done for h in hs)
        evs = [json.loads(l) for l in open(path)]
        names = {(e["kind"], e["name"]) for e in evs}
        for expected in (("counter", "serve.submitted"),
                         ("counter", "serve.admitted"),
                         ("counter", "serve.evicted"),
                         ("gauge", "serve.queue_depth"),
                         ("gauge", "serve.active_slots"),
                         ("span", "serve.step"),
                         ("span", "serve.prefill"),
                         ("span", "serve.decode"),
                         ("hist", "serve.ttft_ms"),
                         ("hist", "serve.token_ms")):
            assert expected in names, f"missing {expected} in {names}"

    def test_sink_lines_of_the_step_spans(self, llama, monkeypatch,
                                          tmp_path):
        """With a sink every phase span of a step is a JSONL line with
        `dur_ms`; the spans of one admission carry the request's trace
        id and nest under its `serve.admit` by `span`/`parent`, but for
        the fetch of its first token, which comes behind the step's
        tick (ISSUE 37): under the request's trace, under no admission."""
        from singa_tpu.serve import engine as engine_mod
        # one block a chunk, so that a prompt under max_len is three
        monkeypatch.setattr(engine_mod, "_PREFILL_ROWS", 8)
        engine = ServeEngine(llama, num_slots=2, max_len=32, block_size=8)
        path = str(tmp_path / "serve_spans.jsonl")
        events.configure(path=path)
        try:
            h = engine.submit(_prompts(1, [20], seed=5)[0],
                              max_new_tokens=3)
            engine.run_until_idle()
        finally:
            events.configure()
        spans = [json.loads(l) for l in open(path)]
        spans = [e for e in spans if e["kind"] == "span"]
        assert {e["name"] for e in spans} >= set(STEP_SPANS)
        assert all(e["dur_ms"] >= 0 for e in spans)
        (admit,) = [e for e in spans if e["name"] == "serve.admit"]
        assert admit["trace"] == h.trace_id and "parent" not in admit
        by_id = {e["span"]: e for e in spans if "span" in e}
        for e in spans:
            if e["name"].startswith(("serve.admit.", "serve.prefill")) \
                    and e["name"] != "serve.admit.probe":
                assert e["trace"] == h.trace_id
                top = e
                while "parent" in top:
                    top = by_id[top["parent"]]
                if e["name"] != "serve.prefill.fetch":
                    assert top is admit, e
        # a 20-token prompt in chunks of 8: three chunks, one fetch
        names = [e["name"] for e in spans]
        assert names.count("serve.prefill.stage") == 3
        assert names.count("serve.prefill.dispatch") == 3
        assert names.count("serve.prefill.fetch") == 1
        # a span's line is written when it closes: the tick's dispatch
        # closed before the fetch of the first token opened, and its
        # delivery has a `serve.deliver` of its own under its trace
        assert names.index("serve.decode.dispatch") \
            < names.index("serve.prefill.fetch") \
            < names.index("serve.decode.fetch")
        assert [e["trace"] for e in spans if e["name"] == "serve.deliver"
                and "trace" in e] == [h.trace_id]
        (prefill,) = [e for e in spans if e["name"] == "serve.prefill"]
        assert (prefill["prompt"], prefill["shared"],
                prefill["chunks"]) == (20, 0, 3)

    def test_snapshot_counts(self, engine):
        from singa_tpu.serve.metrics import ServeMetrics
        engine.metrics = ServeMetrics()   # fresh totals + histograms
        hs = [engine.submit(p, max_new_tokens=3) for p in _prompts(2, [4])]
        engine.run_until_idle()
        assert all(h.done for h in hs)
        snap = engine.metrics.snapshot()
        assert snap["submitted"] == 2
        assert snap["evicted"] == {"length": 2}
        assert snap["ttft_ms"]["count"] == 2
        assert snap["token_ms"]["count"] == 4   # 2 reqs x 2 decode tokens
        # a 4-token prompt is one chunk that fills 4 of its rows
        assert (snap["prefill_chunks"], snap["prefill_chunk_rows"]) == (2, 8)


class TestPrefixSharing:
    """ISSUE 6 satellite: prefix-cache sharing correctness — streams
    token-identical to independent generate() calls, refcounts drain
    to zero, and a referenced shared block can never be evicted."""

    def _shared_prompts(self, n_suffixes=2, prefix_len=19, seed=3):
        rng = np.random.RandomState(seed)
        sysp = rng.randint(0, 256, (prefix_len,)).astype(np.int32)
        sufs = [rng.randint(0, 256, (4 + 3 * i,)).astype(np.int32)
                for i in range(n_suffixes)]
        return [np.concatenate([sysp, s]) for s in sufs]

    def test_divergent_suffixes_match_independent_generate(self, llama,
                                                           engine):
        """Two requests share a 19-token system prompt (2 full blocks
        at block_size 8) with divergent suffixes, CONCURRENTLY: the
        second maps the first's prompt blocks copy-free (visible in
        serve.prefix_hit_tokens) and both streams equal their
        independent generate() references."""
        prompts = self._shared_prompts()
        refs = [llama.generate(p[None], max_new_tokens=6)[0, p.size:]
                for p in prompts]
        hits0 = engine.metrics.prefix_hit_tokens
        hs = [engine.submit(p, max_new_tokens=6) for p in prompts]
        engine.run_until_idle()
        for ref, h in zip(refs, hs):
            np.testing.assert_array_equal(ref, np.asarray(h.tokens))
        # the second admission skipped its 2 shared prompt blocks
        assert engine.metrics.prefix_hit_tokens - hits0 == 16
        assert_program_count(engine, (1, 1))

    def test_refcounts_drain_to_zero_after_both_finish(self, llama,
                                                       engine):
        prompts = self._shared_prompts(seed=13)
        hs = [engine.submit(p, max_new_tokens=5) for p in prompts]
        engine.step()               # both running: shared blocks ref=2
        shared = [b for b in range(engine.pool.num_blocks)
                  if engine.pool.ref[b] > 1]
        assert shared, "no block was actually shared while both ran"
        engine.run_until_idle()
        assert all(h.done for h in hs)
        assert (engine.pool.ref == 0).all()
        # content survives refcount-0 (evictable, reusable): a third
        # request with the same prefix still hits
        h3 = engine.submit(prompts[0], max_new_tokens=3)
        engine.run_until_idle()
        assert h3.done
        assert engine.metrics.prefix_hits >= 1

    def test_evicting_referenced_block_is_impossible(self, llama,
                                                     engine):
        """The pool's core invariant, asserted at the eviction site: a
        block any request still references can never be reclaimed —
        even if it is (wrongly) offered to the LRU."""
        h = engine.submit(self._shared_prompts(seed=17)[0],
                          max_new_tokens=6)
        engine.step()               # running: its blocks have ref >= 1
        pool = engine.pool
        held = next(b for b in range(pool.num_blocks) if pool.ref[b] > 0)
        pool._lru[held] = None      # corrupt: evictable-while-referenced
        taken = []
        with pytest.raises(AssertionError, match="refcount"):
            while True:             # drain the free list into the evictor
                got = pool.alloc_blocks(1)
                assert got is not None
                taken.append(got[0])
        pool.free_blocks(taken)     # restore the shared engine's pool
        # the refused eviction must not have freed the referenced block
        assert held not in pool._lru
        assert pool.ref[held] >= 1
        engine.run_until_idle()
        assert h.done

    def test_share_prefix_off_never_hits(self, llama):
        eng = ServeEngine(llama, num_slots=2, max_len=32, block_size=8,
                          share_prefix=False)
        prompts = self._shared_prompts(seed=23)
        refs = [llama.generate(p[None], max_new_tokens=4)[0, p.size:]
                for p in prompts]
        hs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.run_until_idle()
        for ref, h in zip(refs, hs):
            np.testing.assert_array_equal(ref, np.asarray(h.tokens))
        assert eng.metrics.prefix_hits == 0
        assert eng.metrics.prefix_hit_tokens == 0


class TestPagedArena:
    """Admission counts free blocks (not slots), decode grows block
    tables in place, and an exhausted pool preempts — never corrupts —
    a stream."""

    def test_admission_defers_until_blocks_free(self, llama):
        """9 slot rows but only enough physical blocks for two 23-token
        prompts: the third request waits for BLOCKS even though 7 slot
        rows are free, then completes correctly once blocks release."""
        eng = ServeEngine(llama, num_slots=9, max_len=32, block_size=8,
                          num_blocks=9)      # 8 usable blocks
        prompts = _prompts(3, [23], seed=31)
        refs = [llama.generate(p[None], max_new_tokens=9)[0, 23:]
                for p in prompts]
        hs = [eng.submit(p, max_new_tokens=9) for p in prompts]
        eng.step()
        # each prompt needs 3 blocks at admission: two admit (6 of 8
        # blocks), the third defers on blocks, not slots
        assert eng.pool.active_count == 2
        assert eng.pool.free_count == 7
        eng.run_until_idle()
        for ref, h in zip(refs, hs):
            np.testing.assert_array_equal(ref, np.asarray(h.tokens))
        assert_program_count(eng, (1, 1))
        assert (eng.pool.ref == 0).all()

    def test_preemption_keeps_streams_bit_identical(self, llama):
        """Both requests outgrow the pool mid-decode: the youngest is
        preempted (blocks released, requeued at the head, replayed)
        and every stream still equals its reference."""
        eng = ServeEngine(llama, num_slots=2, max_len=32, block_size=8,
                          num_blocks=6)      # 5 usable blocks
        prompts = _prompts(2, [7], seed=37)
        refs = [llama.generate(p[None], max_new_tokens=16)[0, 7:]
                for p in prompts]
        hs = [eng.submit(p, max_new_tokens=16) for p in prompts]
        eng.run_until_idle()
        for ref, h in zip(refs, hs):
            np.testing.assert_array_equal(ref, np.asarray(h.tokens))
        assert eng.metrics.preempted >= 1
        assert_program_count(eng, (1, 1))


#: the prefill chunk of the tests below, and a `max_len` of three
CH, VIEW = 256, 768


@pytest.fixture(scope="module")
def wide():
    """LlamaConfig.tiny() with room for three whole chunks: at
    block_size 8 a prefill chunk is 256 tokens = 32 blocks."""
    import dataclasses
    tensor.set_seed(0)
    m = models.Llama(dataclasses.replace(models.LlamaConfig.tiny(),
                                         max_position=VIEW))
    m.eval()
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32))],
              is_train=False, use_graph=False)
    return m


@pytest.fixture(scope="module")
def wide_engine(wide):
    eng = ServeEngine(wide, num_slots=3, max_len=VIEW, block_size=8)
    assert eng.programs().chunk == CH
    return eng


def _toks(n, seed):
    return np.random.RandomState(seed).randint(0, 256, (n,)).astype(np.int32)


def _serve_one(eng, model, prompt, new):
    """Serve `prompt` alone; its stream must be generate()'s.  Returns
    the chunks dispatched and the rows they filled."""
    before = eng.metrics.snapshot()
    h = eng.submit(prompt, max_new_tokens=new)
    eng.run_until_idle()
    want = model.generate(prompt[None], max_new_tokens=new)[0, prompt.size:]
    np.testing.assert_array_equal(want, np.asarray(h.tokens))
    snap = eng.metrics.snapshot()
    return (snap["prefill_chunks"] - before["prefill_chunks"],
            snap["prefill_chunk_rows"] - before["prefill_chunk_rows"])


def _slot_of(eng, handle):
    return next(s for s, r in eng.running_items() if r.rid == handle.rid)


def _shared_blocks(eng, n):
    """Bytes of physical blocks `n` (a list of ids), every layer."""
    return [np.asarray(c)[n] for kv in eng.pool.caches for c in kv]


def _prompt_of(P, new):
    def case(model, eng):
        assert _serve_one(eng, model, _toks(P, P), new) == (-(-P // CH), P)
    return case


def _prefix_hit_off_the_grid(model, eng):
    """A hit of 40 tokens (five blocks, no multiple of the chunk) under
    a prompt that reaches into the last chunk of `max_len`: the chunks
    start at 40, 296 and 552, and [552, 808) would cross 768, where
    `dynamic_update_slice` clamps and writes K/V 40 positions early.
    The engine starts that chunk at 512 and writes from 552 on."""
    shared = _toks(40, 1)
    _serve_one(eng, model, np.concatenate([shared, _toks(10, 2)]), 2)
    hit0 = eng.metrics.prefix_hit_tokens
    got = _serve_one(eng, model, np.concatenate([shared, _toks(714, 3)]), 8)
    assert eng.metrics.prefix_hit_tokens - hit0 == 40
    assert got == (3, 714)


def _shared_blocks_are_not_rewritten(model, eng):
    """A hit of 552 tokens under a 626-token prompt: the only chunk
    starts at 512 and recomputes 40 shared tokens, whose five blocks
    the scatter sends to the null block.  The 69 shared blocks hold
    the same bytes after as before."""
    shared = _toks(552, 4)
    first = eng.submit(np.concatenate([shared, _toks(6, 5)]),
                       max_new_tokens=40)
    eng.step()                      # the first request stays in its slot
    ids = eng.pool.mapped_blocks(_slot_of(eng, first))[:69]
    before = _shared_blocks(eng, ids)
    hit0 = eng.metrics.prefix_hit_tokens
    second = np.concatenate([shared, _toks(74, 6)])
    h = eng.submit(second, max_new_tokens=8)
    eng.step()
    assert eng.metrics.prefix_hit_tokens - hit0 == 552
    assert (eng.pool.ref[ids] == 2).all()
    for was, now in zip(before, _shared_blocks(eng, ids)):
        np.testing.assert_array_equal(was, now)
    eng.run_until_idle()
    want = model.generate(second[None], max_new_tokens=8)[0, second.size:]
    np.testing.assert_array_equal(want, np.asarray(h.tokens))


def _int8_arena(model, eng):
    """The first layer's K and V depend on nothing cached, so an int8
    arena must hold the codes and scales of what the f32 arena holds,
    position by position, through every block of three chunks (to an
    ulp: the program fuses the division by the scale)."""
    from singa_tpu.ops import kv_cache as kv_ops
    q8 = ServeEngine(model, num_slots=1, max_len=VIEW, block_size=8,
                     kv_dtype="int8")
    prompt = _toks(600, 7)
    views = []
    for e in (eng, q8):
        h = e.submit(prompt, max_new_tokens=40)
        e.step()
        row = e.pool.tables[_slot_of(e, h)][None]
        views.append(kv_ops.gather_block_kv(*e.pool.caches[0], row))
        e.run_until_idle()
        assert h.finish_reason == "length"
    assert_program_count(q8, (1, 1))
    for full, quant in zip(*views):
        code, scale = kv_ops.quantize_kv(full[0, :600])
        np.testing.assert_allclose(
            np.asarray(quant[0, :600]),
            np.asarray(kv_ops.dequantize_kv(code, scale)), rtol=1e-6, atol=0)


def _speculative_engine(model, eng):
    spec = ServeEngine(model, num_slots=2, max_len=VIEW, block_size=8,
                       draft_model=model, spec_k=2)
    shared = _toks(40, 8)
    _serve_one(spec, model, np.concatenate([shared, _toks(10, 9)]), 3)
    assert _serve_one(spec, model,
                      np.concatenate([shared, _toks(714, 10)]), 8) == (3, 714)
    assert spec.spec_compiled_counts()[0] == 1


def _preempted_replay(model, eng):
    """Two 362-token prompts (two chunks, 46 blocks each) outgrow a
    pool of 96 blocks mid-decode: the youngest replays prompt + tokens
    so far."""
    small = ServeEngine(model, num_slots=2, max_len=VIEW, block_size=8,
                        num_blocks=97)
    prompts = [_toks(362, 11), _toks(362, 12)]
    hs = [small.submit(p, max_new_tokens=30) for p in prompts]
    small.run_until_idle()
    assert small.metrics.preempted >= 1
    for p, h in zip(prompts, hs):
        want = model.generate(p[None], max_new_tokens=30)[0, p.size:]
        np.testing.assert_array_equal(want, np.asarray(h.tokens))
    assert_program_count(small, (1, 1))


class TestChunkOfSeveralBlocks:
    """A prefill dispatch covers `C` = 256 tokens, 32 blocks at
    block_size 8 (ISSUE 29): every stream stays generate()'s, bit for
    bit, wherever a prompt ends inside a chunk and wherever a chunk
    starts; resident blocks are never rewritten."""

    CASES = {
        "shorter_than_a_block": _prompt_of(5, 4),
        "not_a_multiple_of_a_block": _prompt_of(29, 4),
        "exactly_one_chunk": _prompt_of(CH, 4),
        "one_chunk_and_a_token": _prompt_of(CH + 1, 4),
        "three_chunks": _prompt_of(2 * CH + 44, 4),
        "ends_in_the_last_chunk_of_max_len": _prompt_of(VIEW - 6, 5),
        "prefix_hit_off_the_grid": _prefix_hit_off_the_grid,
        "shared_blocks_are_not_rewritten": _shared_blocks_are_not_rewritten,
        "int8_arena": _int8_arena,
        "speculative_engine": _speculative_engine,
        "preempted_replay": _preempted_replay,
    }

    @pytest.mark.parametrize("case", list(CASES))
    def test_streams_are_generates(self, wide, wide_engine, case):
        self.CASES[case](wide, wide_engine)
        assert wide_engine.pending == 0
        assert (wide_engine.pool.ref == 0).all()
        assert_program_count(wide_engine, (1, 1))

    @pytest.mark.parametrize("kind", ["float32", "int8"])
    def test_scatter_leaves_resident_and_unmapped_blocks_alone(self, kind):
        """`scatter_chunk` alone, a chunk of three blocks at position 16
        of a row that maps four: the block below `fresh` (resident) and
        the one past the mapped part go to the null block; only the one
        between is written, with its own rows of the view."""
        import jax.numpy as jnp
        from singa_tpu.ops import kv_cache as kv_ops
        from singa_tpu.serve import spec
        ck, cv = _arena(kind)
        row = jnp.asarray([[3, 1, 2, 4, 0, 0]], jnp.int32)
        rng = np.random.RandomState(1)
        dk, dv = (jnp.asarray(rng.randn(1, 48, *_ARENA[2:]), jnp.float32)
                  for _ in range(2))
        (nk, nv), = spec.scatter_chunk(row, 16, 24, [(ck, cv)], [(dk, dv)],
                                       block_size=8, chunk=24)
        for new, old, view in ((nk, ck, dk), (nv, cv, dv)):
            want = view[0, 24:32]
            if kind == "int8":
                want = kv_ops.dequantize_kv(*kv_ops.quantize_kv(want))
                new, old = (kv_ops.dequantize_kv(c.q, c.scale)
                            for c in (new, old))
            np.testing.assert_array_equal(np.asarray(new[4]),
                                          np.asarray(want))
            np.testing.assert_array_equal(np.asarray(new[1:4]),
                                          np.asarray(old[1:4]))

    def test_shared_programs_refuse_another_chunk(self, llama, engine):
        """`max_len` 32 caps the chunk at 32 tokens, `max_len` 128 at
        128: the closures bake the chunk in."""
        assert engine.programs().chunk == 32
        with pytest.raises(ValueError, match="prefill chunk"):
            ServeEngine(llama, num_slots=2, max_len=128, block_size=8,
                        programs=engine.programs())


#: (num_blocks, block_size, K, D) of the helper test's tiny arena
_ARENA = (5, 8, 2, 4)


def _arena(kind):
    """One (ck, cv) paged arena of random content: a plain bf16 or f32
    pool, or a QuantKV one (int8 codes + per-position f32 scales)."""
    import jax.numpy as jnp
    from singa_tpu.ops import kv_cache as kv_ops
    rng = np.random.RandomState(0)

    def pool():
        if kind == "int8":
            return kv_ops.QuantKV(
                jnp.asarray(rng.randint(-127, 128, _ARENA), jnp.int8),
                jnp.asarray(rng.rand(*_ARENA[:2], 1, 1) + 0.5, jnp.float32))
        return jnp.asarray(rng.randn(*_ARENA), kind)
    return pool(), pool()


class TestPagedGatherHasNoFill:
    """`gather_block_kv` lowers to the gather alone (ISSUE 27): no
    out-of-bounds fill — `jnp.take`'s default mode selects NaN over the
    whole dense view, a second pass over a max_len-sized buffer in
    every serve program — and the tables it is handed only ever hold
    valid block ids, so nothing needs one."""

    @pytest.mark.parametrize("kind", ["bfloat16", "float32", "int8"])
    def test_helper_is_a_bare_gather_equal_to_indexing(self, kind):
        import jax
        from singa_tpu.ops import kv_cache as kv_ops
        ck, cv = _arena(kind)
        # repeated ids, the null block 0 and the highest id
        t = np.asarray([[4, 0, 2], [2, 2, 4]], np.int32)
        assert t.max() == _ARENA[0] - 1
        jaxpr = str(jax.make_jaxpr(kv_ops.gather_block_kv)(ck, cv, t))
        assert "gather" in jaxpr
        assert "select_n" not in jaxpr and "FILL_OR_DROP" not in jaxpr

        def plain(c):
            if kind == "int8":
                c = (np.asarray(c.q)[t].astype(np.float32)
                     * np.asarray(c.scale)[t])
            else:
                c = np.asarray(c)[t]
            return c.reshape((t.shape[0], -1) + c.shape[3:])
        for got, c in zip(kv_ops.gather_block_kv(ck, cv, t), (ck, cv)):
            want = plain(c)
            assert got.dtype == want.dtype and got.shape == want.shape
            np.testing.assert_array_equal(np.asarray(got), want)

    @pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
    def test_program_holds_no_select_over_the_view(self, engine, program):
        """The lowered serve programs: no `select` whose result has the
        gathered view's shape (decode gathers every slot's row, a
        prefill chunk one slot's)."""
        rows = engine.pool.num_slots if program == "decode" else 1
        ck = engine.pool.caches[0][0]
        view = "x".join(map(str, (rows * engine.pool.max_blocks,)
                            + tuple(ck.shape[1:])))
        text = engine.lower_programs(names=(program,))[program].as_text()
        assert f"tensor<{view}x" in text     # the view itself is there
        bad = [ln.strip() for ln in text.splitlines()
               if "stablehlo.select" in ln and f"tensor<{view}x" in ln]
        assert bad == []

    def test_tables_only_ever_hold_valid_block_ids(self, llama):
        """The invariant `gather_block_kv` rests on, after every step
        of a run with admissions, block growth, a preemption and
        evictions: each table entry lies in [0, num_blocks)."""
        eng = ServeEngine(llama, num_slots=2, max_len=32, block_size=8,
                          num_blocks=6)      # 5 usable blocks
        hs = [eng.submit(p, max_new_tokens=16)
              for p in _prompts(3, [7, 12], seed=37)]
        seen = set()
        while eng.pending:
            eng.step()
            t = np.asarray(eng.pool.tables)
            assert ((0 <= t) & (t < eng.pool.num_blocks)).all()
            seen.update(t.ravel().tolist())
        assert all(h.finish_reason == "length" for h in hs)
        assert eng.metrics.preempted >= 1
        # the run reached the null block and the highest id
        assert {0, eng.pool.num_blocks - 1} <= seen


def _read_blocks_model(kind):
    """A tiny model whose arena the paged kernel tiles (heads of 128,
    two KV heads): a Llama that mixes sliding and full layers, or a
    Zaya with its CCA state beside the KV blocks."""
    import dataclasses
    tensor.set_seed(0)
    if kind == "llama":
        m = models.Llama(dataclasses.replace(
            models.LlamaConfig.tiny(), num_layers=2, num_heads=4,
            num_kv_heads=2, head_size=128, sliding_window=12,
            layer_types=("sliding_attention", "full_attention")))
    else:
        m = models.Zaya(dataclasses.replace(models.ZayaConfig.tiny(),
                                            head_size=128))
    m.eval()
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32))],
              is_train=False, use_graph=False)
    return m


@contextlib.contextmanager
def _as_on_a_tpu():
    """What a TPU gets: the platform probe of `ops.kv_cache` steered
    from the test while a decode program traces (the kernel itself
    still sees the CPU and runs in interpret mode).  The program has no
    option for it."""
    from singa_tpu.ops import kv_cache as kv_ops
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kv_ops, "on_tpu", lambda: True)
        yield


@pytest.fixture(scope="module", params=["llama", "zaya"])
def block_readers(request):
    """(model, an engine on the gathered view, an engine whose decode
    reads blocks through the table), each with its two programs traced
    by a first request: a program is traced once, so the probe is only
    steered here and where a test lowers the program again."""
    from singa_tpu.ops import kv_cache as kv_ops
    model = _read_blocks_model(request.param)
    kw = dict(num_slots=3, max_len=64, block_size=8)
    view, paged = ServeEngine(model, **kw), ServeEngine(model, **kw)
    assert not kv_ops.reads_blocks(paged.pool.caches[0][0])
    view.submit(_toks(5, 1), max_new_tokens=3)
    view.run_until_idle()
    with _as_on_a_tpu():
        assert kv_ops.reads_blocks(paged.pool.caches[0][0])
        paged.submit(_toks(5, 1), max_new_tokens=3)
        paged.run_until_idle()
    return model, view, paged


def _view_shapes(eng):
    """The dense view of every slot's table row, before and after its
    reshape, as MLIR tensor types."""
    pool, ck = eng.pool, eng.pool.caches[0][0]
    rest = "x".join(map(str, ck.shape[2:]))
    return (f"tensor<{pool.num_slots * pool.max_blocks}x{ck.shape[1]}x{rest}x",
            f"tensor<{pool.num_slots}x{pool.max_blocks * ck.shape[1]}x{rest}x")


class TestDecodeReadsBlocks:
    """`decode_paged` hands the model the arena, not a view (ISSUE 33):
    the token goes into the pool first and `ops.paged_attention` reads
    each slot's blocks through its table row, up to its length and
    inside the layer's window."""

    def test_streams_equal_the_view_paths(self, block_readers):
        """Five requests over three slots, a shared 16-token prefix:
        prefix hits, tables that grow block by block, slots released
        and admitted into again; sliding and full layers (or CCA side
        state) in one program."""
        _, view, paged = block_readers
        rng = np.random.RandomState(3)
        shared = rng.randint(0, 256, (16,)).astype(np.int32)
        prompts = [np.concatenate(
            [shared, rng.randint(0, 256, (n,)).astype(np.int32)])
            for n in (5, 12, 3, 20, 9)]
        streams = []
        for eng in (view, paged):
            hs = [eng.submit(p, max_new_tokens=10 + i)
                  for i, p in enumerate(prompts)]
            eng.run_until_idle()
            assert all(h.finish_reason == "length" for h in hs)
            assert eng.metrics.snapshot()["prefix_hits"] >= 2
            assert_program_count(eng, (1, 1))
            assert (eng.pool.ref == 0).all()
            streams.append([list(h.tokens) for h in hs])
        assert streams[0] == streams[1]

    def test_lowered_decode_holds_no_view(self, block_readers):
        _, view, paged = block_readers

        def text(eng, name):
            return eng.lower_programs(names=(name,))[name].as_text()
        with _as_on_a_tpu():
            lowered = {paged: text(paged, "decode")}
            prefill = text(paged, "prefill_chunk")
        lowered[view] = text(view, "decode")
        for shape in _view_shapes(view):
            assert shape in lowered[view]       # what is looked for exists
            assert shape not in lowered[paged]
        # prefill still gathers one slot's view, on either engine
        row = _view_shapes(paged)[1].replace(
            f"<{paged.pool.num_slots}x", "<1x")
        assert row in prefill

    def test_counters_add_up(self, block_readers):
        """One request alone: a prompt of 13 and 12 new tokens at block
        size 8.  Prefill gives the first; the eleven ticks write
        positions 13..23, and by hand 13-15 lie in the second block,
        16-23 in the third."""
        _, _, paged = block_readers
        before = paged.metrics.snapshot()
        h = paged.submit(_toks(13, 13), max_new_tokens=12)
        paged.run_until_idle()
        assert len(h.tokens) == 12
        snap = paged.metrics.snapshot()
        live = snap["decode_kv_blocks_live"] - before["decode_kv_blocks_live"]
        span = snap["decode_kv_blocks_view"] - before["decode_kv_blocks_view"]
        assert live == 3 * 2 + 8 * 3
        assert span == 11 * paged.pool.num_slots * paged.pool.max_blocks
        assert 0 < live <= span


def _engine_of(kind, model, **kw):
    """A plain engine, or one that speculates on itself (every proposal
    accepted: a verify round moves a slot by ``spec_k + 1``)."""
    if kind == "speculative":
        kw.update(draft_model=model, spec_k=2)
    return ServeEngine(model, **kw)


def _check_slot_state(eng):
    """The slot state is the host's, and says what the host knows: a
    row is the slot's mapped blocks over null blocks, ``pos`` the
    positions its cache holds (the last token delivered is the next
    dispatch's input, not cached yet), ``active`` whether a request
    runs there."""
    pool, running = eng.pool, dict(eng.running_items())
    for arr, dtype in ((pool.tables, np.int32), (pool.pos, np.int32),
                       (pool.active, np.bool_)):
        assert type(arr) is np.ndarray and arr.dtype == dtype
    for slot in range(pool.num_slots):
        req = running.get(slot)
        row = np.zeros((pool.max_blocks,), np.int32)
        mapped = pool.mapped_blocks(slot)
        row[:len(mapped)] = mapped
        np.testing.assert_array_equal(pool.tables[slot], row)
        assert pool.active[slot] == (req is not None)
        assert pool.pos[slot] == (
            0 if req is None else req.prompt.size + len(req.tokens) - 1)


class TestSlotStateOnTheHost:
    """ISSUE 31: `BlockPool.tables` / `pos` / `active` are numpy the
    host edits and hands to the programs; a step's device work is its
    dispatches and one fetch."""

    @pytest.mark.parametrize("event", ["preemption", "recover"])
    @pytest.mark.parametrize("kind", ["plain", "speculative"])
    def test_state_follows_the_requests_after_every_step(self, llama,
                                                         kind, event):
        """Admissions, block growth, finishes of unequal lengths and a
        preemption (the pool is two blocks short) or a `recover()`
        mid-run: after each step the three arrays are what the
        requests say, and the streams are `generate()`'s."""
        eng = _engine_of(kind, llama, num_slots=2, max_len=32,
                         block_size=8,
                         num_blocks=6 if event == "preemption" else None)
        prompts = _prompts(3, [7, 7, 5], seed=37)
        new = [16, 14, 9]
        refs = [llama.generate(p[None], max_new_tokens=n)[0, p.size:]
                for p, n in zip(prompts, new)]
        hs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
        steps = 0
        while eng.pending:
            eng.step()
            _check_slot_state(eng)
            steps += 1
            if event == "recover" and steps == 3:
                eng.recover("test")
                _check_slot_state(eng)
                assert not eng.pool.active.any()
        for ref, h in zip(refs, hs):
            np.testing.assert_array_equal(ref, np.asarray(h.tokens))
        if event == "preemption":
            assert eng.metrics.preempted >= 1
        else:
            assert eng.metrics.recoveries == 1
        assert not eng.pool.tables.any() and not eng.pool.pos.any()

    @pytest.mark.parametrize("kind", ["plain", "speculative"])
    def test_a_dispatch_in_flight_never_sees_later_edits(self, llama, kind):
        """A dispatch returns before its program ran, and the CPU
        backend reads a 64-byte-aligned numpy argument in place: so a
        program must get arrays nobody edits.  Here every dispatch is
        followed at once by the worst the next tick could store into
        the pool's arrays (all of them, aligned for the purpose), and
        the streams are still `generate()`'s."""
        import jax
        eng = _engine_of(kind, llama, num_slots=2, max_len=32, block_size=8)
        pool = eng.pool
        live = {}
        for name in ("tables", "pos", "active"):
            arr = getattr(pool, name)
            raw = np.zeros((arr.nbytes + 64,), np.uint8)
            off = -raw.ctypes.data % 64
            live[name] = raw[off:off + arr.nbytes].view(arr.dtype) \
                .reshape(arr.shape)
            assert live[name].ctypes.data % 64 == 0
            setattr(pool, name, live[name])

        def edited_behind(program):
            def dispatch(*args):
                for a in args:
                    if isinstance(a, np.ndarray):
                        assert not any(np.shares_memory(a, b)
                                       for b in live.values())
                out = program(*args)
                kept = {n: a.copy() for n, a in live.items()}
                live["tables"][:] = pool.num_blocks - 1
                live["pos"][:] = 3
                live["active"][:] = ~kept["active"]
                jax.block_until_ready(out)
                for n, a in kept.items():
                    live[n][:] = a
                return out
            return dispatch

        eng._prefill = edited_behind(eng._prefill)
        eng._decode = edited_behind(eng._decode)
        if eng._verify is not None:
            eng._verify = edited_behind(eng._verify)
        prompts = _prompts(3, [7, 12, 5], seed=41)
        refs = [llama.generate(p[None], max_new_tokens=12)[0, p.size:]
                for p in prompts]
        hs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        while eng.pending:
            eng.step()      # admits, then grows, right behind a dispatch
            _check_slot_state(eng)
        for ref, h in zip(refs, hs):
            np.testing.assert_array_equal(ref, np.asarray(h.tokens))

    @pytest.mark.parametrize("kind,programs", [
        ("plain", {"jit(prefill_chunk)", "jit(decode_paged)"}),
        ("speculative", {"jit(prefill_chunk_spec)", "jit(verify)"})])
    def test_a_step_compiles_its_programs_and_nothing_else(self, llama,
                                                           kind, programs):
        """With every cache of jax cleared, an admission with a prefix
        hit, ticks across a block boundary and a finish build the
        engine's programs and no other: no `scatter`,
        `convert_element_type` or `squeeze` of an eager
        `x.at[slot].set(...)`, no staging of an argument."""
        import jax
        from jax import monitoring
        eng = _engine_of(kind, llama, num_slots=2, max_len=32, block_size=8)
        shared = _prompts(1, [8], seed=43)[0]
        first, second = (np.concatenate([shared, t])
                         for t in _prompts(2, [3, 5], seed=44))
        ref = llama.generate(second[None], max_new_tokens=10)[0, second.size:]
        eng.submit(first, max_new_tokens=2)
        eng.run_until_idle()            # the shared block is keyed now
        built = []

        def on_compile(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                built.append(kw.get("fun_name"))

        jax.clear_caches()
        monitoring.register_event_duration_secs_listener(on_compile)
        try:
            hits = eng.metrics.snapshot()["prefix_hit_tokens"]
            h = eng.submit(second, max_new_tokens=10)
            eng.run_until_idle()        # positions 13..22 pass 16
        finally:
            monitoring.unregister_event_duration_listener(on_compile)
        assert eng.metrics.snapshot()["prefix_hit_tokens"] == hits + 8
        np.testing.assert_array_equal(ref, np.asarray(h.tokens))
        assert h.finish_reason == "length"
        assert set(built) == programs and len(built) == 2


def _ref(model, prompt, new):
    return [int(t) for t in
            model.generate(prompt[None], max_new_tokens=new)[0, prompt.size:]]


def _eos_of(ref, at):
    """An `eos_id` that stops the greedy stream `ref` at or before index
    `at`, and the stream it leaves (the EOS kept)."""
    eos = ref[at]
    return eos, ref[:ref.index(eos) + 1]


def _fly(eng, steps=3):
    """Step until a decode tick is in flight (no finish in `steps`)."""
    for _ in range(steps):
        eng.step()
    assert len(eng._flying) == 1
    return eng._flying[0]


class TestOneTickInFlight:
    """ISSUE 35: a step dispatches decode tick N and then lands tick
    N-1.  The streams stay `generate()`'s through everything that can
    happen to a request between its tick's dispatch and its landing,
    and both programs are what they were."""

    @pytest.mark.parametrize("slots,lens,new", [
        (4, [3, 5, 8, 11], [9, 4, 12, 7, 2, 10]),   # waves of admissions
        (2, [7, 12, 5], [12, 1, 6, 3, 9]),          # a request of one token
        (1, [6, 9], [5, 8, 2])])                    # one slot, in turn
    def test_streams_are_generates(self, llama, slots, lens, new):
        """Mixed lengths, admissions beside ticks in flight, finishes by
        length at unequal ticks, driven by bare `step()`s: nothing but
        the engine itself lands a tick."""
        eng = ServeEngine(llama, num_slots=slots, max_len=32, block_size=8,
                          max_queue=len(new))
        prompts = _prompts(len(new), lens, seed=51)
        hs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
        while eng.pending:
            eng.step()
        for p, n, h in zip(prompts, new, hs):
            assert h.tokens == _ref(llama, p, n)
            assert h.finish_reason == "length"
        snap = eng.metrics.snapshot()
        assert 0 < snap["decode_ticks_ahead"] < snap["decode_ticks"]
        assert not eng._flying
        assert eng.pool.free_count == slots and (eng.pool.ref == 0).all()
        assert_program_count(eng, (1, 1))

    @pytest.mark.parametrize("at", [0, 2, 5])
    def test_nothing_after_an_eos_and_the_successor_is_right(self, llama,
                                                             at):
        """The request whose EOS lands took part in the tick dispatched
        before the landing: that tick's token is dropped, its row went
        into the request's own block, and the next request in the slot
        (one slot: the same) reads nothing of it."""
        eng = ServeEngine(llama, num_slots=1, max_len=32, block_size=8)
        first, second = _prompts(2, [6, 9], seed=53)
        eos, want = _eos_of(_ref(llama, first, 10), at)
        seen = []
        a = eng.submit(first, max_new_tokens=10, eos_id=eos,
                       on_token=lambda t, h: seen.append(t))
        b = eng.submit(second, max_new_tokens=7)
        while eng.pending:
            eng.step()
        assert a.finish_reason == "eos" and a.tokens == want == seen
        assert b.tokens == _ref(llama, second, 7)
        assert not eng._flying and (eng.pool.ref == 0).all()

    def test_a_deadline_eviction_with_a_tick_in_flight(self, llama):
        eng = ServeEngine(llama, num_slots=2, max_len=32, block_size=8)
        p0, p1, p2 = _prompts(3, [7, 5, 9], seed=55)
        gone = eng.submit(p0, max_new_tokens=20, deadline_s=60.0)
        stays = eng.submit(p1, max_new_tokens=12)
        _, pairs, _ = _fly(eng)
        assert len(pairs) == 2
        gone._req.deadline = 0.0            # passed, as the next step sees
        n = len(gone.tokens)
        after = eng.submit(p2, max_new_tokens=6)    # takes the freed slot
        eng.step()
        assert gone.finish_reason == "deadline" and len(gone.tokens) == n
        assert gone.tokens == _ref(llama, p0, 20)[:n]
        eng.run_until_idle()
        assert stays.tokens == _ref(llama, p1, 12)
        assert after.tokens == _ref(llama, p2, 6)

    def test_a_preemption_across_a_block_boundary(self, llama):
        """Five usable blocks under two requests that each need three:
        growth pre-empts the youngest while the tick it took part in is
        in flight; it replays from prompt + tokens delivered."""
        eng = ServeEngine(llama, num_slots=2, max_len=32, block_size=8,
                          num_blocks=6)
        prompts = _prompts(2, [7], seed=37)
        hs = [eng.submit(p, max_new_tokens=16) for p in prompts]
        dropped = 0
        while eng.pending:
            before = eng.metrics.preempted, list(eng._flying)
            eng.step()
            if eng.metrics.preempted > before[0] and before[1]:
                dropped += 1
        assert dropped >= 1
        for p, h in zip(prompts, hs):
            assert h.tokens == _ref(llama, p, 16)
        assert_program_count(eng, (1, 1))

    @pytest.mark.parametrize("how", ["recover", "heartbeat", "withdraw",
                                     "extract_handoff", "running_items",
                                     "slot_cache", "decode_false"])
    def test_moved_with_a_tick_in_flight(self, llama, how):
        """Whatever reads the requests' tokens or moves a slot from
        outside a step lands first (`recover` as far as the device
        yields the tick; the rebuild a hang asks for drops it)."""
        eng = ServeEngine(llama, num_slots=2, max_len=32, block_size=8)
        prompts = _prompts(2, [7, 12], seed=57)
        hs = [eng.submit(p, max_new_tokens=14) for p in prompts]
        _fly(eng)
        n = [len(h.tokens) for h in hs]
        landed = [k + 1 for k in n]
        if how == "recover":
            eng.recover("test")
        elif how == "heartbeat":
            eng._recover_flag.set()         # the monitor thread's request
            eng.step()
            assert eng.metrics.recoveries == 1
            # dropped; re-prefilled, and the next tick dispatched
            landed = [k + 1 for k in n]
            assert [r for _, r in eng._flying[0][1]] == [h._req for h in hs]
        elif how == "withdraw":
            req = eng.withdraw(0)
            assert len(req.tokens) == landed[0]
            eng.sched.requeue_front([req])
        elif how == "extract_handoff":
            other = ServeEngine(llama, num_slots=2, max_len=32,
                                block_size=8, programs=eng.programs())
            pkg = eng.extract_handoff(0)
            assert pkg.pos == pkg.req.replay_ids().size - 1
            assert len(pkg.req.tokens) == landed[0]
            assert other.inject_handoff(pkg)
        elif how == "running_items":
            assert [s for s, _ in eng.running_items()] == [0, 1]
        elif how == "slot_cache":
            k, _ = eng.slot_cache(1)[0]
            assert k.shape[0] == prompts[1].size + landed[1] - 1
        else:
            eng.step(decode=False)
        assert how == "heartbeat" or not eng._flying
        assert [len(h.tokens) for h in hs] == landed
        for e in (eng, other) if how == "extract_handoff" else (eng,):
            e.run_until_idle()
        for p, h in zip(prompts, hs):
            assert h.tokens == _ref(llama, p, 14)

    @pytest.mark.parametrize("how", ["run_until_idle", "cut", "drain",
                                     "close"])
    def test_the_last_tick_lands(self, llama, how):
        eng = ServeEngine(llama, num_slots=2, max_len=32, block_size=8)
        prompts = _prompts(2, [7, 12], seed=59)
        ref = _ref(llama, prompts[0], 12)
        eos, want = _eos_of(ref, 6)
        # the EOS ends the run with a tick in flight that nobody is
        # left to take a token from
        a = eng.submit(prompts[0], max_new_tokens=12, eos_id=eos)
        b = eng.submit(prompts[1], max_new_tokens=5)
        if how == "cut":
            eng.run_until_idle(max_steps=2)
            assert eng.pending and not eng._flying
            assert len(a.tokens) == 3       # prefill's, and both ticks'
            eng.run_until_idle()
        else:
            getattr(eng, how)()
        assert a.tokens == want and b.tokens == _ref(llama, prompts[1], 5)
        assert eng._closed or not eng._flying

    def test_a_finish_by_length_leaves_nothing_in_flight(self, llama):
        """Rule 4: the host knows before the token does that a tick ends
        a request by length, and lands it in its own step, so that the
        successor a closed loop submits after `step()` prefills on an
        empty device queue.  Every other step leaves one tick in
        flight."""
        eng = ServeEngine(llama, num_slots=2, max_len=32, block_size=8)
        prompts = _prompts(4, [7, 5, 9, 4], seed=61)
        new = [6, 9, 4, 7]
        hs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
        ended, ahead = 0, 0
        while eng.pending:
            done = sum(h.done for h in hs)
            eng.step()
            if sum(h.done for h in hs) > done:
                ended += 1
                assert not eng._flying
            else:
                ahead += 1
                assert len(eng._flying) == 1
        assert ended >= 3 and ahead >= 3
        for p, n, h in zip(prompts, new, hs):
            assert h.tokens == _ref(llama, p, n)

    def test_each_dispatch_opens_before_the_fetch_of_the_tick_before(
            self, llama, host_profile, tmp_path):
        """A run without finishes: on the host's line every
        `serve.decode.dispatch` but the first closes before the
        `serve.decode.fetch` of the tick before it opens, and the
        counter says the same."""
        eng = ServeEngine(llama, num_slots=2, max_len=32, block_size=8)
        eng.submit(_prompts(1, [5])[0], max_new_tokens=2)
        eng.run_until_idle()                    # compile outside the session
        snap0 = eng.metrics.snapshot()
        for p in _prompts(2, [7, 5], seed=63):
            eng.submit(p, max_new_tokens=20)
        with host_profile(tmp_path, ("serve.",)) as lines:
            for _ in range(8):
                eng.step()
        (line,) = lines
        sent = [(s, e) for n, s, e, _ in line if n == "serve.decode.dispatch"]
        fetched = [(s, e) for n, s, e, _ in line if n == "serve.decode.fetch"]
        delivers = [(s, e) for n, s, e, _ in line if n == "serve.deliver"]
        first = [(s, e) for n, s, e, _ in line if n == "serve.prefill.fetch"]
        # the two admissions' first tokens land behind the first tick
        # (ISSUE 37), each with a fetch and a delivery of its own
        assert len(first) == 2 and len(delivers) == 9
        for (fs, fe), (ds, de) in zip(first, delivers[:2]):
            assert sent[0][1] <= fs and fe <= ds and de <= sent[1][0]
        delivers = delivers[2:]
        assert (len(sent), len(fetched), len(delivers)) == (8, 7, 7)
        for tick in range(1, 8):
            assert sent[tick][1] <= fetched[tick - 1][0]
            assert fetched[tick - 1][1] <= delivers[tick - 1][0]
            if tick < 7:
                assert delivers[tick - 1][1] <= sent[tick + 1][0]
        snap = eng.metrics.snapshot()
        assert snap["decode_ticks"] - snap0["decode_ticks"] == 8
        assert snap["decode_ticks_ahead"] - snap0["decode_ticks_ahead"] == 7
        assert snap["token_ms"]["count"] - snap0["token_ms"]["count"] == 14
        eng.close()

    @pytest.mark.parametrize("fault", [False, True])
    def test_the_speculative_engine_never_runs_ahead(self, llama, fault):
        """Its accepted count decides the positions; the plain tick a
        failed verify falls back to lands in its own step too."""
        import warnings
        from singa_tpu import faults
        from singa_tpu.faults.plan import FaultPlan, FaultSpec
        eng = _engine_of("speculative", llama, num_slots=2, max_len=32,
                         block_size=8)
        prompts = _prompts(2, [7, 5], seed=65)
        plan = FaultPlan([FaultSpec("serve.verify", "error", every=1,
                                    times=3)] if fault else [])
        with faults.active(plan), warnings.catch_warnings():
            warnings.simplefilter("ignore")
            hs = [eng.submit(p, max_new_tokens=12) for p in prompts]
            while eng.pending:
                eng.step()
                assert not eng._flying
        for p, h in zip(prompts, hs):
            assert h.tokens == _ref(llama, p, 12)
        snap = eng.metrics.snapshot()
        assert snap["decode_ticks_ahead"] == 0
        assert snap["decode_ticks"] == snap["spec_fallbacks"] == int(fault)

    @pytest.mark.parametrize("program", ["prefill_chunk", "decode"])
    def test_the_programs_are_what_they_were(self, llama, program):
        """The order of a step is the host's: an engine that has run
        ahead lowers the text of one that has not stepped, and the text
        names one token vector in and one out."""
        eng = ServeEngine(llama, num_slots=2, max_len=32, block_size=8)
        text = eng.lower_programs(names=(program,))[program].as_text()
        hs = [eng.submit(p, max_new_tokens=9)
              for p in _prompts(3, [7, 12, 5], seed=67)]
        while eng.pending:
            eng.step()
        assert all(h.finish_reason == "length" for h in hs)
        assert eng.metrics.snapshot()["decode_ticks_ahead"] > 0
        assert eng.compiled_counts() == (1, 1)
        assert eng.lower_programs(names=(program,))[program].as_text() == text
        assert text.count("tensor<2xi32>") >= 2


def _pend(eng, prompt, new):
    """An admission whose chunks are dispatched and whose first token is
    not fetched, as between a step's admissions and its tick."""
    h = eng.submit(prompt, max_new_tokens=new)
    assert eng._admit(eng.sched.pop_for_admission(), True) == 0
    assert len(eng._first) == 1 and not h.tokens
    return h


class TestFirstTokenBehindTheTick:
    """ISSUE 37: a step dispatches its decode tick before it fetches the
    first tokens of its admissions.  The streams stay `generate()`'s,
    the order of delivery stays the order of admission, and a pending
    first token survives what a tick in flight survives."""

    @pytest.mark.parametrize("kind", ["llama", "moe", "zaya", "granite"])
    def test_streams_are_generates(self, tiny_model, kind):
        """Waves of admissions beside ticks in flight, requests of one
        and of two tokens among them, driven by bare `step()`s; the
        counter reads the admissions less those ended by their first
        token, and no step returns with a first token pending."""
        model = tiny_model(kind)
        eng = ServeEngine(model, num_slots=3, max_len=48, block_size=8,
                          max_queue=8)
        new = [6, 1, 9, 2, 5, 1, 7, 3]
        prompts = _prompts(len(new), [5, 11, 17, 8], seed=71)
        hs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
        while eng.pending:
            eng.step()
            assert not eng._first
        for p, n, h in zip(prompts, new, hs):
            assert h.tokens == _ref(model, p, n)
            assert h.finish_reason == "length"
        snap = eng.metrics.snapshot()
        assert snap["admitted"] == len(new)
        assert snap["first_tokens_behind_tick"] == len(new) - new.count(1)
        # what landed at once left the device with nothing: `admit`
        assert 1 <= snap["host"]["exposed_n"]["admit"] <= new.count(1)
        assert eng.pool.free_count == 3 and (eng.pool.ref == 0).all()
        assert_program_count(eng, (1, 1))

    def test_a_request_of_one_token_frees_its_slot_for_the_same_step(
            self, llama):
        eng = ServeEngine(llama, num_slots=1, max_len=32, block_size=8)
        p, q = _prompts(2, [7, 5], seed=73)
        seen = []

        def note(tok, h):
            seen.append((h.rid, len(eng._flying)))

        a = eng.submit(p, max_new_tokens=1, on_token=note)
        b = eng.submit(q, max_new_tokens=4, on_token=note)
        assert eng.step() == 2
        assert a.finish_reason == "length" and a.tokens == _ref(llama, p, 1)
        # a's token landed before any tick was dispatched, b's behind one
        assert seen == [(a.rid, 0), (b.rid, 1)]
        assert b.tokens == _ref(llama, q, 4)[:1] and len(eng._flying) == 1
        snap = eng.metrics.snapshot()
        assert (snap["admitted"], snap["first_tokens_behind_tick"],
                snap["decode_ticks"]) == (2, 1, 1)
        eng.run_until_idle()
        assert b.tokens == _ref(llama, q, 4)

    def test_an_eos_as_first_token(self, llama):
        """As an EOS from a run-ahead tick: the tick dispatched before
        the landing took the request along, wrote one row into its own
        block and picked a token nobody gets."""
        eng = ServeEngine(llama, num_slots=2, max_len=32, block_size=8)
        p, q, r = _prompts(3, [7, 12, 5], seed=75)
        stays = eng.submit(q, max_new_tokens=10)
        eng.step()
        eos, want = _eos_of(_ref(llama, p, 6), 0)
        a = eng.submit(p, max_new_tokens=6, eos_id=eos)
        after = eng.submit(r, max_new_tokens=5)
        eng.step()
        assert a.finish_reason == "eos" and a.tokens == want == [eos]
        # it took part in the tick in flight, and is gone from its slot
        (_, pairs, _), = eng._flying
        assert [req for _, req in pairs] == [stays._req, a._req]
        assert list(eng._running.values()) == [stays._req]
        while eng.pending:
            eng.step()
        assert len(a.tokens) == 1
        assert stays.tokens == _ref(llama, q, 10)
        assert after.tokens == _ref(llama, r, 5)
        assert (eng.pool.ref == 0).all()

    def test_two_admissions_deliver_in_the_order_admitted(self, llama):
        eng = ServeEngine(llama, num_slots=3, max_len=32, block_size=8)
        prompts = _prompts(3, [7, 12, 5], seed=77)
        seen = []
        old = eng.submit(prompts[0], max_new_tokens=9,
                         on_token=lambda t, h: seen.append(h.rid))
        for _ in range(2):
            eng.step()
        del seen[:]
        hs = [eng.submit(p, max_new_tokens=6,
                         on_token=lambda t, h: seen.append(h.rid))
              for p in prompts[1:]]
        assert eng.step() == 3
        # the first tokens, oldest admission first, then the tick before
        assert seen == [hs[0].rid, hs[1].rid, old.rid]
        assert eng.metrics.snapshot()["first_tokens_behind_tick"] == 3
        eng.run_until_idle()
        for p, h in zip(prompts[1:], hs):
            assert h.tokens == _ref(llama, p, 6)

    def test_pre_empted_before_its_token_lands_it_replays_from_its_prompt(
            self, llama):
        """Four usable blocks: the older request crosses into its third
        in the step that admitted the younger into the last two.  Growth
        pre-empts the youngest, whose first token is dropped unfetched."""
        eng = ServeEngine(llama, num_slots=2, max_len=32, block_size=8,
                          num_blocks=5)
        p, q = _prompts(2, [7, 12], seed=79)
        old = eng.submit(p, max_new_tokens=16)
        for _ in range(8):
            eng.step()
        assert int(eng.pool.pos[old._req.slot]) == 15
        young = eng.submit(q, max_new_tokens=6)
        eng.step()
        snap = eng.metrics.snapshot()
        assert snap["preempted"] == 1 and not eng._first
        assert young.status == "queued" and young.tokens == []
        assert snap["first_tokens_behind_tick"] == 1     # the older's own
        eng.run_until_idle()
        assert old.tokens == _ref(llama, p, 16)
        assert young.tokens == _ref(llama, q, 6)
        snap = eng.metrics.snapshot()
        # admitted once, though prefilled twice
        assert (snap["admitted"], snap["first_tokens_behind_tick"]) == (2, 2)
        assert snap["ttft_ms"]["count"] == 2
        assert_program_count(eng, (1, 1))

    @pytest.mark.parametrize("kind", ["decode_false", "speculative"])
    def test_no_plain_tick_to_put_it_behind_it_lands_at_once(self, llama,
                                                              kind):
        """The prefill worker's step dispatches no tick and the
        speculative engine's no plain one: nothing is pending when
        `step()` returns, and the token was there before it did."""
        eng = _engine_of("speculative" if kind == "speculative" else "plain",
                         llama, num_slots=2, max_len=32, block_size=8)
        prompts = _prompts(2, [7, 12], seed=81)
        hs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.step(decode=kind == "speculative")
        assert not eng._first and not eng._flying
        assert all(len(h.tokens) >= 1 for h in hs)
        if kind == "decode_false":
            assert [len(h.tokens) for h in hs] == [1, 1]
        while eng.pending:
            eng.step()
            assert not eng._first
        for p, h in zip(prompts, hs):
            assert h.tokens == _ref(llama, p, 8)
        snap = eng.metrics.snapshot()
        assert snap["first_tokens_behind_tick"] == 0
        assert snap["ttft_ms"]["count"] == snap["admitted"] == 2

    @pytest.mark.parametrize("how", ["withdraw", "running_items",
                                     "extract_handoff", "slot_cache",
                                     "recover", "run_until_idle", "close"])
    def test_moved_with_a_first_token_pending(self, llama, how):
        """Whatever reads a request's tokens or moves a slot from
        outside a step lands a pending first token before a tick in
        flight."""
        eng = ServeEngine(llama, num_slots=2, max_len=32, block_size=8)
        p, q = _prompts(2, [7, 12], seed=83)
        old = eng.submit(p, max_new_tokens=10)
        _fly(eng)
        n = len(old.tokens)
        order = []
        old._req.on_token = lambda t, h: order.append("tick")
        h = _pend(eng, q, 6)
        h._req.on_token = lambda t, h: order.append("first")
        slot = h._req.slot
        if how == "withdraw":
            req = eng.withdraw(slot)
            assert req.tokens == _ref(llama, q, 6)[:1]
            eng.sched.requeue_front([req])
        elif how == "running_items":
            assert [r for _, r in eng.running_items()] == [old._req, h._req]
        elif how == "extract_handoff":
            other = ServeEngine(llama, num_slots=2, max_len=32,
                                block_size=8, programs=eng.programs())
            pkg = eng.extract_handoff(slot)
            assert pkg.req.tokens == _ref(llama, q, 6)[:1]
            assert other.inject_handoff(pkg)
            other.run_until_idle()
        elif how == "slot_cache":
            k, _ = eng.slot_cache(slot)[0]
            assert k.shape[0] == q.size
        elif how == "recover":
            eng.recover("test")
        else:
            getattr(eng, how)()
        assert order[:2] == ["first", "tick"]
        assert eng._closed or not (eng._first or eng._flying)
        # no tick was dispatched behind that chunk: the older's own only
        assert how in ("run_until_idle", "close") or \
            eng.metrics.snapshot()["first_tokens_behind_tick"] == 1
        if how in ("running_items", "slot_cache"):
            assert (len(h.tokens), len(old.tokens)) == (1, n + 1)
        if not eng._closed:
            eng.run_until_idle()
        assert h.tokens == _ref(llama, q, 6)
        assert old.tokens == _ref(llama, p, 10)

    @pytest.mark.parametrize("where", ["behind", "at_once"])
    def test_a_chunk_that_died_on_the_device(self, llama, monkeypatch,
                                             where):
        """Behind the tick its failure surfaces at the deferred fetch,
        under `step()`'s handler: the arena is rebuilt and every request
        replays, the admitted one from its prompt.  At once it is, as
        before, inside the admission: that request is quarantined."""
        import warnings
        eng = ServeEngine(llama, num_slots=2, max_len=32, block_size=8)
        p, q = _prompts(2, [7, 12], seed=85)
        old = eng.submit(p, max_new_tokens=10)
        _fly(eng)
        real, died = eng._fetch_first, []

        def dead(arr, slot, behind):
            if not died:
                died.append(behind)
                raise RuntimeError("the chunk died on the device")
            return real(arr, slot, behind)

        monkeypatch.setattr(eng, "_fetch_first", dead)
        new = 6 if where == "behind" else 1
        h = eng.submit(q, max_new_tokens=new)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            eng.step()
            snap = eng.metrics.snapshot()
            assert died == [where == "behind"]
            assert (snap["recoveries"], snap["quarantined"]) == \
                ((1, 0) if where == "behind" else (0, 1))
            assert not eng._first
            eng.run_until_idle()
        assert old.tokens == _ref(llama, p, 10)
        if where == "behind":
            assert h.tokens == _ref(llama, q, new) and not h.failed
            assert eng.metrics.snapshot()["admitted"] == 2
        else:
            assert h.failed and h.tokens == []
        assert (eng.pool.ref == 0).all()

    def test_the_counter_is_in_the_sink(self, llama, tmp_path):
        eng = ServeEngine(llama, num_slots=2, max_len=32, block_size=8)
        path = str(tmp_path / "ev.jsonl")
        events.configure(path=path)
        try:
            for p, n in zip(_prompts(3, [7, 12, 5], seed=87), (5, 1, 3)):
                eng.submit(p, max_new_tokens=n)
            eng.run_until_idle()
        finally:
            events.configure()
        lines = [json.loads(l) for l in open(path)]
        mine = [e for e in lines
                if e["name"] == "serve.first_tokens_behind_tick"]
        assert len(mine) == 2 == \
            eng.metrics.snapshot()["first_tokens_behind_tick"]
        assert all(e["kind"] == "counter" and "trace" in e for e in mine)


def test_loadgen_quick_run_emits_valid_record(llama, engine, tmp_path):
    """tools/loadgen.py end-to-end against the shared engine: an
    open-loop burst completes, every request is accounted for
    (completed + shed + deadline + rejected + failed == offered), and
    the serve_load record validates against the schema."""
    from singa_tpu.obs import record as obs_record
    from tools import loadgen

    wl = loadgen.build_workload(10, rate_rps=500.0, seed=5,
                                prompt_lens=(4, 6), new_tokens=(2, 3),
                                tenants=2, shared_len=8)
    payload = loadgen.run_load(engine, wl, deadline_s=30.0)
    assert payload["requests"] == 10
    accounted = (payload["completed"] + payload["shed"]
                 + payload["rejected"]
                 + payload["detail"]["deadline_evicted"]
                 + payload["detail"]["quarantined"])
    assert accounted == 10
    store = loadgen.append_record(payload,
                                  str(tmp_path / "records.jsonl"))
    assert obs_record.RunRecord(store).validate() == []
    entry = obs_record.RunRecord(store).entries()[0]
    assert entry["kind"] == "serve_load"
    assert engine.pending == 0
    assert_program_count(engine, (1, 1))


class TestHistogramPrimitive:
    def test_summary_semantics(self):
        events.reset_histograms("t.h")
        for v in (1.0, 2.0, 3.0, 4.0, 100.0):
            events.histogram("t.h", v)
        s = events.histogram_summary("t.h")
        assert s["count"] == 5
        assert s["sum"] == pytest.approx(110.0)
        assert s["mean"] == pytest.approx(22.0)
        assert s["min"] == 1.0 and s["max"] == 100.0
        assert s["p50"] == 3.0
        assert s["p99"] == 100.0

    def test_reset_and_missing(self):
        events.reset_histograms("t.h2")
        assert events.histogram_summary("t.h2") is None
        events.histogram("t.h2", 5.0)
        assert events.histogram_summary("t.h2")["count"] == 1
        events.reset_histograms("t.h2")
        assert events.histogram_summary("t.h2") is None

    def test_bounded_ring_keeps_exact_totals(self):
        from singa_tpu.obs.events import _HIST_CAP
        events.reset_histograms("t.ring")
        n = _HIST_CAP + 100
        for i in range(n):
            events.histogram("t.ring", float(i))
        s = events.histogram_summary("t.ring")
        # count/sum/min/max exact beyond the ring capacity
        assert s["count"] == n
        assert s["sum"] == pytest.approx(n * (n - 1) / 2.0)
        assert s["min"] == 0.0 and s["max"] == float(n - 1)

    def test_sink_emission(self, tmp_path):
        path = str(tmp_path / "h.jsonl")
        events.configure(path=path)
        try:
            events.histogram("t.sink", 7.5, stage="x")
        finally:
            events.configure()
        ev = json.loads(open(path).read().strip())
        assert ev["kind"] == "hist" and ev["name"] == "t.sink"
        assert ev["value"] == 7.5 and ev["stage"] == "x"


def test_serve_record_schema_roundtrip(tmp_path):
    """A serve_throughput store entry validates; a truncated one is
    named-field rejected (the record_check CI contract)."""
    from singa_tpu.obs import record as obs_record
    from singa_tpu.obs import schema

    store = obs_record.RunRecord(str(tmp_path / "records.jsonl"))
    entry = obs_record.new_entry(
        "serve_throughput", "cpu", True, "cpu",
        payload={"tokens_per_s": 1000.0, "speedup_vs_sequential": 2.0,
                 "ttft_p50_ms": 5.0, "ttft_p99_ms": 9.0, "requests": 12})
    store.append(entry)
    assert store.validate() == []
    bad = dict(entry)
    bad["payload"] = {"tokens_per_s": 1000.0}
    with pytest.raises(schema.SchemaError, match="ttft_p50_ms|speedup"):
        schema.validate_entry(bad)
