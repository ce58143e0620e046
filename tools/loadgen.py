"""Open-loop traffic generator for the paged serving engine.

Closed-loop drivers (submit, wait, submit) let a slow server set its
own pace and hide queueing collapse; an OPEN-loop generator arrives on
its own clock — Poisson inter-arrivals at a configured rate, mixed
prompt/output lengths, a tenant mix whose requests share per-tenant
system prompts — so scheduler and paging changes are judged on what
production cares about: p99 TTFT, tokens/s, and how gracefully load is
shed when the offered rate exceeds capacity.

    python -m tools.loadgen --rate 20 --requests 80 --deadline 10
    SINGA_FAULTS="serve.decode=error:every=40" python -m tools.loadgen ...

    # disaggregated tier (ISSUE 12): N prefill + M decode workers
    # behind the SLO-aware Router, and the independent-scaling sweep —
    # one serve_load record per N:M point, same Poisson workload
    python -m tools.loadgen --prefill-workers 3 --decode-workers 1
    python -m tools.loadgen --ratio-sweep 3:1,2:2,1:3 --rate 40
    python -m tools.loadgen --disagg-smoke     # CI: tier == engine

    # speculative decoding (ISSUE 13): verify-k through a
    # self-speculation draft; --spec-compare commits the plain-vs-spec
    # serve_load pair (shared spec_pair_id, interleaved-median trials)
    python -m tools.loadgen --spec-k 4 --new-tokens 32
    python -m tools.loadgen --spec-compare --num-slots 1 --spec-k 7
    python -m tools.loadgen --spec-smoke       # CI: spec == generate()

    # multi-process tier (ISSUE 18): every worker a ServeEngine in its
    # own OS process behind the serve.net wire (framed RPC + digest-
    # checked KV handoff codec); records stamp the transport trio,
    # `procs` and `host_cores` (a 1-core box serializes the workers —
    # the record says so instead of faking a scaling win), and
    # `mp_sweep_id` (NOT sweep_id: the in-process ratio-direction
    # assertion in tests/test_disagg.py must not adopt mp points)
    python -m tools.loadgen --procs --prefill-workers 1 --decode-workers 2
    python -m tools.loadgen --procs --ratio-sweep 2:1,1:2 --rate 40
    python -m tools.loadgen --mp-smoke         # CI: mp tier == engine

The run drives ``ServeEngine.step()`` directly (arrivals are submitted
the tick their timestamp passes; ``QueueFull`` rejections count as
overload outcomes, not errors) and reports SLO percentiles from the
engine's obs histograms.  The headline lands in the run-record store as
a ``serve_load`` entry (``obs/schema.py``; linted by ``python -m
tools.lint --records``) with the offered/completed/shed/rejected
counts and TTFT p50/p99 — and the whole thing is runnable under a
``SINGA_FAULTS`` chaos plan, where the resilience claim is simply "the
engine finished the run" (every fired fault shows up in the detail).

Importable: :func:`build_workload` + :func:`run_load` are used by
tests/test_serve.py against a prebuilt engine (the CLI builds its own
model on CPU).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclass
class _Arrival:
    at_s: float
    prompt: np.ndarray
    max_new: int
    tenant: int


def build_workload(n_requests: int, rate_rps: float, seed: int, *,
                   prompt_lens: Sequence[int] = (6, 10, 16, 24),
                   new_tokens: Sequence[int] = (4, 8, 16),
                   tenants: int = 3, shared_len: int = 16,
                   vocab: int = 256) -> List[_Arrival]:
    """A reproducible open-loop trace: Poisson arrivals at ``rate_rps``,
    prompts drawn as ``tenant system prefix (shared_len tokens) +
    private suffix (prompt_lens mix)``, output budgets from
    ``new_tokens``.  ``tenants=0`` or ``shared_len=0`` disables
    sharing (every prompt fully private)."""
    rng = np.random.RandomState(seed)
    at = np.cumsum(rng.exponential(1.0 / rate_rps, n_requests))
    prefixes = [rng.randint(0, vocab, (shared_len,)).astype(np.int32)
                for _ in range(tenants)] if tenants and shared_len else []
    out = []
    for i in range(n_requests):
        tenant = int(rng.randint(0, tenants)) if prefixes else -1
        suffix = rng.randint(
            0, vocab,
            (int(prompt_lens[rng.randint(0, len(prompt_lens))]),)
        ).astype(np.int32)
        prompt = (np.concatenate([prefixes[tenant], suffix])
                  if prefixes else suffix)
        out.append(_Arrival(float(at[i]), prompt,
                            int(new_tokens[rng.randint(0,
                                                       len(new_tokens))]),
                            tenant))
    return out


def run_load(engine, workload: List[_Arrival], *,
             deadline_s: Optional[float] = None,
             eos_id: Optional[int] = None,
             max_wall_s: float = 300.0,
             pass_tenant: bool = False) -> dict:
    """Drive ``engine`` through ``workload`` open-loop and return the
    ``serve_load`` payload (plus a ``detail`` sub-dict that is NOT part
    of the schema contract).  Never raises on overload outcomes —
    ``QueueFull`` is a counted result; only an engine CRASH (the thing
    chaos runs assert cannot happen) propagates.

    ``engine`` may equally be a :class:`singa_tpu.serve.Router` (a
    disaggregated tier — same submit/step/pending/metrics surface);
    the payload then additionally carries the per-pool tier fields
    (``engine.tier_stats()``, linted as schema
    ``_SERVE_TIER_FIELDS``).  ``pass_tenant`` forwards each arrival's
    tenant id to ``submit(tenant=...)`` so per-tenant quotas are
    exercised (Router only — a plain engine has no tenant door).

    An injected ``serve.router`` fault at the door is a counted
    outcome like ``QueueFull`` (``detail.router_faults``) — the chaos
    contract is that only an engine CRASH aborts the harness, and the
    routing site's documented behavior is 'surfaces to the submitter
    like a routing outage'."""
    from singa_tpu.faults import InjectedFault
    from singa_tpu.serve import QueueFull

    handles = []
    router_faults = 0
    n = len(workload)
    i = 0
    t0 = time.monotonic()
    while True:
        now = time.monotonic() - t0
        while i < n and workload[i].at_s <= now:
            kw = {"tenant": f"t{workload[i].tenant}"} \
                if pass_tenant and workload[i].tenant >= 0 else {}
            try:
                handles.append(engine.submit(
                    workload[i].prompt,
                    max_new_tokens=workload[i].max_new,
                    deadline_s=deadline_s, eos_id=eos_id, **kw))
            except QueueFull:
                handles.append(None)       # counted via metrics.rejected
            except InjectedFault:
                handles.append(None)       # a chaos-plan routing outage
                router_faults += 1
            i += 1
        if engine.pending:
            engine.step()
        elif i < n:
            # idle gap before the next arrival: sleep it off instead of
            # spinning (open loop — we must not pull arrivals early)
            time.sleep(min(workload[i].at_s - now, 0.05))
        else:
            break
        if now > max_wall_s:
            break
    wall = time.monotonic() - t0
    snap = engine.metrics.snapshot()
    # stamp the architecture key (ISSUE 14): the autotuner's spec_k
    # picker matches records to a (model, platform) strictly, so a
    # pair measured on one architecture can never decide another's k
    served_model = getattr(engine, "model", None)
    model_key = None
    if served_model is not None:
        from singa_tpu.autotune import table as autotune_table
        model_key = autotune_table.model_key(served_model)
    done = [h for h in handles if h is not None]
    completed = sum(1 for h in done
                    if h.finish_reason in ("eos", "length"))
    tokens = sum(len(h.tokens) for h in done)
    ttft = snap["ttft_ms"] or {}
    payload = {
        "requests": n,
        "completed": completed,
        "shed": int(snap["evicted"].get("shed", 0)),
        "rejected": int(snap["rejected"]),
        "tokens_per_s": round(tokens / wall, 1) if wall else 0.0,
        "ttft_p50_ms": round(ttft.get("p50", 0.0), 3),
        "ttft_p99_ms": round(ttft.get("p99", 0.0), 3),
    }
    if model_key is not None:
        payload["model"] = model_key
    if snap.get("accept_rate") is not None:
        # speculative engine/tier: the pair joins the headline (schema
        # both-or-neither contract, _SPEC_FIELDS) — accept rate plus the
        # tokens-per-dispatch density the spec path exists to raise
        payload["accept_rate"] = round(snap["accept_rate"], 4)
        payload["tokens_per_dispatch"] = round(
            snap["tokens_per_dispatch"] or 0.0, 3)
    pool = getattr(engine, "pool", None)
    if pool is not None and getattr(pool, "spill", None) is not None:
        # spill-tier engine: the trio joins the headline as a unit
        # (schema all-or-nothing contract, _SERVE_SPILL_FIELDS)
        payload["spilled_blocks"] = int(snap.get("spilled_blocks", 0))
        payload["prefetch_hits"] = int(snap.get("prefetch_hits", 0))
        payload["prefetch_wait_ms"] = round(
            float(snap.get("prefetch_wait_ms", 0.0)), 3)
    payload["detail"] = {
        "wall_s": round(wall, 3),
        "generated_tokens": tokens,
        "deadline_evicted": int(snap["evicted"].get("deadline", 0)),
        "quarantined": int(snap["quarantined"]),
        "preempted": int(snap["preempted"]),
        "recoveries": int(snap["recoveries"]),
        "prefix_hits": int(snap["prefix_hits"]),
        "prefix_hit_tokens": int(snap["prefix_hit_tokens"]),
        "retries": dict(snap["retries"]),
        "token_p50_ms": round((snap["token_ms"] or {}).get("p50", 0.0),
                              3),
        "router_faults": router_faults,
        "spec_rounds": int(snap.get("spec_rounds", 0)),
        "spec_fallbacks": int(snap.get("spec_fallbacks", 0)),
    }
    tier = getattr(engine, "tier_stats", None)
    if tier is not None:
        # a disaggregated Router: the per-pool quartet joins the
        # headline (schema both-or-neither contract) and the tier-only
        # diagnostics stay in detail
        payload.update(tier())
        payload["detail"]["reroutes"] = int(snap.get("reroutes", 0))
        payload["detail"]["worker_deaths"] = int(
            snap.get("worker_deaths", 0))
        payload["detail"]["handoff_p50_ms"] = round(
            (snap.get("handoff_ms") or {}).get("p50", 0.0), 3)
    return payload


def append_record(payload: dict, store: Optional[str] = None,
                  prefix: str = "load", tier=None) -> str:
    """Write the headline (schema-required fields + numeric extras;
    the ``detail`` sub-dict stays out of the durable record) as a
    ``serve_load`` entry.  Returns the store path.

    ``prefix`` must DIFFER between two appends from the same process in
    the same second: the store keys entries by ``(run_id, platform,
    smoke)`` and ``new_run_id``'s timestamp has second resolution, so
    back-to-back same-prefix appends (the --spec-compare pair) would
    silently overwrite each other.

    The record names the platform the work ran on: this process's jax
    backend, or — for a ``--procs`` ``tier`` — the one its worker
    processes reported (they run on the CPU whatever this process
    holds)."""
    from singa_tpu.obs import record as obs_record
    from singa_tpu.obs import schema

    body = {k: v for k, v in payload.items() if k != "detail"}
    body.update({k: v for k, v in payload["detail"].items()
                 if isinstance(v, (int, float))})
    if tier is not None:
        platform, device_kind = tier.platform, tier.device_kind
    else:
        import jax
        dev = jax.devices()[0]
        platform, device_kind = dev.platform, dev.device_kind
    entry = obs_record.new_entry(
        "serve_load", platform, platform != "tpu", device_kind,
        run_id=obs_record.new_run_id(prefix), payload=body)
    schema.validate_entry(entry)           # fail before touching disk
    store = store or os.path.join(_REPO, obs_record.DEFAULT_STORE)
    obs_record.RunRecord(store).append(entry)
    return store


def _spec_kwargs(spec_k, model):
    """The ServeEngine speculative kwargs for ``--spec-k`` — ONE place
    parameterizes every engine/tier/template builder (self-speculation
    draft; a template built differently from its workers would only
    surface at programs= validation time)."""
    return {"draft_model": model, "spec_k": spec_k} if spec_k else {}


def _build_model():
    from singa_tpu import models, tensor
    tensor.set_seed(0)
    m = models.Llama(models.LlamaConfig.tiny())
    m.eval()
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32))],
              is_train=False, use_graph=False)
    return m


def _resolve_serve_knobs(args, model) -> dict:
    """Fill ``args.num_slots`` / ``args.block_size`` /
    ``args.spill_blocks`` from the committed best-config table
    (``singa_tpu.autotune.table``) when the CLI left them at their None
    defaults.  Precedence is the autotuner's contract: an explicit flag
    always wins; else the table's entry for this (model, platform);
    else the registry's hand-carried constants
    (``autotune.knobs.DEFAULTS`` — ONE source of truth), announced
    loudly once.  The registry stores ``spill_blocks`` as a number with
    0 = off; the engine constructor wants None for off, so 0 maps
    back."""
    import jax

    from singa_tpu.autotune import table as autotune_table

    knobs = autotune_table.resolve(
        "serve", autotune_table.model_key(model), jax.default_backend(),
        {"num_slots": args.num_slots, "block_size": args.block_size,
         "spill_blocks": getattr(args, "spill_blocks", None)})
    args.num_slots = int(knobs["num_slots"])
    args.block_size = int(knobs["block_size"])
    if getattr(args, "spill_blocks", None) is None:
        spill = int(knobs.get("spill_blocks", 0) or 0)
        args.spill_blocks = spill if spill > 0 else None
    return {"num_slots": args.num_slots,
            "block_size": args.block_size}


def _build_tier(model, n_prefill: int, n_decode: int, args, store,
                template=None):
    """A Router over N + M same-config workers (sharing ``template``'s
    compiled programs when given, so a ratio sweep compiles once).
    With ``--spec-k`` the whole tier carries the (self-speculation)
    draft — prefill workers write both arenas, decode workers verify."""
    from singa_tpu.serve import Router, build_pools

    spec = _spec_kwargs(getattr(args, "spec_k", 0), model)
    pw, dw = build_pools(model, n_prefill, n_decode, template=template,
                         num_slots=args.num_slots, max_len=args.max_len,
                         block_size=args.block_size,
                         num_blocks=args.num_blocks,
                         share_prefix=not args.no_share,
                         max_queue=args.max_queue,
                         backoff_base=0.005, backoff_max=0.05,
                         max_recoveries=100, record_store=store, **spec)
    return Router(pw, dw, tenant_quota=args.tenant_quota,
                  record_store=store)


def parse_ratios(spec: str) -> List[tuple]:
    """``"3:1,2:2,1:3"`` -> [(3, 1), (2, 2), (1, 3)] — the N:M
    prefill:decode points a ratio sweep runs (each must have >= 1
    worker per pool)."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        try:
            n, m = part.split(":")
            n, m = int(n), int(m)
        except ValueError:
            raise ValueError(
                f"--ratio-sweep: expected N:M points like '3:1,1:3', "
                f"got {part!r}")
        if n < 1 or m < 1:
            raise ValueError(f"--ratio-sweep: each pool needs >= 1 "
                             f"worker, got {part!r}")
        out.append((n, m))
    if not out:
        raise ValueError("--ratio-sweep: no points")
    return out


def disagg_smoke() -> int:
    """The CI gate's disagg stage: a tiny 1:1 tier serves 8 requests
    with greedy streams asserted IDENTICAL to a single-engine
    ServeEngine run (and the first one to ``generate()``) — the
    handoff path's end-to-end correctness as one cheap command
    (``python -m tools.loadgen --disagg-smoke``)."""
    from singa_tpu.serve import Router, ServeEngine, build_pools

    m = _build_model()
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, m.cfg.vocab_size, (int(n),)).astype(np.int32)
               for n in (4, 6, 9, 12, 5, 7, 10, 8)]
    eng = ServeEngine(m, num_slots=4, max_len=32, block_size=8)
    ref = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_idle()
    ref_toks = [h.tokens for h in ref]
    gen = m.generate(prompts[0][None], max_new_tokens=6)[0,
                                                         prompts[0].size:]
    if list(map(int, gen)) != ref_toks[0]:
        print("disagg-smoke: FAIL — single engine drifted from "
              "generate()", file=sys.stderr)
        return 1
    pw, dw = build_pools(m, 1, 1, template=eng, num_slots=4, max_len=32,
                         block_size=8)
    tier = Router(pw, dw)
    got = [tier.submit(p, max_new_tokens=6) for p in prompts]
    tier.run_until_idle()
    got_toks = [h.tokens for h in got]
    if got_toks != ref_toks:
        for i, (a, b) in enumerate(zip(ref_toks, got_toks)):
            if a != b:
                print(f"disagg-smoke: FAIL — request {i} diverged: "
                      f"engine={a} tier={b}", file=sys.stderr)
        return 1
    handoffs = tier.metrics.handoffs
    print(f"disagg-smoke: OK — {len(prompts)} streams identical "
          f"through a 1:1 tier ({handoffs} handoffs)")
    return 0


def spec_smoke() -> int:
    """The CI gate's speculative-decoding stage: the same 8 prompts
    decoded three ways — ``generate()``, a plain engine, and a
    self-speculation engine (draft == target, spec_k=3) — must produce
    IDENTICAL greedy streams, and self-speculation must accept every
    proposal (the identity end of the correctness envelope; the
    adversarial end lives in tests/test_spec.py).  One cheap command:
    ``python -m tools.loadgen --spec-smoke``."""
    from singa_tpu.serve import ServeEngine

    m = _build_model()
    rng = np.random.RandomState(13)
    prompts = [rng.randint(0, m.cfg.vocab_size, (int(n),)).astype(np.int32)
               for n in (4, 6, 9, 12, 5, 7, 10, 8)]
    plain = ServeEngine(m, num_slots=4, max_len=32, block_size=8)
    ref = [plain.submit(p, max_new_tokens=6) for p in prompts]
    plain.run_until_idle()
    ref_toks = [h.tokens for h in ref]
    gen = m.generate(prompts[0][None], max_new_tokens=6)[0,
                                                         prompts[0].size:]
    if list(map(int, gen)) != ref_toks[0]:
        print("spec-smoke: FAIL — plain engine drifted from generate()",
              file=sys.stderr)
        return 1
    spec = ServeEngine(m, num_slots=4, max_len=32, block_size=8,
                       draft_model=m, spec_k=3)
    got = [spec.submit(p, max_new_tokens=6) for p in prompts]
    spec.run_until_idle()
    got_toks = [h.tokens for h in got]
    if got_toks != ref_toks:
        for i, (a, b) in enumerate(zip(ref_toks, got_toks)):
            if a != b:
                print(f"spec-smoke: FAIL — request {i} diverged: "
                      f"plain={a} spec={b}", file=sys.stderr)
        return 1
    snap = spec.metrics.snapshot()
    if snap["accept_rate"] != 1.0:
        print(f"spec-smoke: FAIL — self-speculation accept_rate "
              f"{snap['accept_rate']} != 1.0 (the draft IS the target; "
              f"anything rejected means the verify window diverged "
              f"from sequential decode)", file=sys.stderr)
        return 1
    print(f"spec-smoke: OK — {len(prompts)} streams identical "
          f"(generate == plain == spec_k=3), accept_rate 1.0, "
          f"{snap['tokens_per_dispatch']:.2f} tokens/dispatch")
    return 0


def spill_smoke() -> int:
    """The CI gate's spill-tier stage: a deliberately shrunk arena
    (num_blocks=9) with a host spill store serves a shared-prefix
    request, churns the arena until the cold prefix blocks are evicted
    to the spill tier, then re-hits the prefix so the blocks are
    restored.  Asserts both shared-prefix streams are IDENTICAL to
    ``generate()``, that blocks actually spilled, and that the prefix
    re-hit was served from the spill store — one cheap command
    (``python -m tools.loadgen --spill-smoke``)."""
    from singa_tpu.serve import ServeEngine

    m = _build_model()
    rng = np.random.RandomState(17)
    shared = rng.randint(0, m.cfg.vocab_size, (16,)).astype(np.int32)
    tails = [rng.randint(0, m.cfg.vocab_size, (4,)).astype(np.int32)
             for _ in range(2)]
    prompts = [np.concatenate([shared, t]) for t in tails]
    refs = [list(map(int, m.generate(p[None], max_new_tokens=6)
                     [0, p.size:])) for p in prompts]
    # shrunk arena: the churn requests below need 3+ blocks each and
    # run two-at-a-time, so with only 9 physical blocks the LRU must
    # evict the first request's cold shared-prefix blocks — into the
    # spill store instead of oblivion
    eng = ServeEngine(m, num_slots=2, max_len=32, block_size=8,
                      num_blocks=9, spill_blocks=16)
    h1 = eng.submit(prompts[0], max_new_tokens=6)
    eng.run_until_idle()
    for _ in range(4):
        q = rng.randint(0, m.cfg.vocab_size, (20,)).astype(np.int32)
        eng.submit(q, max_new_tokens=4)
    eng.run_until_idle()
    # prefix re-hit: the shared blocks come back from the spill store
    h2 = eng.submit(prompts[1], max_new_tokens=6)
    eng.run_until_idle()
    got = [h1.tokens, h2.tokens]
    if got != refs:
        for i, (a, b) in enumerate(zip(refs, got)):
            if a != b:
                print(f"spill-smoke: FAIL — request {i} diverged: "
                      f"generate={a} spill={b}", file=sys.stderr)
        return 1
    snap = eng.metrics.snapshot()
    if snap["spilled_blocks"] < 1:
        print("spill-smoke: FAIL — the shrunk arena never spilled a "
              "block (arena sizing drifted?)", file=sys.stderr)
        return 1
    if snap["prefetch_hits"] < 1:
        print("spill-smoke: FAIL — blocks spilled but no prefix re-hit "
              "was served from the spill store", file=sys.stderr)
        return 1
    print(f"spill-smoke: OK — streams identical to generate() through "
          f"a 9-block arena, {snap['spilled_blocks']} blocks spilled, "
          f"{snap['prefetch_hits']} restored "
          f"({snap['prefetch_wait_ms']:.1f} ms total prefetch wait)")
    return 0


def _build_proc_tier(n_prefill: int, n_decode: int, args, store,
                     policy=None):
    """A ProcRouter over N + M worker PROCESSES (ISSUE 18): each worker
    re-builds this module's ``_build_model`` in its own interpreter
    (deterministic — seed 0, same tiny config) and compiles its own
    program set; KV handoffs travel the digest-checked wire codec
    instead of a same-process device copy."""
    from singa_tpu.serve import ProcRouter, build_proc_pools

    pw, dw = build_proc_pools(
        "tools.loadgen:_build_model", n_prefill, n_decode,
        num_slots=args.num_slots, max_len=args.max_len,
        block_size=args.block_size, num_blocks=args.num_blocks,
        share_prefix=not args.no_share, max_queue=args.max_queue,
        record_store=store, self_spec_k=args.spec_k)
    return ProcRouter(pw, dw, record_store=store, policy=policy)


def _stamp_mp(payload: dict, tier, n_procs: int) -> None:
    """The multi-process provenance a ``--procs`` record carries: the
    transport trio (schema ``_SERVE_TRANSPORT_FIELDS``), the worker
    process count, and the host's core count — ``host_cores`` is what
    lets a reader (and the frozen-record assertion in tests) judge
    whether the tokens/s number COULD have scaled with processes, or
    whether a 1-core box serialized them."""
    payload.update(tier.transport_stats())
    if tier.model_key:
        payload["model"] = tier.model_key
    payload["procs"] = int(n_procs)
    payload["host_cores"] = int(os.cpu_count() or 1)


def mp_smoke() -> int:
    """The CI gate's multi-process stage: a 2-process 1:1 tier (each
    worker a ServeEngine in its own OS process behind the serve.net
    RPC) serves 6 requests with greedy streams asserted IDENTICAL to a
    single in-process engine — spawn, framed RPC, the digest-checked KV
    wire codec, and donated-scatter injection end-to-end as one cheap
    command (``python -m tools.loadgen --mp-smoke``)."""
    from singa_tpu.serve import ServeEngine

    m = _build_model()
    rng = np.random.RandomState(19)
    prompts = [rng.randint(0, m.cfg.vocab_size, (int(n),)).astype(np.int32)
               for n in (4, 6, 9, 12, 5, 10)]
    eng = ServeEngine(m, num_slots=4, max_len=32, block_size=8)
    ref = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_idle()
    ref_toks = [h.tokens for h in ref]
    eng.close()

    class _Args:
        num_slots, max_len, block_size = 4, 32, 8
        num_blocks, max_queue, spec_k = None, None, 0
        no_share = False

    tier = _build_proc_tier(1, 1, _Args(), None)
    try:
        got = [tier.submit(p, max_new_tokens=6) for p in prompts]
        tier.run_until_idle()
        got_toks = [h.tokens for h in got]
        handoffs = tier.metrics.handoffs
        wire = tier.metrics.wire_bytes
    finally:
        tier.close()
    if got_toks != ref_toks:
        for i, (a, b) in enumerate(zip(ref_toks, got_toks)):
            if a != b:
                print(f"mp-smoke: FAIL — request {i} diverged across "
                      f"the process boundary: engine={a} tier={b}",
                      file=sys.stderr)
        return 1
    if handoffs < 1:
        print("mp-smoke: FAIL — a 1:1 tier completed without a single "
              "KV handoff (the wire path was never exercised)",
              file=sys.stderr)
        return 1
    print(f"mp-smoke: OK — {len(prompts)} streams identical through a "
          f"2-process 1:1 tier ({handoffs} KV handoffs, {wire} bytes "
          f"over the wire)")
    return 0


def spec_compare(args, store, trials: int = 3) -> int:
    """``--spec-compare``: the SAME Poisson workload through a plain
    engine and a self-speculation verify-k engine (the PR 12-era
    baseline vs ISSUE 13), one ``serve_load`` record each, paired by a
    shared ``spec_pair_id`` — the committed pair is the frozen evidence
    tier-1 asserts the end-to-end tokens/s win from
    (tests/test_spec.py, same contract as the ratio-sweep records).

    Trials are INTERLEAVED (plain, spec, plain, spec, ...) and each
    side records its median-tokens/s run: single back-to-back passes on
    a shared CPU box drift by more than the effect under measurement,
    and an interleaved median is evidence where an A-then-B pair is
    weather."""
    from singa_tpu.obs import record as obs_record
    from singa_tpu.serve import ServeEngine
    from singa_tpu.serve.metrics import ServeMetrics

    m = _build_model()
    _resolve_serve_knobs(args, m)
    new_tokens = tuple(int(t) for t in args.new_tokens.split(",")
                       if t.strip())
    prompt_lens = tuple(int(t) for t in args.prompt_lens.split(",")
                        if t.strip())
    pair_id = obs_record.new_run_id("specpair")
    variants = (0, args.spec_k or 3)
    engines = {}
    for spec_k in variants:
        spec = _spec_kwargs(spec_k, m)
        eng = ServeEngine(m, args.num_slots, args.max_len,
                          block_size=args.block_size,
                          num_blocks=args.num_blocks,
                          share_prefix=not args.no_share,
                          max_queue=args.max_queue,
                          backoff_base=0.005, backoff_max=0.05,
                          max_recoveries=100, record_store=store, **spec)
        # warm the programs so neither side pays a mid-run compile
        eng.submit(build_workload(1, 1.0, args.seed + 1,
                                  vocab=m.cfg.vocab_size)[0].prompt,
                   max_new_tokens=2)
        eng.run_until_idle()
        engines[spec_k] = eng
    runs = {spec_k: [] for spec_k in variants}
    for trial in range(max(1, trials)):
        for spec_k in variants:
            eng = engines[spec_k]
            eng.metrics = ServeMetrics(flight=eng.flight)
            wl = build_workload(args.requests, args.rate, args.seed,
                                prompt_lens=prompt_lens,
                                new_tokens=new_tokens,
                                tenants=args.tenants,
                                shared_len=args.shared_prefix,
                                vocab=m.cfg.vocab_size)
            runs[spec_k].append(run_load(eng, wl,
                                         deadline_s=args.deadline))
    rows = []
    for seq, spec_k in enumerate(variants):
        ordered = sorted(runs[spec_k], key=lambda p: p["tokens_per_s"])
        payload = ordered[len(ordered) // 2]       # median trial
        payload["spec_pair_id"] = pair_id
        payload["spec_seq"] = seq
        payload["spec_k"] = spec_k
        payload["spec_trials"] = len(ordered)
        rows.append(payload)
        print(f"# {'spec_k=' + str(spec_k) if spec_k else 'plain'}  "
              f"tokens/s={payload['tokens_per_s']} (median of "
              f"{len(ordered)})  ttft_p99={payload['ttft_p99_ms']} ms"
              + (f"  accept_rate={payload['accept_rate']}"
                 f"  tokens/dispatch={payload['tokens_per_dispatch']}"
                 if spec_k else ""), file=sys.stderr)
        print(json.dumps(payload, indent=2))
        if store is not None:
            append_record(payload, store,
                          prefix=f"load-spec{spec_k}")
    plain_tps, spec_tps = (r["tokens_per_s"] for r in rows)
    print(f"# spec vs plain tokens/s: {spec_tps} vs {plain_tps} "
          f"({spec_tps / plain_tps:.2f}x, pair {pair_id})",
          file=sys.stderr)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="open-loop Poisson traffic through the paged "
                    "serving engine or a disaggregated prefill/decode "
                    "tier (SLO readout + serve_load record)")
    ap.add_argument("--requests", type=int, default=60)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="offered arrivals/s (push past capacity to "
                         "study overload)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tenants", type=int, default=3,
                    help="tenant count for the shared-prefix mix "
                         "(0 = no sharing)")
    ap.add_argument("--shared-prefix", type=int, default=16,
                    help="system-prompt tokens shared per tenant")
    ap.add_argument("--deadline", type=float, default=30.0,
                    help="per-request SLO deadline (s); drives "
                         "shedding under overload")
    ap.add_argument("--new-tokens", default="4,8,16",
                    help="comma-separated generation-budget mix drawn "
                         "per request (generation-heavy mixes sharpen "
                         "the decode-side of a ratio sweep)")
    ap.add_argument("--prompt-lens", default="6,10,16,24",
                    help="comma-separated private-suffix prompt-length "
                         "mix (short prompts + long generations isolate "
                         "the decode path a --spec-k comparison is "
                         "about)")
    ap.add_argument("--num-slots", type=int, default=None,
                    help="decode-batch slots (default: the committed "
                         "best-config table's value for this model+"
                         "platform, else 8)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission-queue capacity (default: the "
                         "engine's 2*num_slots)")
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=None,
                    help="paged-KV block size (default: the committed "
                         "best-config table's value for this model+"
                         "platform, else 8)")
    ap.add_argument("--num-blocks", type=int, default=None)
    ap.add_argument("--no-share", action="store_true",
                    help="disable prefix-cache sharing in the engine")
    ap.add_argument("--store", default=None,
                    help="run-record store path (default: "
                         "runs/records.jsonl)")
    ap.add_argument("--no-record", action="store_true")
    ap.add_argument("--prefill-workers", type=int, default=0,
                    help="disaggregated tier: prefill pool size "
                         "(with --decode-workers; 0 = single engine)")
    ap.add_argument("--decode-workers", type=int, default=0,
                    help="disaggregated tier: decode pool size")
    ap.add_argument("--tenant-quota", type=int, default=None,
                    help="per-tenant in-flight quota at the tier door "
                         "(Router only)")
    ap.add_argument("--ratio-sweep", default=None, metavar="N:M,...",
                    help="run the SAME workload through each "
                         "prefill:decode ratio (e.g. '3:1,2:2,1:3'), "
                         "emitting one serve_load record per point — "
                         "the independent-scaling measurement")
    ap.add_argument("--disagg-smoke", action="store_true",
                    help="CI smoke: 1:1 tier streams asserted "
                         "identical to a single engine (8 requests); "
                         "exits non-zero on divergence")
    ap.add_argument("--procs", action="store_true",
                    help="run the tier MULTI-PROCESS (serve.net): each "
                         "worker a ServeEngine in its own OS process, "
                         "KV handoffs over the digest-checked wire "
                         "codec; records stamp the transport trio plus "
                         "procs/host_cores provenance")
    ap.add_argument("--elastic-max", type=int, default=0,
                    help="with --procs: cap for an ElasticPolicy that "
                         "grows/shrinks the pools at runtime from "
                         "backpressure signals (0 = fixed pools)")
    ap.add_argument("--mp-smoke", action="store_true",
                    help="CI smoke: 2-process 1:1 tier streams "
                         "asserted identical to a single in-process "
                         "engine (6 requests, >=1 wire handoff); "
                         "exits non-zero on divergence")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: propose/verify k "
                         "tokens per round through a self-speculation "
                         "draft (0 = plain decode)")
    ap.add_argument("--spec-compare", action="store_true",
                    help="run the SAME workload through a plain and a "
                         "speculative engine, one serve_load record "
                         "each paired by spec_pair_id — the committed "
                         "tokens/s-win evidence")
    ap.add_argument("--spec-smoke", action="store_true",
                    help="CI smoke: self-speculation streams asserted "
                         "identical to generate() and a plain engine, "
                         "accept rate asserted 1.0; exits non-zero on "
                         "divergence")
    ap.add_argument("--spill-smoke", action="store_true",
                    help="CI smoke: shrunk arena + host spill store; "
                         "streams asserted identical to a roomy "
                         "engine, with blocks spilled AND a prefix "
                         "re-hit served from the spill store; exits "
                         "non-zero on divergence")
    ap.add_argument("--chaos-campaign", action="store_true",
                    help="delegate to tools.chaosd: a seeded "
                         "deterministic campaign of worker kills/"
                         "hangs, fault plans and resizes against a "
                         "live multi-process tier under this Poisson "
                         "load shape, committing a chaos_campaign "
                         "record (see python -m tools.chaosd --help "
                         "for the full knob set)")
    ap.add_argument("--chaos-events", type=int, default=6,
                    help="with --chaos-campaign: schedule length")
    ap.add_argument("--kv-dtype", default=None,
                    choices=("f32", "int8"),
                    help="KV arena storage format (plain engine only; "
                         "int8 = quantize-on-scatter blocks with "
                         "per-position scales)")
    ap.add_argument("--spill-blocks", type=int, default=None,
                    help="host spill-store capacity in blocks (plain "
                         "engine only; default: no spill tier)")
    args = ap.parse_args(argv)

    import jax

    from singa_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache(jax.devices()[0].platform)

    if args.disagg_smoke:
        return disagg_smoke()
    if args.mp_smoke:
        return mp_smoke()
    if args.spec_smoke:
        return spec_smoke()
    if args.spill_smoke:
        return spill_smoke()
    if args.chaos_campaign:
        from tools import chaosd
        cargv = ["--seed", str(args.seed),
                 "--events", str(args.chaos_events),
                 "--rate", str(args.rate)]
        if args.prefill_workers:
            cargv += ["--prefill", str(args.prefill_workers)]
        if args.decode_workers:
            cargv += ["--decode", str(args.decode_workers)]
        if args.store:
            cargv += ["--store", args.store]
        if args.no_record:
            cargv += ["--no-record"]
        return chaosd.main(cargv)
    if args.spec_k < 0:
        ap.error("--spec-k must be >= 0")
    if ((args.kv_dtype or args.spill_blocks) and
            (args.prefill_workers or args.decode_workers or
             args.ratio_sweep or args.spec_compare or args.procs)):
        ap.error("--kv-dtype/--spill-blocks drive a plain engine — "
                 "not a tier, sweep, or --spec-compare")
    if args.procs and args.spec_compare:
        ap.error("--spec-compare is an in-process A/B (interleaved "
                 "trials on shared engines) — it has no --procs mode")
    if args.procs and not (args.ratio_sweep or
                           (args.prefill_workers and
                            args.decode_workers)):
        ap.error("--procs needs a tier: --prefill-workers/"
                 "--decode-workers or --ratio-sweep")
    if args.procs and args.tenant_quota is not None:
        ap.error("--tenant-quota is the in-process Router's door — "
                 "the multi-process tier has no per-tenant quota yet")
    if args.elastic_max and not args.procs:
        ap.error("--elastic-max resizes worker PROCESSES — it needs "
                 "--procs")

    from singa_tpu.obs import record as obs_record
    from singa_tpu.serve import ServeEngine

    # one resolved store for BOTH record producers: the engine's
    # incident entries (quarantine/recovery under chaos) and the final
    # serve_load headline — otherwise a default-args chaos soak would
    # silently drop its incident evidence
    store = (None if args.no_record else
             args.store or os.path.join(_REPO, obs_record.DEFAULT_STORE))

    if args.spec_compare:
        return spec_compare(args, store)

    m = _build_model()
    _resolve_serve_knobs(args, m)
    new_tokens = tuple(int(t) for t in args.new_tokens.split(",")
                       if t.strip())
    prompt_lens = tuple(int(t) for t in args.prompt_lens.split(",")
                        if t.strip())

    if args.ratio_sweep and args.procs:
        points = parse_ratios(args.ratio_sweep)
        # no template sharing across process boundaries: every point
        # spawns fresh workers that each compile their own program set
        # (the per-point spawn+compile cost is the price of real
        # process isolation, and it stays OUT of run_load's wall)
        sweep_id = obs_record.new_run_id("mpsweep")
        rows = []
        for i, (n, mdec) in enumerate(points):
            tier = _build_proc_tier(n, mdec, args, store)
            try:
                wl = build_workload(args.requests, args.rate, args.seed,
                                    prompt_lens=prompt_lens,
                                    new_tokens=new_tokens,
                                    tenants=args.tenants,
                                    shared_len=args.shared_prefix,
                                    vocab=m.cfg.vocab_size)
                payload = run_load(tier, wl, deadline_s=args.deadline)
                _stamp_mp(payload, tier, n + mdec)
            finally:
                tier.close()
            # mp_sweep_id, NOT sweep_id: the in-process ratio-direction
            # assertion (tests/test_disagg.py) groups by sweep_id and
            # must never adopt points measured across process
            # boundaries on an unknown core budget
            payload["mp_sweep_id"] = sweep_id
            payload["mp_sweep_seq"] = i
            rows.append((n, mdec, payload))
            print(f"# mp ratio {n}:{mdec} ({n + mdec} procs, "
                  f"{payload['host_cores']} cores)  "
                  f"ttft_p99={payload['ttft_p99_ms']} ms  "
                  f"tokens/s={payload['tokens_per_s']}  "
                  f"handoffs={payload['handoffs']}  "
                  f"wire_bytes={payload['handoff_wire_bytes']}",
                  file=sys.stderr)
            print(json.dumps(payload, indent=2))
            if store is not None:
                append_record(payload, store, prefix=f"mpload{i}",
                              tier=tier)
        if store is not None:
            print(f"# {len(rows)} serve_load entries (mp sweep "
                  f"{sweep_id}) appended to {store}", file=sys.stderr)
        return 0

    if args.ratio_sweep:
        points = parse_ratios(args.ratio_sweep)
        # every point's tier shares ONE template engine's compiled
        # programs, so the sweep pays one compile no matter how many
        # ratios it visits — and a shared sweep_id groups the points
        # for the direction assertion in tests/test_disagg.py.  The
        # template must carry the same draft/spec_k the workers get:
        # programs= sharing validates draft identity
        spec = _spec_kwargs(args.spec_k, m)
        template = ServeEngine(m, args.num_slots, args.max_len,
                               block_size=args.block_size,
                               num_blocks=args.num_blocks,
                               share_prefix=not args.no_share, **spec)
        # warm every program (incl. the lazily-compiled handoff
        # gather) through a throwaway 1:1 tier, so the first sweep
        # point does not pay a mid-run compile the others skip
        warm = _build_tier(m, 1, 1, args, None, template=template)
        warm.submit(build_workload(1, 1.0, args.seed + 1,
                                   vocab=m.cfg.vocab_size)[0].prompt,
                    max_new_tokens=2)
        warm.run_until_idle()
        sweep_id = obs_record.new_run_id("sweep")
        rows = []
        for i, (n, mdec) in enumerate(points):
            tier = _build_tier(m, n, mdec, args, store,
                               template=template)
            wl = build_workload(args.requests, args.rate, args.seed,
                                prompt_lens=prompt_lens,
                                new_tokens=new_tokens,
                                tenants=args.tenants,
                                shared_len=args.shared_prefix,
                                vocab=m.cfg.vocab_size)
            payload = run_load(tier, wl, deadline_s=args.deadline,
                               pass_tenant=args.tenant_quota is not None)
            payload["sweep_id"] = sweep_id
            payload["sweep_seq"] = i
            rows.append((n, mdec, payload))
            print(f"# ratio {n}:{mdec}  ttft_p99={payload['ttft_p99_ms']}"
                  f" ms  tokens/s={payload['tokens_per_s']}  "
                  f"handoffs={payload['handoffs']}", file=sys.stderr)
            print(json.dumps(payload, indent=2))
            if store is not None:
                append_record(payload, store)
        if store is not None:
            print(f"# {len(rows)} serve_load entries (sweep {sweep_id}) "
                  f"appended to {store}", file=sys.stderr)
        return 0

    if args.prefill_workers or args.decode_workers:
        if args.prefill_workers < 1 or args.decode_workers < 1:
            ap.error("a tier needs --prefill-workers >= 1 AND "
                     "--decode-workers >= 1")
        if args.procs:
            policy = None
            if args.elastic_max:
                from singa_tpu.serve import ElasticPolicy
                policy = ElasticPolicy(max_total=args.elastic_max)
            eng = _build_proc_tier(args.prefill_workers,
                                   args.decode_workers, args, store,
                                   policy=policy)
        else:
            eng = _build_tier(m, args.prefill_workers,
                              args.decode_workers, args, store)
    else:
        if args.tenant_quota is not None:
            ap.error("--tenant-quota needs a tier "
                     "(--prefill-workers/--decode-workers) — a plain "
                     "engine has no tenant door")
        spec = _spec_kwargs(args.spec_k, m)
        eng = ServeEngine(m, args.num_slots, args.max_len,
                          block_size=args.block_size,
                          num_blocks=args.num_blocks,
                          share_prefix=not args.no_share,
                          max_queue=args.max_queue,
                          backoff_base=0.005, backoff_max=0.05,
                          # a chaos soak may recover many times; the
                          # engine-default budget of 2 is tuned for unit
                          # scenarios, not sustained injection
                          max_recoveries=100,
                          record_store=store,
                          kv_dtype=args.kv_dtype,
                          spill_blocks=args.spill_blocks, **spec)
    wl = build_workload(args.requests, args.rate, args.seed,
                        prompt_lens=prompt_lens,
                        new_tokens=new_tokens,
                        tenants=args.tenants,
                        shared_len=args.shared_prefix,
                        vocab=m.cfg.vocab_size)
    payload = run_load(eng, wl, deadline_s=args.deadline,
                       pass_tenant=args.tenant_quota is not None)
    if args.procs:
        _stamp_mp(payload, eng,
                  args.prefill_workers + args.decode_workers)
        eng.close()
    print(json.dumps(payload, indent=2))
    if store is not None:
        append_record(payload, store,
                      prefix="mpload" if args.procs else "load",
                      tier=eng if args.procs else None)
        print(f"# serve_load entry appended to {store}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
