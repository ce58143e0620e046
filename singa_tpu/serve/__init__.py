"""singa_tpu.serve — continuous-batching inference engine (ISSUE 2).

The serving counterpart of the Graph/Scheduler training layer: the
whole serving lifetime runs through exactly two compiled XLA programs.

* :mod:`~singa_tpu.serve.slots` — :class:`BlockPool`, the PAGED
  KV-cache arena built on ``ops/kv_cache``: fixed-size blocks behind
  per-request block tables (host numpy, like the per-slot pos/active
  vectors: arguments of every dispatch), chain-hashed prefix-cache
  sharing with refcounts, and an evictable LRU of resident prefixes.
  Admit/evict/grow are pure index updates, freed blocks are reused
  without recompilation.  (The PR 2 fixed-slot ``SlotPool`` is gone —
  a default-sized ``BlockPool`` has capacity parity with it.)
* :mod:`~singa_tpu.serve.scheduler` — FIFO queue, admission control
  (:class:`QueueFull` backpressure), per-request deadlines and token
  budgets, eviction policy.
* :mod:`~singa_tpu.serve.engine` — :class:`ServeEngine`:
  ``submit() / step() / run_until_idle() / drain() / close()``,
  streaming token callbacks, greedy decode token-identical to
  ``GenerateMixin.generate``; resilience (ISSUE 4): bounded-backoff
  retry of transient dispatch failures, quarantine of requests that
  repeatedly poison prefill (a ``failed`` handle status, not an engine
  crash), deadline-aware overload shedding, and a Heartbeat-driven
  arena-recovery path (see docs/robustness.md).
* :mod:`~singa_tpu.serve.metrics` — queue/slot gauges, admit/reject/
  evict counters, TTFT and per-token latency histograms through
  ``obs.events``.
* :mod:`~singa_tpu.serve.spec` — speculative decoding (ISSUE 13):
  draft-model propose-k / target-model verify-k as a third compiled
  program over the same paged arena (the draft's KV blocks ride the
  same block tables); accepted runs are the target's own greedy picks
  (bitwise identical to ``generate()`` by construction), rejected
  positions roll back by position/limit truncation, and an injected
  ``serve.verify`` fault falls back to plain decode for that tick.
* :mod:`~singa_tpu.serve.disagg` — disaggregated serving (ISSUE 12):
  separately scaled prefill/decode worker pools (engines sharing ONE
  set of compiled programs) behind an SLO-aware :class:`Router` with
  per-tenant quotas, KV block handoff between arenas, and worker-death
  re-routing with bitwise-identical streams.
* :mod:`~singa_tpu.serve.net` — multi-process disaggregated serving
  (ISSUE 18): the same tier with each worker a ``ServeEngine`` in its
  own OS process behind a framed local-socket RPC, KV handoff over a
  versioned digest-checked wire codec (a torn transfer is never
  injected — it replays), and elastic grow/shrink of either pool at
  runtime (:class:`ProcRouter` / :func:`build_proc_pools` /
  :class:`ElasticPolicy`).

See docs/serving.md for the architecture, the slot lifecycle and the
backpressure semantics.
"""

from .disagg import (QuotaExceeded, Router, SLOClass, Worker,
                     build_pools)
from .engine import EngineClosed, ServeEngine, SharedPrograms
from .net import (ElasticPolicy, ProcHandle, ProcRouter, WorkerDied,
                  WorkerProc, build_proc_pools)
from .scheduler import (EVICTED, FAILED, FINISHED, QUEUED, RUNNING,
                        QueueFull, RequestHandle, Scheduler)
from .slots import BlockPool

__all__ = ["ServeEngine", "BlockPool", "Scheduler", "RequestHandle",
           "QueueFull", "EngineClosed", "SharedPrograms",
           "Router", "SLOClass", "QuotaExceeded", "Worker",
           "build_pools",
           "ProcRouter", "ProcHandle", "WorkerProc", "WorkerDied",
           "build_proc_pools", "ElasticPolicy",
           "QUEUED", "RUNNING", "FINISHED", "EVICTED", "FAILED"]
