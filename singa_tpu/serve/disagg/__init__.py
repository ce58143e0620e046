"""singa_tpu.serve.disagg — disaggregated serving (ISSUE 12).

Prefill and decode live in opposite roofline classes (prefill
compute-bound, decode memory-bound), so one engine co-scheduling both wastes whichever resource the traffic
mix doesn't saturate.  This package splits them into separately scaled
pools behind an SLO-aware front door:

* :mod:`~singa_tpu.serve.disagg.worker` — :class:`Worker` (one
  :class:`~singa_tpu.serve.engine.ServeEngine` + a ``prefill`` /
  ``decode`` role) and :func:`build_pools`, which constructs N + M
  same-config workers sharing ONE set of compiled programs
  (``SharedPrograms``) — a whole tier costs one engine's compiles and
  the per-worker two-program invariant is asserted on the shared
  caches.
* :mod:`~singa_tpu.serve.disagg.handoff` — the KV block handoff: a
  finished prefill is just blocks + a table row, gathered through the
  engine's optional third compiled program (``handoff_gather``) and
  scattered into the destination pool block-by-block; refcounts and
  prefix-cache chain keys transfer with the blocks, so shared prefixes
  cross once per decode worker, not once per request.
* :mod:`~singa_tpu.serve.disagg.router` — :class:`Router`:
  per-tenant quotas, :class:`SLOClass` deadlines enforced by the
  existing scheduler backpressure/shed machinery, least-loaded
  routing, the ``serve.handoff``/``serve.router`` fault sites
  (worker death → re-route, re-prefill from prompt, streams bitwise
  identical), and one trace id per request across every worker it
  touches (``tools/obsq trace``).

``tools/loadgen.py --prefill-workers N --decode-workers M
[--ratio-sweep N:M,...]`` drives the tier open-loop and commits
``serve_load`` records with the per-pool fields; see
docs/serving.md ("Disaggregated tier").
"""

from .handoff import HandoffPackage
from .router import QuotaExceeded, Router, SLOClass, TierMetrics
from .worker import DECODE, PREFILL, Worker, build_pools

__all__ = ["Router", "SLOClass", "QuotaExceeded", "TierMetrics",
           "Worker", "build_pools", "HandoffPackage",
           "PREFILL", "DECODE"]
