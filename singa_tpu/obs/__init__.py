"""singa_tpu.obs — durable run records + structured telemetry.

The observability subsystem (ISSUE 1):

* :mod:`~singa_tpu.obs.schema` — versioned field contracts for every
  committed telemetry artifact; ``require()`` gives consumers
  named-field errors instead of KeyError.
* :mod:`~singa_tpu.obs.record` — :class:`RunRecord`, the append-only
  JSONL store of bench/session runs keyed by
  ``(run_id, platform, smoke)`` with atomic write-temp-then-rename;
  smoke/CPU entries can never overwrite or shadow on-chip entries.
* :mod:`~singa_tpu.obs.events` — ``span`` / ``counter`` / ``gauge``
  with a JSONL sink, wired into the compiled-step, collective,
  grad-sync and serve-engine hot paths; every span is also a
  ``jax.profiler.TraceAnnotation``, so a profiler trace shows it on
  the device ops' clock.
* :mod:`~singa_tpu.obs.trace` — contextvar-carried request/step trace
  contexts (ISSUE 11): every event emitted inside an active trace is
  stamped with its id, spans nest, and worker threads inherit (or
  explicitly drop) the spawner's context.
* :mod:`~singa_tpu.obs.flight` — :class:`FlightRecorder`, the bounded
  in-memory incident ring dumped to ``runs/incidents/`` (and referenced
  from ``incident``/``train_run`` records via ``flight_ref``) when a
  fault fires through to quarantine/recovery/fatal.

``tools/obsq.py`` is the query layer over all three (timeline
rendering, trace-derived SLO recomputation, record trajectories).  See
docs/observability.md for the schema and the smoke-vs-chip protection
rule.
"""

from . import events, flight, record, schema, trace
from .events import (configure, counter, gauge, histogram,
                     histogram_summary, reset_histograms, span)
from .flight import FlightRecorder
from .record import RunRecord, is_onchip_session_doc, new_entry, new_run_id
from .schema import SCHEMA_VERSION, SchemaError, require

__all__ = ["schema", "record", "events", "trace", "flight",
           "FlightRecorder", "RunRecord", "SchemaError",
           "SCHEMA_VERSION", "require", "new_entry", "new_run_id",
           "is_onchip_session_doc", "configure", "counter", "gauge",
           "span", "histogram", "histogram_summary",
           "reset_histograms"]
