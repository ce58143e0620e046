"""singa_tpu.autotune — the record-driven autotuner (ISSUE 14).

Closes the loop ROADMAP item 4 names: the obs record store already
holds analytic per-program cost numbers (``tools.lint.cost.
summarize_cost()``, appended on every bench run) and a measured bench/
serve trajectory; this package turns them into config decisions —

* :mod:`~singa_tpu.autotune.knobs` — the closed registry of tunable
  knobs per domain (train: batch / ce_chunk / int8_ring; serve:
  num_slots / block_size / spec_k) and their hand-carried defaults;
* :mod:`~singa_tpu.autotune.sweep` — knob points -> ``autotune_sweep``
  records under one ``sweep_id`` (+ the ``point = -1`` fit record);
* :mod:`~singa_tpu.autotune.predictor` — deterministic ridge /
  nearest-neighbor fit with an exact leave-one-out error report;
* :mod:`~singa_tpu.autotune.table` — the committed best-config table
  (``tools/autotune/data/best.json``) that bench.py, ServeEngine and
  tools/loadgen.py consult by default (explicit values always win; a
  missing table falls back to today's constants, loudly once).

Front door: ``python -m tools.autotune`` (sweep / fit / best / check /
smoke).  Everything here is host-only — no jax import at package
import time.
"""

from . import knobs, predictor, sweep, table  # noqa: F401
from .knobs import DEFAULTS, KNOBS, OBJECTIVES, KnobError  # noqa: F401
from .predictor import Predictor, best_point, fit_points  # noqa: F401
from .table import (best_knobs, load_table, model_key,  # noqa: F401
                    pick_spec_k, resolve, resolve_spec_k)

__all__ = ["knobs", "predictor", "sweep", "table", "KNOBS", "DEFAULTS",
           "OBJECTIVES", "KnobError", "Predictor", "fit_points",
           "best_point", "model_key", "best_knobs", "resolve",
           "resolve_spec_k", "pick_spec_k", "load_table"]
