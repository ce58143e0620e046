"""singa_tpu — a TPU-native distributed deep-learning training system.

Scope (reference: /root/reference README.md:1-4 — "Distributed deep
learning training system"; capability contract /root/repo/BASELINE.json:5):
the full SINGA surface — device / tensor / autograd / layer / model /
opt(DistOpt) / sonnx — rebuilt TPU-first on JAX/XLA/Pallas: imperative
Python API on top, single-XLA-module compiled training steps underneath,
collectives over ICI via mesh axes.

The `singa` package alias re-exports these modules so reference user
scripts run with only the device line changed.
"""

__version__ = "0.3.0"

from . import device
from . import proto
from . import tensor
from . import autograd
from . import layer
from . import model
from . import opt
from . import graph
from . import obs
from . import faults  # eager: SINGA_FAULTS env activation happens here
from . import ops
from . import parallel
from . import utils

__all__ = ["device", "proto", "tensor", "autograd", "layer", "model", "opt",
           "graph", "obs", "faults", "ops", "parallel", "utils", "sonnx",
           "models", "serve", "train"]


def __getattr__(name):
    # lazy: sonnx pulls in the onnx proto machinery, models pulls model
    # zoo, serve pulls the inference engine, train pulls the run
    # orchestrator
    if name in ("sonnx", "models", "serve", "train"):
        import importlib
        mod = importlib.import_module("." + name, __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(name)
