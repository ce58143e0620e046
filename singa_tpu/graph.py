"""Graph capture bookkeeping — the Python face of the Graph/Scheduler
(capability parity: BASELINE.json:5 "the Graph/Scheduler that buffers
singa.autograd ops compiles the captured computational graph into a
single XLA HLO module").

In this framework the *capture* is a jax trace of the user's imperative
``train_one_batch`` and the *schedule* is XLA's — but we keep a real
graph object: the closed jaxpr (op list, topological order) plus the
lowered/compiled artifacts, so users can inspect what was captured, dump
HLO, and get cost analysis (FLOPs → MFU accounting, BASELINE.json:5
"≥45% MFU" target).  The native C++ scheduler (csrc/scheduler.cc) is fed
from this same captured graph for host-side execution planning.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

__all__ = ["CapturedGraph", "Schedule", "reset_graph"]


class CapturedGraph:
    """A captured training/eval step: jaxpr + lowered + compiled handles."""

    def __init__(self, name: str, jaxpr=None, lowered=None, compiled=None,
                 jaxpr_thunk=None):
        self.name = name
        self._jaxpr = jaxpr
        self._jaxpr_thunk = jaxpr_thunk
        self.lowered = lowered
        self.compiled = compiled

    @property
    def jaxpr(self):
        if self._jaxpr is None and self._jaxpr_thunk is not None:
            self._jaxpr = self._jaxpr_thunk()
            self._jaxpr_thunk = None
        return self._jaxpr

    # -- introspection --------------------------------------------------------
    @property
    def num_ops(self) -> int:
        if self.jaxpr is not None:
            return _count_eqns(self.jaxpr.jaxpr)
        # fall back to counting HLO instructions in the lowered module
        txt = self.hlo_text()
        return sum(1 for line in txt.splitlines() if " = " in line)

    def op_types(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        if self.jaxpr is not None:
            _collect_ops(self.jaxpr.jaxpr, out)
        return out

    def hlo_text(self) -> str:
        if self.lowered is None:
            return ""
        return self.lowered.as_text()

    def compiled_hlo(self) -> str:
        if self.compiled is None:
            return ""
        return self.compiled.as_text()

    def cost_analysis(self) -> Dict[str, Any]:
        """XLA cost analysis of the compiled module (flops, bytes)."""
        if self.compiled is None:
            return {}
        from .obs import events as obs_events
        with obs_events.span("graph.cost_analysis", graph=self.name):
            return dict(self.compiled.cost_analysis())

    def flops(self) -> float:
        return float(self.cost_analysis().get("flops", 0.0))

    def memory_analysis(self) -> Dict[str, Any]:
        if self.compiled is None:
            return {}
        ma = self.compiled.memory_analysis()
        return {k: getattr(ma, k) for k in dir(ma) if not k.startswith("_")}

    def save_hlo(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.hlo_text())

    # -- native scheduler bridge ---------------------------------------------
    def schedule(self):
        """Feed the captured op graph to the native C++ scheduler
        (csrc/scheduler.cc): deterministic topological order + first-fit
        arena plan for a serial host replay.  Returns a Schedule with
        .order, .arena_bytes, .num_nodes — the reference Graph/Scheduler's
        introspection surface, TPU-side scheduling stays XLA's."""
        from . import _core
        from .obs import events as obs_events
        if not _core.available():
            raise RuntimeError("native core unavailable")
        cj = self.jaxpr
        if cj is None:
            raise RuntimeError("no jaxpr captured for this graph")
        jaxpr = cj.jaxpr
        with obs_events.span("graph.schedule", graph=self.name,
                             eqns=len(jaxpr.eqns)):
            ng = _core.NativeGraph()
            buf_ids = {}

            def bid(v):
                key = id(v)
                if key not in buf_ids:
                    buf_ids[key] = len(buf_ids)
                return buf_ids[key]

            for v in jaxpr.invars:
                bid(v)
            for eqn in jaxpr.eqns:
                # Literals carry .val; Vars don't — version-stable check
                ins = [bid(v) for v in eqn.invars if not hasattr(v, "val")]
                outs = [bid(v) for v in eqn.outvars]
                sizes = [int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                         for v in eqn.outvars]
                ng.add_node(eqn.primitive.name, ins, outs, sizes)
            # sink node: jaxpr outputs are read after the last eqn, so
            # their buffers must stay live to the end of the plan (replay
            # returns arena views of them)
            sink_ins = [bid(v) for v in jaxpr.outvars
                        if not hasattr(v, "val")]
            if sink_ins:
                ng.add_node("__sink__", sink_ins, [], [], 0)
            order = ng.toposort()
            arena, offsets = ng.plan_memory()
            return Schedule(order=order, arena_bytes=arena,
                            num_nodes=ng.num_nodes, buffer_offsets=offsets,
                            closed_jaxpr=cj, var_buf=buf_ids)

    def __repr__(self):
        return f"<CapturedGraph {self.name}: {self.num_ops} ops>"


class Schedule:
    """Native-planned execution schedule: deterministic topological order
    plus the first-fit arena plan from csrc/scheduler.cc — and a host
    REPLAY that consumes both (SURVEY.md §5: the scheduler's
    single-threaded deterministic replay mode).  Replay executes the
    captured jaxpr eqn-by-eqn in the planned order, writes f32 results
    into their planned arena offsets (so an unsound liveness plan
    corrupts outputs and fails the equivalence tests), and dispatches
    the hot elementwise/GEMM primitives to the native csrc kernels."""

    def __init__(self, order, arena_bytes, num_nodes, buffer_offsets,
                 closed_jaxpr=None, var_buf=None):
        self.order = order
        self.arena_bytes = arena_bytes
        self.num_nodes = num_nodes
        self.buffer_offsets = buffer_offsets
        self.closed_jaxpr = closed_jaxpr
        self.var_buf = var_buf or {}
        self.native_hits = 0

    def replay(self, *args, use_native: bool = True):
        """Serial host execution of the captured graph in planned order.

        `args` match the jaxpr invars (flattened). Returns the flat
        output list. Single-threaded and deterministic by construction —
        the race-detection story for the host path."""
        import jax.numpy as jnp

        from . import _core

        cj = self.closed_jaxpr
        if cj is None:
            raise RuntimeError("schedule has no captured jaxpr")
        jaxpr = cj.jaxpr
        if len(args) != len(jaxpr.invars):
            raise ValueError(f"replay needs {len(jaxpr.invars)} args, "
                             f"got {len(args)}")
        native_ok = use_native and _core.available()
        arena = (np.zeros(self.arena_bytes, np.uint8)
                 if self.arena_bytes else None)
        env = {}
        for v, c in zip(jaxpr.constvars, cj.consts):
            env[id(v)] = c
        for v, a in zip(jaxpr.invars, args):
            env[id(v)] = np.asarray(a)

        def read(v):
            if hasattr(v, "val"):
                return v.val
            return env[id(v)]

        def place(v, value):
            """Store an output, into its planned arena slot when f32."""
            aval = v.aval
            off = self.buffer_offsets.get(self.var_buf.get(id(v)))
            if (arena is not None and off is not None
                    and aval.dtype == np.float32 and aval.shape):
                n = int(np.prod(aval.shape))
                view = np.frombuffer(arena, np.float32, count=n,
                                     offset=off).reshape(aval.shape)
                view[...] = np.asarray(value, np.float32)
                env[id(v)] = view
            else:
                env[id(v)] = np.asarray(value)

        self.native_hits = 0
        for idx in self.order:
            if idx >= len(jaxpr.eqns):
                continue              # liveness sink node, nothing to run
            eqn = jaxpr.eqns[idx]
            vals = [read(v) for v in eqn.invars]
            outs = self._native_eqn(eqn, vals) if native_ok else None
            if outs is None:
                subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
                res = eqn.primitive.bind(
                    *subfuns, *[jnp.asarray(v) for v in vals], **bind_params)
                outs = list(res) if eqn.primitive.multiple_results else [res]
            else:
                self.native_hits += 1
            for v, o in zip(eqn.outvars, outs):
                place(v, o)
        # copy at the boundary: outputs must not be aliases into the
        # (possibly large, mutable) shared arena
        return [np.array(read(v)) for v in jaxpr.outvars]

    @staticmethod
    def _native_eqn(eqn, vals):
        """Dispatch an eqn to csrc kernels; None -> no native lowering."""
        from . import _core
        name = eqn.primitive.name
        if any(not isinstance(v, np.ndarray) or v.dtype != np.float32
               for v in vals):
            return None
        if name in ("add", "sub", "mul", "div") and len(vals) == 2 \
                and vals[0].shape == vals[1].shape and vals[0].shape:
            return [getattr(_core, name)(vals[0], vals[1])]
        if name == "exp" and vals[0].shape:
            return [_core.exp(vals[0])]
        if name == "tanh" and vals[0].shape:
            return [_core.tanh(vals[0])]
        if name == "logistic" and vals[0].shape:
            return [_core.sigmoid(vals[0])]
        if name == "dot_general":
            dn = eqn.params["dimension_numbers"]
            # plain (m,k)@(k,n) f32 — native f32 FMA gemm matches any XLA
            # CPU precision setting for f32 inputs
            if (dn == (((1,), (0,)), ((), ()))
                    and vals[0].ndim == 2 and vals[1].ndim == 2
                    and np.dtype(eqn.params.get("preferred_element_type")
                                 or np.float32) == np.float32):
                return [_core.gemm(vals[0], vals[1])]
        return None

    def __repr__(self):
        return (f"<Schedule nodes={self.num_nodes} "
                f"arena={self.arena_bytes}B>")


def _count_eqns(jaxpr) -> int:
    n = len(jaxpr.eqns)
    for eq in jaxpr.eqns:
        for sub in _sub_jaxprs(eq):
            n += _count_eqns(sub)
    return n


def _collect_ops(jaxpr, out: Dict[str, int]) -> None:
    for eq in jaxpr.eqns:
        out[eq.primitive.name] = out.get(eq.primitive.name, 0) + 1
        for sub in _sub_jaxprs(eq):
            _collect_ops(sub, out)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        if hasattr(v, "jaxpr"):
            yield v.jaxpr
        elif isinstance(v, (list, tuple)):
            for x in v:
                if hasattr(x, "jaxpr"):
                    yield x.jaxpr


def reset_graph(device=None) -> None:
    """Drop captured graphs so the next step re-captures (reference
    Device.ResetGraph). Models track their own executors; this clears the
    process-wide registry."""
    from . import model as model_mod
    model_mod._invalidate_all_graphs()
