"""singa_tpu.model — the Model API + graph executor.

Capability parity: ``singa.model`` (BASELINE.json:5,8 — "singa.model
Graph mode").  The user writes an *imperative* subclass:

    class MLP(model.Model):
        def __init__(self): ...
        def forward(self, x): ...
        def train_one_batch(self, x, y):
            out = self.forward(x)
            loss = autograd.softmax_cross_entropy(out, y)
            self.optimizer(loss)
            return out, loss

and ``compile(..., use_graph=True)`` makes ``train_one_batch`` execute as
ONE compiled XLA module: the executor traces the user's Python —
forward, tape backward, optimizer update, and (with DistOpt) the
gradient all-reduce — into a single jitted function with donated
buffers.  That is exactly the north-star execution model
(BASELINE.json:5: "compiles the captured computational graph into a
single XLA HLO module", allreduce "swapped for XLA collectives over
ICI").

Functionalization: parameters/buffers are held in mutable Tensor objects
whose ``.data`` is rebound during the trace; the executor threads them
in and out of the jitted step (SURVEY.md §7.3 items 1–2).  Graph
invalidation: keyed on input shapes/dtypes + train flag; shape change →
re-capture (ibid.).
"""

from __future__ import annotations

import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd
from . import tensor as tensor_mod
from .graph import CapturedGraph
from .obs import events as obs_events
from .layer import Layer
from .opt import DistOpt, Optimizer
from .tensor import Tensor

__all__ = ["Model", "Module"]

_live_models = weakref.WeakSet()


def _invalidate_all_graphs():
    for m in list(_live_models):
        m._executors.clear()


class Model(Layer):
    """Base model (reference surface: forward / train_one_batch / loss /
    optimizer / compile / save_states / load_states)."""

    def __init__(self, name=None):
        super().__init__(name)
        self.optimizer: Optional[Optimizer] = None
        self.loss_fn: Optional[Callable] = None
        self.graph_mode = False
        self.sequential = False
        self._training = False
        self._executors: Dict[Any, "_StepExecutor"] = {}
        self._compiled_init = False
        self._base_key = jax.random.PRNGKey(np.random.randint(0, 2**31 - 1))
        self._step_count = 0
        _live_models.add(self)

    # -- reference API --------------------------------------------------------
    def set_optimizer(self, opt: Optimizer) -> None:
        self.optimizer = opt

    def set_loss(self, fn) -> None:
        self.loss_fn = fn

    def loss(self, out, ty):
        if self.loss_fn is not None:
            return self.loss_fn(out, ty)
        return autograd.softmax_cross_entropy(out, ty)

    def train(self, mode: bool = True) -> "Model":
        self._training = mode
        autograd.set_training(mode)
        return self

    def eval(self) -> "Model":
        return self.train(False)

    def compile(self, inputs: List[Tensor], is_train: bool = True,
                use_graph: bool = True, sequential: bool = False) -> None:
        """Initialize parameters from example inputs and arm graph mode.

        `sequential` is accepted for reference compatibility (op ordering
        is XLA's concern here)."""
        self.graph_mode = use_graph
        self.sequential = sequential
        self.train(is_train)
        # materialize lazily-created parameters from the example inputs
        prev = autograd.is_training()
        autograd.set_training(False)
        try:
            import os
            mode = os.environ.get("SINGA_JIT_INIT", "auto")
            accel = self._on_accelerator(inputs)
            pending = self._lazy_uninitialized()
            if not pending and accel and mode != "0":
                # everything already materialized (e.g. a sonnx import):
                # an eager dry-run would replay the whole forward on the
                # device for nothing
                pass
            elif pending and not self.get_params() and (
                    mode == "1" or (mode == "auto" and accel)):
                self._jit_init(inputs)
            else:
                # eager dry-run (CPU default, and the mixed
                # concrete/lazy fallback)
                self.forward(*inputs)
        finally:
            autograd.set_training(prev)
        self._compiled_init = True
        self._executors.clear()

    def _on_accelerator(self, inputs) -> bool:
        for t in inputs:
            if isinstance(t, Tensor):
                return t.device.is_tpu
        return model_device(self).is_tpu

    def _lazy_uninitialized(self) -> list:
        """Layers that override initialize() and have not run it yet."""
        out = []

        def walk(l):
            if type(l).initialize is not Layer.initialize \
                    and not l._initialized:
                out.append(l)
            for s in l._sublayers.values():
                walk(s)

        walk(self)
        return out

    def _jit_init(self, inputs: List[Tensor]) -> None:
        """Materialize all lazily-initialized parameters in ONE compiled
        XLA program instead of an eager per-op dry run.

        The lazy-init forward is traced under jit with the freshly
        created params/buffers (plus the advanced RNG key) as outputs;
        XLA dead-code-eliminates the activation math nothing depends on,
        so the program that actually compiles and runs is just the
        initializers.  The trace consumes PRNG keys in the same order as
        the eager path, so parameter values match up to XLA fusion
        rounding (FMA gives ~1-ulp differences vs the eager ops).  On
        an accelerator the eager path is hundreds of tiny compiles and
        dispatches; this is one."""
        example = tuple(t.data if isinstance(t, Tensor) else jnp.asarray(t)
                        for t in inputs)
        # preserve each argument's type: Tensor inputs stay Tensors under
        # the trace, raw arrays stay raw (same contract as the eager path)
        was_tensor = tuple(isinstance(t, Tensor) for t in inputs)
        dev = None
        for t in inputs:
            if isinstance(t, Tensor):
                dev = t.device
                break
        pending = self._lazy_uninitialized()
        saved_key = tensor_mod._rng_key
        if saved_key is None:
            saved_key = jax.random.PRNGKey(0)  # _next_key()'s default

        def init_program(batch, key):
            tensor_mod._rng_key = key
            args = tuple(
                Tensor(data=a, device=dev, requires_grad=False) if w else a
                for a, w in zip(batch, was_tensor))
            self.forward(*args)
            params = {n: t.data for n, t in self.get_params().items()}
            bufs = {n: t.data for n, t in self._get_buffers().items()}
            return params, bufs, tensor_mod._rng_key

        try:
            params, bufs, new_key = jax.jit(init_program)(example, saved_key)
        except Exception as e:
            tensor_mod._rng_key = saved_key
            # a failed trace leaves half-initialized layers holding
            # tracers; reset exactly the layers whose initialize() ran
            # (or could have run) under the trace — not the whole model,
            # which would wipe states registered outside initialize() —
            # then fall back to the eager dry-run so forwards that are
            # not jit-traceable (host-side control flow, .to_numpy())
            # keep compiling exactly as before
            for l in pending:
                l._initialized = False
                l._params.clear()
                l._states.clear()
            import warnings
            warnings.warn(
                f"jit-init trace failed ({type(e).__name__}); falling "
                f"back to the eager init dry-run", stacklevel=3)
            self.forward(*inputs)
            return
        tensor_mod._rng_key = new_key
        # the layer tensors hold leaked tracers from the trace — rebind
        # the concrete results by name
        for n, t in self.get_params().items():
            t.data = params[n]
        for n, t in self._get_buffers().items():
            t.data = bufs[n]

    def train_one_batch(self, x, y, *args):
        """Default train step; override for custom behavior (reference
        requires the override — we provide the canonical body)."""
        if self.optimizer is None:
            raise RuntimeError(
                "no optimizer: call model.set_optimizer(...) before training")
        out = self.forward(x)
        ls = self.loss(out, y)
        self.optimizer.backward_and_update(ls)
        return out, ls

    # -- execution entry points ----------------------------------------------
    def __call__(self, *xs):
        if self.graph_mode and self._compiled_init and not autograd.is_training():
            return self._run_graph("eval", self._eval_body, xs)
        return super().__call__(*xs)

    def train_step(self, *batch):
        """Run train_one_batch — compiled when graph mode is on.

        Telemetry: each call is a ``model.train_step`` span (obs.events;
        host wall clock — dispatch is async, see events docstring)."""
        self.train(True)
        with obs_events.span("model.train_step", model=self.name,
                             step=self._step_count,
                             compiled=self.graph_mode):
            if self.graph_mode:
                return self._run_graph("train", self._train_body, batch)
            out = self.train_one_batch(*batch)
            # the compiled path's executor advances the counter; the
            # eager path must too, or every eager span reports step=0
            self._step_count += 1
            return out

    def _train_body(self, batch_tensors):
        return self.train_one_batch(*batch_tensors)

    def _eval_body(self, batch_tensors):
        return self.forward(*batch_tensors)

    # -- the graph executor ---------------------------------------------------
    def _run_graph(self, tag: str, body, batch):
        arrays = tuple(b.data if isinstance(b, Tensor) else jnp.asarray(b)
                       for b in batch)
        key = tuple((a.shape, str(a.dtype)) for a in arrays) + (tag,)
        ex = self._executors.get(key)
        if ex is None:
            ex = _StepExecutor(self, tag, body, arrays)
            self._executors[key] = ex
        return ex(arrays)

    @property
    def graph(self) -> Optional[CapturedGraph]:
        """Most recently captured step graph."""
        return self.get_graph()

    def get_graph(self, tag: Optional[str] = None) -> Optional[CapturedGraph]:
        """Captured graph, optionally filtered by step kind
        ('train' | 'eval') — a model that ran both has one of each."""
        for ex in self._executors.values():
            if ex.captured is not None and (tag is None or ex.tag == tag):
                return ex.captured
        return None

    # -- state I/O ------------------------------------------------------------
    def save_states(self, fpath: str, aux_states: Optional[Dict] = None) -> None:
        from .utils import checkpoint
        checkpoint.save_states(self, fpath, aux_states)

    def load_states(self, fpath: str) -> Dict:
        from .utils import checkpoint
        return checkpoint.load_states(self, fpath)


# reference exposes the same class as Module in places
Module = Model


def _place(a, s):
    """Put `a` onto sharding `s` (no-op when already placed).

    Multi-host: `s` may span devices of other processes, where
    `device_put` is illegal — every process holds the same host-global
    value (executor contract), so each assembles its addressable shards
    from its own copy via make_array_from_callback."""
    if hasattr(a, "sharding") and a.sharding == s:
        return a
    if s.is_fully_addressable:
        return jax.device_put(a, s)
    import numpy as np
    host = np.asarray(a)
    return jax.make_array_from_callback(host.shape, s,
                                        lambda idx: host[idx])


class _StepExecutor:
    """Traces the model's imperative step into one jitted XLA module.

    Input/output plumbing (all dict-of-arrays pytrees):
      params   — trainable tensors      (donated, returned updated)
      buffers  — non-trainable states   (donated, returned updated)
      slots    — optimizer state        (donated, returned updated)
      step     — optimizer step counter (i32 scalar)
      rng      — PRNG key for dropout etc.
      batch    — the input arrays
    With a mesh + DistOpt, the step runs under shard_map over the mesh:
    batch sharded on axis 0 over 'data', params replicated, gradients
    pmean'ed in-graph by DistOpt.reduce_gradients.
    """

    @classmethod
    def for_planning(cls, model: Model, optimizer, slots_abstract,
                     example_sds) -> "_StepExecutor":
        """Abstract executor for shape-only lowering (parallel.planner):
        same field contract as __init__, but slots come in pre-computed
        (eval_shape'd — opt.init on real zeros would allocate) and no
        placement/compile ever happens."""
        ex = cls.__new__(cls)
        ex.model = model
        ex.tag = "train"
        ex.body = model._train_body
        ex.captured = None
        ex.is_train = True
        ex.param_tensors = dict(model.get_params())
        ex.buffer_tensors = dict(model._get_buffers())
        ex.opt = optimizer
        ex.slots = slots_abstract
        ex._out_treedef = None
        ex._build(example_sds)
        return ex

    def __init__(self, model: Model, tag: str, body, example_arrays):
        # a full collector pass in a step loop shows in its profile
        # (``py.gc``) and in ``py.gc_pause_ms``
        obs_events.watch_gc()
        self.model = model
        self.tag = tag
        self.body = body
        self.captured: Optional[CapturedGraph] = None
        self.is_train = (tag == "train")

        # stable param/buffer ordering
        params = model.get_params()
        buffers = model._get_buffers()
        self.param_tensors: Dict[str, Tensor] = dict(params)
        self.buffer_tensors: Dict[str, Tensor] = dict(buffers)

        opt = model.optimizer if self.is_train else None
        self.opt = opt
        if opt is not None:
            p_arrays = {n: t.data for n, t in self.param_tensors.items()}
            self.slots = opt.init(p_arrays)
            # resume: a restored checkpoint leaves moment arrays in the
            # optimizer's eager store — seed the compiled-step slots from
            # it so resuming reproduces the uninterrupted trajectory.
            # Copy (not alias): this executor donates its slots, and the
            # source arrays may be another live executor's buffers.
            est = getattr(opt, "_eager_state", None) or {}
            if isinstance(opt, DistOpt) and not est:
                est = getattr(opt.opt, "_eager_state", None) or {}
            for n, restored in est.items():
                if n not in self.slots:
                    continue
                # structured slots (GradAccum's {"acc","base"}) are
                # rebuilt by the optimizer's own load_slot_arrays; here
                # structure must already match exactly
                if not _slot_fits(restored, self.slots[n]):
                    raise ValueError(
                        f"restored optimizer state for {n!r} does not fit "
                        f"this optimizer/model (structure or shape mismatch) "
                        f"— refusing to silently reinitialize moments")
                self.slots[n] = jax.tree.map(jnp.copy, restored)
        else:
            self.slots = {}

        self._out_treedef = None
        self._build(example_arrays)

    # .....................................................................
    def _traced_step(self, params, buffers, slots, step, rng, batch):
        model, opt = self.model, self.opt
        # bind state into the live tensor objects
        from .parallel import mesh as mesh_mod
        saved_key = tensor_mod._rng_key
        tensor_mod._rng_key = rng
        saved_training = autograd.is_training()
        autograd.set_training(self.is_train)
        # trace-scoped batch-axis name, so ops (ring attention) agree with
        # DistOpt.data_axis no matter when jit re-traces this body
        saved_data_axis = mesh_mod.current_data_axis()
        mesh_mod.set_data_axis(opt.data_axis if isinstance(opt, DistOpt)
                               else "data")
        from .parallel import spmd as spmd_mod
        saved_rules = spmd_mod.current_trace_rules()
        spmd_mod.set_trace_rules(getattr(self, "_rules", None))
        saved_opt_state = None
        saved_param_data = {n: t.data for n, t in self.param_tensors.items()}
        saved_buffer_data = {n: t.data for n, t in self.buffer_tensors.items()}
        try:
            for n, t in self.param_tensors.items():
                t.data = params[n]
            for n, t in self.buffer_tensors.items():
                t.data = buffers[n]
            if opt is not None:
                saved_opt_state = (getattr(opt, "_eager_state", None),
                                   opt.step_counter)
                opt._eager_state = dict(slots)
                opt.step_counter = step
                if isinstance(opt, DistOpt):
                    saved_inner_state = (getattr(opt.opt, "_eager_state", None),
                                         opt.opt.step_counter)
                    opt.opt._eager_state = opt._eager_state
                    opt.opt.step_counter = step

            batch_t = tuple(
                Tensor(data=a, device=model_device(model), requires_grad=False)
                for a in batch)
            outs = self.body(batch_t)

            from .parallel import communicator as comm
            dist = isinstance(opt, DistOpt)
            new_params = {n: t.data for n, t in self.param_tensors.items()}
            new_buffers = {}
            for n, t in self.buffer_tensors.items():
                v = t.data
                if dist:
                    v = comm.allreduce(v, opt.data_axis, "mean")
                new_buffers[n] = v
            if opt is not None:
                src = opt.opt._eager_state if isinstance(opt, DistOpt) else opt._eager_state
                new_slots = {n: src.get(n, self.slots.get(n)) for n in self.slots}
            else:
                new_slots = {}

            out_arrays, treedef = _flatten_outs(outs)
            if dist:
                # replicate scalar outputs (loss) for a consistent view
                out_arrays = [comm.allreduce(a, opt.data_axis, "mean")
                              if a.ndim == 0 else a for a in out_arrays]
            self._out_treedef = treedef
            return tuple(out_arrays), new_params, new_buffers, new_slots
        finally:
            # restore concrete bindings — traces (jit/eval_shape) must not
            # leave tracers in the live tensors/optimizer
            tensor_mod._rng_key = saved_key
            autograd.set_training(saved_training)
            mesh_mod.set_data_axis(saved_data_axis)
            spmd_mod.set_trace_rules(saved_rules)
            for n, t in self.param_tensors.items():
                t.data = saved_param_data[n]
            for n, t in self.buffer_tensors.items():
                t.data = saved_buffer_data[n]
            if opt is not None and saved_opt_state is not None:
                opt._eager_state, opt.step_counter = saved_opt_state
                if isinstance(opt, DistOpt):
                    opt.opt._eager_state, opt.opt.step_counter = saved_inner_state

    # .....................................................................
    def _build(self, example_arrays):
        from .parallel import mesh as mesh_mod

        mesh = mesh_mod.current_mesh()
        data_axis = (self.opt.data_axis if isinstance(self.opt, DistOpt)
                     else "data")
        # multi-axis mesh (TP/SP alongside DP) → GSPMD: jit the global-
        # semantics step with rule-derived param shardings and let XLA
        # insert the collectives.  1-D data mesh + DistOpt → shard_map with
        # explicit in-graph pmean (the reference Communicator path).
        extra = [a for a, n in (mesh.shape.items() if mesh else [])
                 if a != data_axis and n > 1]
        # ZeRO-1 weight-update sharding rides the GSPMD path even on a
        # 1-D data mesh: slot shardings over 'data' make XLA partition
        # the update (reduce-scatter grads / update shard / all-gather).
        # Compressed/sparsified allreduce takes precedence (shard_map).
        from .parallel import spmd as spmd_mod
        zero1 = (isinstance(self.opt, DistOpt)
                 and spmd_mod.zero1_axis_for(self.opt, mesh) is not None)
        gspmd = mesh is not None and (bool(extra) or zero1)
        dist = (not gspmd and isinstance(self.opt, DistOpt)
                and mesh is not None and data_axis in mesh.shape)
        self.dist = dist
        self.gspmd = gspmd
        self.mesh = mesh if (dist or gspmd) else None

        def fn(params, buffers, slots, step, rng, *batch):
            return self._traced_step(params, buffers, slots, step, rng, batch)

        if gspmd:
            from .parallel import spmd
            P = mesh_mod.P
            if isinstance(self.opt, DistOpt) and (
                    self.opt.compress_dtype is not None
                    or self.opt.topk_ratio
                    or self.opt.compression is not None):
                import warnings
                warnings.warn(
                    "DistOpt compressed/sparsified allreduce applies only on "
                    "1-D data-parallel meshes (explicit in-graph pmean); on "
                    "multi-axis meshes GSPMD chooses the collectives and "
                    "these options are ignored", stacklevel=2)
            rules = spmd.collect_shard_rules(self.model)
            self._rules = rules   # trace-scoped handoff (_traced_step)
            rep = mesh_mod.NamedSharding(mesh, P())
            p_arrays = {n: t.data for n, t in self.param_tensors.items()}
            b_arrays = {n: t.data for n, t in self.buffer_tensors.items()}
            self._param_sh = spmd.param_shardings(p_arrays, rules, mesh)
            self._buffer_sh = {n: rep for n in b_arrays}
            self._slot_sh = spmd.tree_shardings(
                self.slots, self._param_sh, mesh,
                {n: a.shape for n, a in p_arrays.items()},
                zero1_axis=data_axis if zero1 else None)
            self._rep_sh = rep
            self._batch_sh = tuple(
                mesh_mod.NamedSharding(
                    mesh, spmd.batch_spec(a.shape, a.dtype, mesh, data_axis))
                for a in example_arrays)
            in_sh = (self._param_sh, self._buffer_sh, self._slot_sh, rep,
                     rep) + self._batch_sh
            # step outputs unconstrained; state pinned to its input
            # shardings so donation reuses buffers and steady state never
            # reshards
            out_sh = (None, self._param_sh, self._buffer_sh, self._slot_sh)
            self._jitted = jax.jit(fn, in_shardings=in_sh,
                                   out_shardings=out_sh,
                                   donate_argnums=(0, 1, 2))
            return

        if dist:
            P = mesh_mod.P
            axis = self.opt.data_axis
            # discover output structure once (abstract eval, no device work)
            shapes = jax.eval_shape(
                fn, {n: t.data for n, t in self.param_tensors.items()},
                {n: t.data for n, t in self.buffer_tensors.items()},
                self.slots, jnp.zeros((), jnp.int32), self.model._base_key,
                *[jax.ShapeDtypeStruct(_shard_shape(a.shape, mesh, axis), a.dtype)
                  for a in example_arrays])
            out_specs_leaves = jax.tree.map(
                lambda s: P() if len(s.shape) == 0 else P(axis), shapes[0])
            # optimizer state is replicated EXCEPT the error-feedback
            # residual of compression="int8_ring": per-rank state with a
            # leading world axis, sharded over 'data' so each rank owns
            # exactly its own slice (replicating it would be wrong, not
            # wasteful — the copies diverge by construction, and a
            # checkpoint would capture rank 0's residual for everyone)
            self._ef_sharded = (isinstance(self.opt, DistOpt)
                                and self.opt.compression is not None)
            slot_specs = ({n: {"base": P(), "ef": P(axis)}
                           for n in self.slots} if self._ef_sharded
                          else P())
            out_specs = (out_specs_leaves, P(), P(), slot_specs)
            in_specs = (P(), P(), slot_specs, P(), P()) + tuple(
                P(axis) for _ in example_arrays)
            wrapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                    out_specs=out_specs, check_vma=False)
        else:
            wrapped = fn

        self._jitted = jax.jit(wrapped, donate_argnums=(0, 1, 2))

    def __call__(self, batch_arrays):
        m = self.model
        params = {n: t.data for n, t in self.param_tensors.items()}
        buffers = {n: t.data for n, t in self.buffer_tensors.items()}
        # resolve the counter to a host int ONCE, before any device work:
        # the post-step advance must not read the device scalar back
        # (int() of a device array is a blocking D2H round trip, which
        # would serialize every step behind the previous one)
        step_host = int(self.opt.step_counter if self.opt is not None
                        else m._step_count)
        step = jnp.asarray(step_host, jnp.int32)
        rng = jax.random.fold_in(m._base_key, m._step_count)
        place = _place
        if self.dist:
            # place state replicated / batch data-sharded over the mesh the
            # step was compiled against; no-op after the first step
            # (outputs already carry shardings)
            from .parallel import mesh as mesh_mod
            rep = mesh_mod.NamedSharding(self.mesh, mesh_mod.P())
            shard = mesh_mod.NamedSharding(self.mesh, mesh_mod.P(self.opt.data_axis))
            params = {n: place(a, rep) for n, a in params.items()}
            buffers = {n: place(a, rep) for n, a in buffers.items()}
            if getattr(self, "_ef_sharded", False):
                # error-feedback residuals shard over 'data' (per-rank
                # state); everything else in the slot replicates
                self.slots = {
                    n: {k: (place(v, shard) if k == "ef"
                            else jax.tree.map(lambda a: place(a, rep), v))
                        for k, v in s.items()}
                    for n, s in self.slots.items()}
            else:
                self.slots = jax.tree.map(lambda a: place(a, rep),
                                          self.slots)
            step = place(step, rep)
            rng = place(rng, rep)
            batch_arrays = tuple(place(a, shard) for a in batch_arrays)
        elif self.gspmd:
            # place state/batch onto their rule-derived shardings; no-op
            # after the first step
            params = {n: place(a, self._param_sh[n]) for n, a in params.items()}
            buffers = {n: place(a, self._buffer_sh[n]) for n, a in buffers.items()}
            self.slots = {n: jax.tree.map(place, s, self._slot_sh[n])
                          for n, s in self.slots.items()}
            step = place(step, self._rep_sh)
            rng = place(rng, self._rep_sh)
            batch_arrays = tuple(place(a, s)
                                 for a, s in zip(batch_arrays, self._batch_sh))
        else:
            # plain single-device step, but state may still live on a
            # multi-device mesh from an earlier dist/gspmd executor (e.g.
            # eval compiled after set_mesh(None)) — normalize onto the
            # model's device so jit sees consistent placements
            dev = model_device(m).jax_devices[0]

            def _unshard(a):
                if isinstance(a, jax.Array) and len(a.sharding.device_set) > 1:
                    from .utils.checkpoint import _to_host
                    return jax.device_put(_to_host(a), dev)
                return a

            params = {n: _unshard(a) for n, a in params.items()}
            buffers = {n: _unshard(a) for n, a in buffers.items()}
            self.slots = jax.tree.map(_unshard, self.slots)
        if self.captured is None:
            with obs_events.span("graph.compile",
                                 graph=f"{m.name}.{self.tag}"):
                lowered = self._jitted.lower(params, buffers, self.slots,
                                             step, rng, *batch_arrays)
                compiled = lowered.compile()
            # lazy jaxpr capture (shapes only — safe w.r.t. donation)
            absargs = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (params, buffers, self.slots, step, rng, tuple(batch_arrays)))

            def jaxpr_thunk(absargs=absargs):
                p, b, s, st, rk, batch = absargs
                return jax.make_jaxpr(
                    lambda *a: self._jitted.__wrapped__(*a[:-1], *a[-1]))(
                        p, b, s, st, rk, batch)

            self.captured = CapturedGraph(f"{m.name}.{self.tag}",
                                          lowered=lowered, compiled=compiled,
                                          jaxpr_thunk=jaxpr_thunk)
        from . import faults
        # "device.execute" injection site: error/hang fire HOST-side
        # before the dispatch (so donated buffers are still intact and
        # the caller's retry can re-dispatch this same step); nan
        # corrupts the step outputs after a clean dispatch
        faults.fire("device.execute", graph=f"{m.name}.{self.tag}",
                    step=step_host)
        with obs_events.span("graph.execute",
                             graph=f"{m.name}.{self.tag}", step=step_host):
            outs, new_params, new_buffers, new_slots = self._jitted(
                params, buffers, self.slots, step, rng, *batch_arrays)
        outs = faults.corrupt("device.execute", outs)
        # rebind updated state into the live tensors
        for n, t in self.param_tensors.items():
            t.data = new_params[n]
        for n, t in self.buffer_tensors.items():
            t.data = new_buffers[n]
        self.slots = new_slots
        m._step_count += 1
        if self.opt is not None:
            self.opt.step_counter = step_host + 1
            # mirror compiled-step slots into the optimizer's eager store
            # (reference assignment, no copy) so save_states always sees
            # the live moments regardless of execution mode
            self.opt._eager_state = dict(new_slots)
            if isinstance(self.opt, DistOpt):
                self.opt.opt.step_counter = self.opt.step_counter
                self.opt.opt._eager_state = self.opt._eager_state
        return _unflatten_outs(outs, self._out_treedef, m)


def _slot_fits(restored, fresh) -> bool:
    """True when a restored slot has the same pytree structure and leaf
    shapes as the freshly initialized one (guards shape/arch mismatch)."""
    if fresh is None:
        return restored is None
    ls_r, td_r = jax.tree.flatten(restored)
    ls_f, td_f = jax.tree.flatten(fresh)
    if td_r != td_f or len(ls_r) != len(ls_f):
        return False
    return all(tuple(a.shape) == tuple(b.shape) for a, b in zip(ls_r, ls_f))


def model_device(model: Model):
    for t in model.get_params().values():
        return t.device
    from . import device as device_mod
    return device_mod.get_default_device()


def _shard_shape(shape, mesh, axis):
    if not shape:
        return shape
    n = mesh.shape[axis]
    s = list(shape)
    s[0] = max(1, s[0] // n)
    return tuple(s)


def _flatten_outs(outs):
    """Tensor-pytree -> list of arrays + treedef."""
    leaves, treedef = jax.tree.flatten(
        outs, is_leaf=lambda x: isinstance(x, Tensor))
    arrays = [l.data if isinstance(l, Tensor) else jnp.asarray(l)
              for l in leaves]
    return arrays, treedef


def _unflatten_outs(arrays, treedef, model):
    from . import device as device_mod
    dev = model_device(model)
    tensors = [Tensor(data=a, device=dev, requires_grad=False)
               for a in arrays]
    return jax.tree.unflatten(treedef, tensors)
