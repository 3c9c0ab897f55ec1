"""Sharded, jit-compiled training step over the device mesh.

This is the TPU-native successor of the reference's whole DP stack
(SURVEY.md §3.3: KVStoreLocal/CommDevice reduce + Trainer._allreduce_grads +
optimizer update ops): one XLA program computes forward, backward, gradient
reduction and the optimizer update, with collectives inserted by the
compiler from sharding annotations (GSPMD) instead of hand-written NCCL/
ps-lite calls (SURVEY.md §4.4 TPU mapping).

- batch sharded over ``dp`` (and ``fsdp``) → grads of replicated params
  become an automatic psum riding ICI;
- ``param_sharding='fsdp'`` shards parameters/optimizer state over the
  ``fsdp`` axis (ZeRO-style: all-gather on use, reduce-scatter on grads —
  cf. PAPERS.md "Automatic Cross-Replica Sharding of Weight Update");
- tensor-parallel specs from parallel.tensor_parallel compose with the same
  step; everything under one jit.
"""
from __future__ import annotations

from collections import OrderedDict
from functools import partial

from .. import profiler as _profiler
from .. import telemetry as _telemetry
from ..base import MXNetError
from .functional import functionalize

__all__ = ["TrainStep", "make_sgd_update", "make_adam_update",
           "replicated_specs", "fsdp_specs"]

# host phases of one TrainStep call (``telemetry.phase``): staging, key,
# signature and tree walks / the call into the executable alone / the
# compile of a fresh signature.  What follows the call is left unspanned.
PHASE_PREPARE = "train_step.prepare"
PHASE_EXECUTE = "train_step.execute"
PHASE_COMPILE = "train_step.compile"


def _jax():
    import jax

    return jax


# --------------------------------------------------------------------------
# pure optimizer updates (the jit-fused analog of src/operator/optimizer_op.cc)
# --------------------------------------------------------------------------
def make_sgd_update(lr=0.01, momentum=0.9, wd=0.0):
    import jax

    def init(params):
        return {"mom": jax.tree_util.tree_map(lambda p: p * 0.0, params)}

    def update(params, grads, state):
        def upd(p, g, m):
            g = g + wd * p
            m_new = momentum * m + g
            return p - lr * m_new, m_new

        out = jax.tree_util.tree_map(upd, params, grads, state["mom"])
        new_p = jax.tree_util.tree_map(lambda t: t[0], out,
                                       is_leaf=lambda t: isinstance(t, tuple))
        new_m = jax.tree_util.tree_map(lambda t: t[1], out,
                                       is_leaf=lambda t: isinstance(t, tuple))
        return new_p, {"mom": new_m}

    return init, update


def make_adam_update(lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8, wd=0.0):
    import jax
    import jax.numpy as jnp

    def init(params):
        z = jax.tree_util.tree_map(lambda p: p * 0.0, params)
        return {"m": z, "v": jax.tree_util.tree_map(lambda p: p * 0.0, params),
                "t": jnp.zeros((), "int32")}

    def update(params, grads, state):
        t = state["t"] + 1
        c1 = 1.0 - beta1 ** t.astype("float32")
        c2 = 1.0 - beta2 ** t.astype("float32")

        def upd(p, g, m, v):
            g = g + wd * p
            m_new = beta1 * m + (1 - beta1) * g
            v_new = beta2 * v + (1 - beta2) * g * g
            step = lr * (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)
            return p - step.astype(p.dtype), m_new, v_new

        out = jax.tree_util.tree_map(upd, params, grads, state["m"], state["v"])
        pick = lambda i: jax.tree_util.tree_map(
            lambda t_: t_[i], out, is_leaf=lambda t_: isinstance(t_, tuple))
        return pick(0), {"m": pick(1), "v": pick(2), "t": t}

    return init, update


# --------------------------------------------------------------------------
# sharding spec builders — thin shims over the planner's rule engine
# (parallel/planner owns the heuristics; these keep the original API)
# --------------------------------------------------------------------------
def replicated_specs(params):
    from jax.sharding import PartitionSpec as P

    from .planner.rules import named_rule_set

    rs = named_rule_set("replicated")
    return OrderedDict((k, P(*rs.spec_for(k, getattr(v, "shape", ()),
                                          {})))
                       for k, v in params.items())


def fsdp_specs(params, mesh, axis="fsdp"):
    """Shard each parameter's first evenly-divisible dim over the fsdp
    axis (ZeRO-3 layout); replication for small/indivisible params.
    Delegates to the planner's shape heuristic — the planner must
    reproduce this hand-wired layout bit-identically, so there is
    exactly one implementation."""
    from jax.sharding import PartitionSpec as P

    from .planner.rules import RuleSet

    rs = RuleSet(heuristic_axis=axis, name="fsdp")
    sizes = dict(mesh.shape)
    return OrderedDict(
        (k, P(*rs.spec_for(k, v.shape, sizes)))
        for k, v in params.items())


class TrainStep:
    """One fused XLA training step for a Gluon net.

    Usage::

        step = TrainStep(net, loss_fn, optimizer='sgd',
                         optimizer_params={'learning_rate': 0.1},
                         mesh=mesh, param_sharding='fsdp')
        loss = step(x, y)          # x, y numpy/jax arrays (global batch)
        step.write_back()          # sync trained params into the Gluon net
    """

    def __init__(self, net, loss_fn, optimizer="sgd", optimizer_params=None,
                 mesh=None, param_sharding="replicated", extra_param_specs=None,
                 batch_axes=("dp", "fsdp"), donate=True, train_mode=True,
                 dtype=None, pipeline=None, remat=False, plan=None):
        """``pipeline``: dict enabling pipeline parallelism over a mesh
        axis — {'num_microbatches': M, 'axis': 'pp', 'schedule':
        'gpipe'|'1f1b', 'remat_stage': bool}.  The net must implement
        ``pipeline_decompose(n_stages, train_mode)`` (the model zoo's
        LlamaForCausalLM does): heterogeneous embed/head ends run outside
        the pipe, the homogeneous trunk streams over pp, and dp/fsdp
        batch axes compose with it in the same jit.

        ``plan``: a :class:`~mxnet_tpu.parallel.planner.ShardingPlan` —
        the planner-native entry.  Supplies the mesh (built from the
        plan's axes when ``mesh`` is not also given), every parameter's
        PartitionSpec, the batch spec, and the pipeline in-jit-sharding
        flag; ``param_sharding`` is ignored (``extra_param_specs`` still
        applies last, as the per-call escape hatch).  Without ``plan``,
        the legacy string/dict modes are themselves routed through the
        planner (``ShardingPlan.from_specs``), so every sharded
        TrainStep now has exactly one audited layout object."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        if remat and pipeline is not None:
            raise MXNetError(
                "TrainStep(remat=True) does not compose with pipeline=; "
                "use pipeline={'remat_stage': True} for per-stage "
                "rematerialization inside the pipe")
        # plan-first resolution: the plan supplies mesh and batch axes
        # BEFORE the pipeline block filters them
        self._plan = plan
        if plan is not None:
            if mesh is None:
                mesh = plan.build_mesh()
            else:
                for ax, size in plan.axes.items():
                    if size != mesh.shape.get(ax, 1):
                        raise MXNetError(
                            f"plan axis {ax}={size} does not match the "
                            f"mesh ({dict(mesh.shape)}) — build the mesh "
                            "with plan.build_mesh() or re-plan")
            batch_axes = tuple(plan.batch_axes)
        self._net = net
        apply_fn, params = functionalize(net, train_mode=train_mode,
                                         with_state=train_mode)
        if remat:
            # whole-model rematerialization: backward recomputes the
            # forward instead of storing activations — the standard lever
            # for 2x batch (PERF_NOTES escalation step 2).  Models with
            # finer-grained remat (Llama's per-layer checkpoint) should
            # use their own option instead.
            base_apply = apply_fn

            def apply_fn(p, rng, *args):
                import jax as _jx

                return _jx.checkpoint(
                    lambda pp, aa: base_apply(pp, rng, *aa))(p, args)
        self._apply_fn = apply_fn
        self._with_state = train_mode
        self._pipeline = None
        if pipeline is not None:
            if mesh is None:
                raise MXNetError("pipeline parallelism needs a mesh")
            pp_axis = pipeline.get("axis", "pp")
            if pp_axis not in mesh.axis_names:
                raise MXNetError(f"mesh has no {pp_axis!r} axis")
            decomp = net.pipeline_decompose(mesh.shape[pp_axis],
                                            train_mode=train_mode)
            self._pipeline = {
                "M": int(pipeline["num_microbatches"]),
                "axis": pp_axis,
                "schedule": pipeline.get("schedule", "gpipe"),
                "remat_stage": bool(pipeline.get("remat_stage", False)),
                "decomp": decomp,
                "batch_axes": tuple(a for a in batch_axes
                                    if a in mesh.axis_names
                                    and a != pp_axis),
            }
        # split trainable vs frozen/state params (grad_req='null' covers
        # BatchNorm running stats and user-frozen params): gradients and
        # optimizer updates apply only to the trainable set
        grad_req = {name: p.grad_req
                    for name, p in net.collect_params().items()}
        self._train_names = [k for k in params if grad_req.get(k) != "null"]
        opt_params = dict(optimizer_params or {})
        if optimizer == "sgd":
            init, update = make_sgd_update(
                lr=opt_params.get("learning_rate", 0.01),
                momentum=opt_params.get("momentum", 0.0),
                wd=opt_params.get("wd", 0.0))
        elif optimizer == "adam":
            init, update = make_adam_update(
                lr=opt_params.get("learning_rate", 1e-3),
                beta1=opt_params.get("beta1", 0.9),
                beta2=opt_params.get("beta2", 0.999),
                eps=opt_params.get("epsilon", 1e-8),
                wd=opt_params.get("wd", 0.0))
        else:
            raise MXNetError(f"TrainStep optimizer {optimizer!r} not supported "
                             "(use 'sgd' or 'adam', or the imperative Trainer)")

        from . import planner as _planner

        self._mesh = mesh
        if mesh is not None:
            if plan is None:
                # legacy modes: resolve exactly as before, then wrap as
                # a plan — one audited layout object either way
                if param_sharding == "fsdp":
                    specs = fsdp_specs(params, mesh)
                elif param_sharding == "replicated":
                    specs = replicated_specs(params)
                elif isinstance(param_sharding, dict):
                    specs = OrderedDict(
                        (k, param_sharding.get(k, P())) for k in params)
                else:
                    raise MXNetError(
                        f"bad param_sharding {param_sharding!r}")
                plan = _planner.ShardingPlan.from_specs(
                    dict(mesh.shape), specs, batch_axes,
                    _planner.signature_of(params),
                    optimizer=("adam" if optimizer == "adam" else
                               ("sgd_momentum"
                                if opt_params.get("momentum") else "sgd")))
            missing = [k for k in params if k not in plan.specs]
            if missing and plan.specs:
                # a plan keyed on a DIFFERENT net instance's auto-names
                # would silently replicate everything — make it loud
                import warnings

                warnings.warn(
                    f"sharding plan covers none of/only part of this "
                    f"net's params ({len(missing)}/{len(params)} "
                    f"missing, e.g. {missing[0]!r}); missing params "
                    "replicate. Re-plan from THIS net's signature "
                    "(planner.signature_of) — gluon auto-name prefixes "
                    "differ between instances.", stacklevel=2)
            specs = plan.partition_specs(params.keys())
            if extra_param_specs:
                specs.update(extra_param_specs)
            self._plan = plan
            self._param_shard = OrderedDict(
                (k, NamedSharding(mesh, s)) for k, s in specs.items())
            self._batch_shard = NamedSharding(mesh, plan.batch_spec())
            # copy first: device_put returns the SAME buffer when the target
            # sharding already matches (1-device mesh, replicated params), and
            # jit donation below would then invalidate the Gluon net's own
            # parameter buffers
            params = OrderedDict(
                (k, jax.device_put(jnp.array(v, copy=True),
                                   self._param_shard[k]))
                for k, v in params.items())
        else:
            self._param_shard = None
            self._batch_shard = None
            # copy: jit donation below must not invalidate the jax buffers
            # the Gluon net's Parameters still reference
            params = OrderedDict((k, jnp.array(v, copy=True))
                                 for k, v in params.items())

        # plain dicts, as the step hands them back: a compiled executable
        # takes the tree structure it was lowered with and no other
        self.train_params = {k: params[k] for k in self._train_names}
        self.rest_params = {k: v for k, v in params.items()
                            if k not in self.train_params}
        self.opt_state = init(self.train_params)
        if mesh is not None:
            self.opt_state = jax.tree_util.tree_map(
                lambda leaf: jax.device_put(leaf, NamedSharding(mesh, P()))
                if leaf.ndim == 0 else leaf, self.opt_state)

        with_state = self._with_state
        # mixed precision (AMP): trace the model under the bf16/fp16 cast
        # policy — master weights stay fp32, matmuls/convs run low-precision
        # on the MXU, loss is computed in fp32 (contrib.amp._cast_scope)
        if dtype is None:
            from contextlib import nullcontext

            amp_scope = nullcontext
        else:
            from ..contrib.amp import _cast_scope

            amp_scope = partial(_cast_scope, dtype)

        # GSPMD cannot partition the Pallas attention kernel: under a mesh
        # it runs per batch shard (ops.flash_attention.batch_sharded).  The
        # pipelined forward is already inside a shard_map over pp.
        if mesh is None or self._pipeline is not None:
            from contextlib import nullcontext as attention_scope
        else:
            from ..ops.flash_attention import batch_sharded

            attention_scope = partial(batch_sharded, mesh,
                                      self._plan.batch_axes)

        pipeline_cfg = self._pipeline
        mesh_ = mesh
        # planner flag: keep the jax-0.4.37 GSPMD replicated workaround
        # unless the plan (or MXNET_PLANNER_PIPELINE_IN_JIT) asks for
        # true in-jit P(pp) stage sharding (ROADMAP "re-test after jax
        # upgrade" is now a config flip, not a code hunt)
        pipe_in_jit = self._plan.pipeline_in_jit_sharding \
            if self._plan is not None else None

        def pipelined_forward(p, rng, x):
            from .pipeline_parallel import pipeline_apply, stack_stage_params

            d = pipeline_cfg["decomp"]
            S = mesh_.shape[pipeline_cfg["axis"]]
            L = len(d["layer_names"])
            per = L // S
            h = d["pre_fn"]({k: p[k] for k in d["pre_names"]}, rng, x)
            # leaves (S, per, ...): inner stack = layers within a stage,
            # outer stack = the stage-major axis pipeline_apply shards
            stage_trees = [
                stack_stage_params(
                    [{k0: p[d["layer_names"][li][k0]]
                      for k0 in d["layer0_names"]}
                     for li in range(si * per, (si + 1) * per)])
                for si in range(S)]
            stacked = stack_stage_params(stage_trees)

            def stage_fn(sp, h_mb):
                # fold stage + layer indices into the key so every trunk
                # layer draws DISTINCT dropout masks (a shared key would
                # correlate all layers).  The key must not depend on the
                # tick/microbatch: the 1F1B backward recomputes the stage
                # from the stashed input and has to reproduce the exact
                # forward masks.
                s_idx = jax.lax.axis_index(pipeline_cfg["axis"])
                s_rng = jax.random.fold_in(rng, s_idx)
                n_layers = jax.tree_util.tree_leaves(sp)[0].shape[0]

                def body(hh, pl_li):
                    pl, li = pl_li
                    return d["layer_fn"](
                        pl, jax.random.fold_in(s_rng, li), hh), None

                out, _ = jax.lax.scan(
                    body, h_mb, (sp, jnp.arange(n_layers)))
                return out

            h = pipeline_apply(
                stage_fn, stacked, h, mesh_, pipeline_cfg["M"],
                axis=pipeline_cfg["axis"],
                schedule=pipeline_cfg["schedule"],
                remat_stage=pipeline_cfg["remat_stage"],
                batch_axes=pipeline_cfg["batch_axes"],
                in_jit_sharding=pipe_in_jit)
            return d["post_fn"]({k: p[k] for k in d["post_names"]}, rng, h)

        # Named ``train_step_<digest of the scope names>`` below, so that a
        # trace shows the module as ``jit_train_step_...``.  The name is
        # part of JAX's persistent compile-cache key, which the scopes are
        # not: an executable cached by a build with other scope names is
        # not loaded in place of this one (it would carry that build's op
        # names), whoever adds or renames a scope.
        def train_step(train_params, rest_params, opt_state, rng, x, y):
            # the scopes only name the ops (profiler.scopes_of reads them
            # back); the backward needs none, JAX derives its ops' names
            # from the forward's: transpose(jvp(mx_forward))
            @jax.named_scope(_profiler.SCOPE_FORWARD)
            def loss_of(tp):
                p = dict(rest_params)
                p.update(tp)
                # numbers the layers give to telemetry.step_scalar leave the
                # step beside the loss (an expert layer's routed pairs)
                with amp_scope(), attention_scope(), \
                        _telemetry.collect_step_scalars() as scalars:
                    # a batch's x of several arrays (ids and segment ids) is
                    # the net's inputs in order
                    xs = x if isinstance(x, tuple) else (x,)
                    if pipeline_cfg is not None:
                        out = pipelined_forward(p, rng, x)
                        state = {}
                    elif with_state:
                        out, state = apply_fn(p, rng, *xs)
                    else:
                        out = apply_fn(p, rng, *xs)
                        state = {}
                # the loss is the caller's function: its call is named
                with jax.named_scope(_profiler.SCOPE_LOSS):
                    if dtype is not None:
                        out = jax.tree_util.tree_map(
                            lambda o: o.astype(jnp.float32)
                            if jnp.issubdtype(o.dtype, jnp.floating) else o,
                            out)
                    loss = jnp.mean(loss_fn(out, y))
                return loss, (state, scalars.stacked())

            (loss, (state, scalars)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(train_params)
            with jax.named_scope(_profiler.SCOPE_OPTIMIZER):
                new_tp, new_opt = update(train_params, grads, opt_state)
            new_rest = dict(rest_params)
            for k, v in state.items():
                if k in new_rest:
                    new_rest[k] = v
            return loss, new_tp, new_rest, new_opt, scalars

        train_step.__name__ = train_step.__qualname__ = \
            f"train_step_{_profiler.scope_digest()}"
        donate_argnums = (0, 1, 2) if donate else ()
        jit_kw = {}
        if mesh is not None:
            # a mesh step returns its state in the layout it was placed
            # with, so the executable takes what it gave and the plan's
            # layout is the layout that runs
            replicated = NamedSharding(mesh, P())
            jit_kw["out_shardings"] = (replicated,) + jax.tree_util.tree_map(
                lambda leaf: leaf.sharding,
                (self.train_params, self.rest_params, self.opt_state)) \
                + (replicated,)
        self._step = jax.jit(train_step, donate_argnums=donate_argnums,
                             **jit_kw)
        self._rng_seed = 0
        self.step_count = 0      # steps taken (lifecycle train_state)
        self._seen_sigs = set()  # telemetry: (x, y) avals already compiled
        # one AOT executable a batch signature (lower().compile() on the
        # cold path): the compiled object is what steady state dispatches,
        # and its cost_analysis() FLOP count, captured once at compile
        # time, feeds the online MFU gauge with no steady-state work
        # (mxnet_tpu/introspection.py)
        self._compiled = {}      # batch sig -> (compiled, flops)
        # the records of this step's calls (telemetry.step_records())
        self._track = _telemetry.StepTrack(type(net).__name__)
        self._record = None      # the open call's

    @property
    def params(self):
        merged = OrderedDict(self.rest_params)
        merged.update(self.train_params)
        return merged

    def _stage_batch(self, v):
        """Place one input on device under the step's batch sharding via
        the shared staging decision tree (``prefetcher.stage_leaf``): an
        array the prefetcher already put with the right sharding passes
        through untouched — the overlap path must add zero work here (and
        must NOT round-trip device arrays through numpy).  A tuple or list
        of arrays (a net of several inputs) is staged leaf by leaf and
        comes back a tuple."""
        if isinstance(v, (tuple, list)):
            return tuple(self._stage_batch(leaf) for leaf in v)
        v = getattr(v, "_get", lambda: v)()
        if self._batch_shard is None:
            return v
        from ..gluon.data.prefetcher import stage_leaf

        return stage_leaf(v, self._batch_shard)

    def __call__(self, x, y):
        """One step on the batch ``(x, y)``.  ``x`` is the net's input, or a
        tuple or list of its inputs in order (token ids and segment ids):
        each is staged and is part of the signature a step compiles for."""
        from jax import random as jr

        if self._pipeline is not None and isinstance(x, (tuple, list)):
            raise MXNetError("a pipelined step streams one array through its "
                             "stages; x is a tuple of several")
        # the call's record: the step's number and its batch's, what the
        # process did since the previous call, and a look (no wait) at
        # whether earlier steps are done
        record = self._record = self._track.open(self.step_count)
        with _telemetry.phase(PHASE_PREPARE, step=record["step"]) as prepare:
            x = self._stage_batch(x)
            y = self._stage_batch(y)
            rng = jr.PRNGKey(self._rng_seed)
            self._rng_seed += 1
            # telemetry compile tracer: an unseen batch signature means
            # this call traces+compiles the whole step before running it.
            # The set is capped like dispatch_cache._COMPILE_SEEN — a
            # variable-shape workload must not leak memory proportional to
            # distinct sigs (past the cap fresh compiles simply go
            # unrecorded)
            # over every leaf: one array's signature is what it always was
            sig = tuple(part for v in (x if isinstance(x, tuple) else (x,))
                        + (y,)
                        for part in (tuple(getattr(v, "shape", ())),
                                     str(getattr(v, "dtype", ""))))
            fresh = sig not in self._seen_sigs \
                and len(self._seen_sigs) < 4096
            if fresh:
                self._seen_sigs.add(sig)
            args = (self.train_params, self.rest_params, self.opt_state,
                    rng, x, y)
        spans = record["spans"]
        spans[PHASE_PREPARE] = prepare.stamps
        # the cold path lowers + compiles once (capturing XLA's
        # cost_analysis FLOPs while the executable is in hand); steady
        # state is one dict lookup + dispatch: no retrace, no host sync
        out, flops = self._call_aot(sig, args)
        loss, self.train_params, self.rest_params, self.opt_state, \
            scalars = out
        # the loss and the step's scalars are read when they are there, by
        # a later call's look or by who reads the metrics; nothing waits
        _telemetry.defer_step_scalars(scalars, loss, record, self._track)
        self.step_count += 1
        if flops:
            from .. import introspection as _introspection

            _introspection.account_flops(flops, kind="train_step")
        if fresh:
            _telemetry.compile_event(
                "train_step", type(self._net).__name__,
                spans[PHASE_EXECUTE][1] - spans[PHASE_PREPARE][0],
                "new_step" if len(self._seen_sigs) == 1 else "new_shape")
        return loss

    def _execute(self, fn, args):
        """The call into the executable, alone in its phase."""
        record = self._record
        with _telemetry.phase(PHASE_EXECUTE, step=record["step"]) as execute:
            out = fn(*args)
        record["spans"][PHASE_EXECUTE] = execute.stamps
        return out

    def _aot_step(self, args):
        """Lower + compile one operand tuple ahead of time and capture
        its cost-analysis FLOPs.  A compile error propagates: there is
        no second dispatch path for the compiler to refuse again."""
        from .. import introspection as _introspection

        record = self._record
        with _telemetry.phase(PHASE_COMPILE, step=record["step"]) as compiling:
            compiled = self._step.lower(*args).compile()
            # the op-to-scope table of this executable, for whoever reads
            # a device trace of it (profiler.op_scopes)
            _profiler.register_executable(
                f"train_step:{type(self._net).__name__}", compiled)
        record["spans"][PHASE_COMPILE] = compiling.stamps
        return (compiled, _introspection.flops_of(compiled))

    def _call_aot(self, sig, args):
        """Dispatch one step through the signature's AOT executable,
        compiled at its first call; returns ``(outputs, flops)``.  An
        error from the executable propagates: it takes the layout it
        returns, so there is no other to lower for."""
        entry = self._compiled.get(sig)
        if entry is None:
            entry = self._compiled[sig] = self._aot_step(args)
        compiled, flops = entry
        return self._execute(compiled, args), flops

    def run(self, batches, steps=None, prefetch=None, guard=None):
        """Drive the fused step over an iterator of ``(x, y)`` batches with
        device prefetch: a background thread keeps the next
        ``MXNET_PREFETCH_BUFFER`` batches in flight (non-blocking
        ``device_put`` with this step's batch sharding), so host-side input
        staging overlaps the previous step's compute.  ``prefetch``
        overrides the depth (0 = serial staging).  Returns the per-step
        losses (device scalars — only the last is synced).

        ``guard`` (a :class:`mxnet_tpu.guard.Guard`, default = a fresh
        one when ``MXNET_GUARD=1``) polls the loss sentinel after every
        step: the fused jit commits its update before any verdict can
        land (donated buffers), so an anomalous verdict cannot skip —
        ``Guard.poll_loss`` escalates persistent anomalies straight to
        ``GuardRewind``, which ``run_with_recovery`` absorbs as a
        rewind-class restart from the latest valid checkpoint.  The
        poll feeds on the step's lazily-dispatched loss scalar: with the
        default sync stride it adds no trace and no extra collective
        beyond the one agreement the verdict needs.

        With ``steps=N`` the loop never pops past batch N, but the
        background pipeline has up to ``depth`` more batches staged which
        ``close()`` drops — callers chunking ONE shared iterator across
        several ``run`` calls should pass ``prefetch=0`` (or slice the
        batch list) so no batch is consumed and discarded.

        Preemption contract (:mod:`mxnet_tpu.lifecycle`): every step
        boundary polls ``lifecycle.check_stop()`` (agreed across SPMD
        peers, and it beats the stall-watchdog heartbeat); on a stop the
        loop returns the losses so far — the caller checks
        ``lifecycle.stop_requested()``, publishes its final checkpoint,
        and raises ``lifecycle.GracefulExit``."""
        from .. import flight_recorder as _flight
        from .. import guard as _guard_mod
        from .. import lifecycle as _lifecycle
        from ..gluon.data.prefetcher import PrefetchIterator

        if guard is None and _guard_mod.enabled():
            guard = _guard_mod.Guard()

        if prefetch is None:
            # resolve through the tuning funnel with THIS step's plan
            # digest, so a per-signature winner (bench.py --tune) can
            # steer the depth; env pin > winner > default, and the
            # iterator's own env fallback still guards a broken tier
            try:
                from .. import tuning as _tuning

                prefetch = int(_tuning.resolve(
                    "prefetch_buffer",
                    plan_digest=self._plan.digest()
                    if self._plan is not None else None))
            except Exception:
                prefetch = None
        it = PrefetchIterator(iter(batches), depth=prefetch,
                              sharding=self._batch_shard)
        losses = []
        try:
            try:
                while steps is None or len(losses) < steps:
                    if _lifecycle.check_stop():
                        break
                    try:
                        batch = next(it)
                    except StopIteration:
                        break
                    x, y = batch[0], batch[1]
                    losses.append(self(x, y))
                    if guard is not None:
                        guard.poll_loss(losses[-1], step=len(losses))
            finally:
                it.close()
            if losses:
                import numpy as _np

                # ONE deliberate end-of-run sync so step errors surface
                # inside run(), not at the caller's first read:
                # mxtpu: noqa[MXT010]
                _np.asarray(losses[-1])
        except _lifecycle.GracefulExit:
            raise          # clean preemption, not a crash — no black box
        except Exception:
            # unhandled failure in the training loop: dump this rank's
            # collective ledger (atomic, per-rank, never a collective)
            # so the cross-rank blame merge has a ring to align
            _flight.dump_blackbox("train_step_failure")
            raise
        return losses

    def write_back(self):
        """Copy trained parameter values back into the Gluon net."""
        merged = self.params
        for name, p in self._net.collect_params().items():
            if name in merged:
                p.data()._set(merged[name])
