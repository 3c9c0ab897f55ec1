"""AOT-compiled serving engine: continuous batching over paged decode.

The inference analog of TrainStep.  One engine owns one model's frozen
weights, a :class:`~mxnet_tpu.serving.kvcache.PagedKVCache`, and a table
of **ahead-of-time compiled** executables — prefill per prompt-length
bucket, decode per (batch bucket, page bucket), sampling per batch
bucket — built once at :meth:`start` and looked up thereafter with the
PR 1 dispatch-cache keying (``dispatch_cache.signature_key``).  The
steady-state loop therefore performs **zero fresh traces**: every
request is padded up to a bucketed signature that already has an
executable, and the PR 3 compile tracer (kind ``serving``) proves it —
after warmup the compile counter must not move.

Loop shape (one iteration = one engine step):

1. **admit** — pop waiting requests (deadline-expired ones resolve with
   a clean error), allocate KV pages (evicting the youngest active
   sequence back to the queue if the pool is short), run the bucketed
   prefill executable, sample the first token.
2. **decode** — one batched single-token step for every active
   sequence: rows at arbitrary positions share one executable call
   (join/leave per step), new k/v is scattered into each row's pages,
   logits are sampled (greedy or keyed temperature) and the ONE host
   sync per step fetches the tokens.
3. **retire** — finished sequences (max tokens / EOS / context cap)
   free their pages and resolve their futures.

Shutdown honors the PR 5 lifecycle contract: a SIGTERM (or
``close(drain=True)``) stops admission, lets in-flight sequences
finish, rejects queued work with a clean error, and :func:`serve` exits
with ``lifecycle.EXIT_PREEMPTED``.
"""
from __future__ import annotations

import json
import logging
import threading
import time
from collections import deque

import numpy as _np

from .. import env as _env
from .. import fault as _fault
from .. import introspection as _introspection
from .. import telemetry as _telemetry
from ..base import MXNetError
from ..ndarray import dispatch_cache as _dc
from .kvcache import PagedKVCache, pages_for
from .scheduler import (AdmissionQueue, DeadlineExceededError, Request,
                        bucket_for, parse_buckets)

__all__ = ["ServingEngine", "serve"]

_LOGGER = logging.getLogger(__name__)


# -- metric families (registered once; recording is always-on) -------------
_G_QUEUE = _telemetry.gauge(
    "mxnet_serving_queue_depth", "requests waiting for admission")
_G_ACTIVE = _telemetry.gauge(
    "mxnet_serving_active_sequences", "sequences in the decode batch")
_H_OCCUPANCY = _telemetry.histogram(
    "mxnet_serving_batch_occupancy",
    "decode-batch fill ratio (active rows / padded bucket rows)",
    buckets=[0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0])
_H_PHASE = _telemetry.histogram(
    "mxnet_serving_phase_seconds",
    "serving step time by phase (prefill includes the first-token "
    "sample; decode includes sampling + the per-step token fetch)",
    labelnames=("phase",))
_H_LATENCY = _telemetry.histogram(
    "mxnet_serving_request_seconds", "request latency, submit -> done")
_H_TTFT = _telemetry.histogram(
    "mxnet_serving_ttft_seconds", "time to first token")
_C_TOKENS = _telemetry.counter(
    "mxnet_serving_tokens_total", "tokens processed",
    labelnames=("kind",))
_C_REQS = _telemetry.counter(
    "mxnet_serving_requests_total", "finished requests by outcome",
    labelnames=("outcome",))
_C_EVICT = _telemetry.counter(
    "mxnet_serving_evictions_total",
    "sequences evicted from the KV pool back to the queue")
_G_PAGES = _telemetry.gauge(
    "mxnet_serving_kv_pages", "KV-cache pool pages",
    labelnames=("state",))
_G_TOKS_S = _telemetry.gauge(
    "mxnet_serving_tokens_per_s",
    "generated tokens/s over the trailing window")
_G_TOKS_CHIP = _telemetry.gauge(
    "mxnet_tokens_per_s_per_chip",
    "generated tokens/s per device over the trailing window (the "
    "serving half of online utilization accounting)")
_H_JOIN = _telemetry.histogram(
    "mxnet_serving_join_to_first_token_seconds",
    "replica handoff: wall time from joining (params donated by a "
    "running engine) to this replica's first generated token")
_C_STEP_FAIL = _telemetry.counter(
    "mxnet_serving_step_failures_total",
    "engine-loop steps that raised and were absorbed (incl. injected "
    "serving.decode_step faults) — the loop retries, state untorn")


class _Seq:
    """One active sequence: its request plus cache bookkeeping.

    ``cache_len`` counts tokens whose k/v live in the pool; the next
    decode step feeds ``last_token`` at position ``cache_len`` (its k/v
    is written by that step)."""

    __slots__ = ("req", "cache_len", "last_token", "joined")

    def __init__(self, req, cache_len, last_token, joined):
        self.req = req
        self.cache_len = cache_len
        self.last_token = last_token
        self.joined = joined


class ServingEngine:
    """Continuous-batching inference engine for the llama model zoo.

    ``net`` is an initialized (non-MoE) ``LlamaForCausalLM``; its
    parameters are snapshotted at construction (frozen-weights
    deployment semantics — a served model does not train).  All bucket
    grids default from the ``MXNET_SERVING_*`` knobs (see env.py and
    the README "Serving" section)."""

    def __init__(self, net, *, batch_buckets=None, prefill_buckets=None,
                 kv_pages=None, page_size=None, queue_bound=None,
                 max_batch=None, deadline_ms=None, name=None, plan=None,
                 params_from=None, trace_requests=None):
        from ..gluon.model_zoo.language.llama import (LlamaForCausalLM,
                                                      serving_params)

        if not isinstance(net, LlamaForCausalLM):
            raise MXNetError("ServingEngine serves the model-zoo llama "
                             f"family, got {type(net).__name__}")
        cfg = net.config
        if cfg.num_experts > 0:
            raise MXNetError("incremental decode does not support MoE "
                             "FFNs yet (prefill/decode_apply contract)")
        self._cfg = cfg
        self._name = name or "llama"
        # replica handoff skips this entirely: the donated params below
        # ARE the weights, and the join-to-first-token path must not
        # pay a second materialization from the net
        self._params = {} if params_from is not None else \
            dict(serving_params(net))
        # tensor-parallel serving (ROADMAP serving follow-on (a)): a
        # ShardingPlan places the frozen params once at construction and
        # every prefill/decode/sample executable AOT-compiles against
        # the sharded avals — steady state still performs zero fresh
        # traces, GSPMD owns the collectives.  plan=None keeps the
        # single-device layout bit-for-bit.
        self._plan = plan
        self._serve_mesh = None
        self._rep_sharding = None
        # replica handoff (join_replica): a RUNNING donor engine hands
        # its frozen params over through the live-resharding transfer
        # (donor plan -> this plan) while it keeps serving — its param
        # arrays are immutable, the transfer only reads them.  The
        # join-to-first-token clock starts here.
        self._join_t0 = None
        if params_from is not None:
            from ..parallel import resharding as _resharding

            self._params = _resharding.transfer_params(
                dict(params_from._params), src_plan=params_from._plan,
                tgt_plan=plan)
            self._join_t0 = time.monotonic()
        if plan is not None:
            import jax

            self._serve_mesh = plan.build_mesh()
            self._rep_sharding = plan.replicated(self._serve_mesh)
            if params_from is None:
                self._params = {
                    k: jax.device_put(v,
                                      plan.sharding(k, self._serve_mesh))
                    for k, v in self._params.items()}
        # bucket grids + page size resolve through the tuning funnel
        # (explicit ctor args > env pins > MXNET_TUNE=1 stored winners
        # keyed by this engine's plan digest > defaults); the env
        # accessors remain the fallback so serving never depends on
        # the tuning tier
        _pd = plan.digest() if plan is not None else None
        try:
            from .. import tuning as _tuning

            _t_batch = str(_tuning.resolve("serving_batch_buckets",
                                           plan_digest=_pd))
            _t_prefill = str(_tuning.resolve("serving_prefill_buckets",
                                             plan_digest=_pd))
            _t_page = int(_tuning.resolve("serving_page_size",
                                          plan_digest=_pd))
        except Exception:
            _t_batch = _env.serving_batch_buckets()
            _t_prefill = _env.serving_prefill_buckets()
            _t_page = _env.serving_page_size()
        self._batch_buckets = list(batch_buckets) if batch_buckets else \
            parse_buckets(_t_batch, "batch bucket")
        self._prefill_buckets = list(prefill_buckets) if prefill_buckets \
            else parse_buckets(_t_prefill, "prefill bucket")
        self._page_size = int(page_size or _t_page)
        pages = int(kv_pages or _env.serving_kv_pages())
        self._max_batch = int(max_batch or _env.serving_max_batch())
        if self._max_batch > max(self._batch_buckets):
            raise MXNetError(
                f"max_batch {self._max_batch} exceeds the largest batch "
                f"bucket {max(self._batch_buckets)} — every admitted "
                "batch must fit a pre-compiled signature")
        self._deadline_ms = deadline_ms if deadline_ms is not None else \
            _env.serving_deadline_ms()
        dt = str(net.model.embed_tokens.weight.data().dtype)
        self._kv = PagedKVCache(cfg.num_layers, cfg.num_kv_heads,
                                cfg.head_dim, pages, self._page_size,
                                dtype=dt)
        # longest context a sequence can reach: the model's window, the
        # pool minus scratch, and the largest decode page bucket all cap it
        self._ctx_cap = min(cfg.max_seq_len, (pages - 1) * self._page_size)
        self._page_buckets = self._make_page_buckets()
        if max(self._prefill_buckets) > self._ctx_cap:
            raise MXNetError(
                f"prefill bucket {max(self._prefill_buckets)} exceeds the "
                f"context cap {self._ctx_cap} (max_seq_len / KV pool)")
        self._queue = AdmissionQueue(
            queue_bound or _env.serving_queue_bound(),
            on_expire=lambda r: _C_REQS.labels(outcome="expired").inc())
        self._active: list = []
        self._exec: dict = {}
        # per-executable FLOPs from compile-time cost_analysis (same
        # key space as _exec; None = unavailable — accounting just
        # skips, the MFU gauge stays absent rather than wrong)
        self._exec_flops: dict = {}
        self._n_chips = 1
        # per-request span traces (serving/tracing.py): explicit kwarg
        # > MXNET_TRACE_REQUESTS (default on).  The store keeps the
        # slowest N + every error/evicted trace; /v1/requests serves it
        from .tracing import TraceStore

        self._trace_enabled = bool(
            trace_requests if trace_requests is not None
            else _env.trace_requests())
        self._traces = TraceStore()
        self._lock = threading.Lock()          # guards _exec + counters
        self._stop_evt = threading.Event()     # close() requested
        self._drain = True                     # finish in-flight on stop
        self._drained = False                  # loop ran its final drain
        self._thread = None
        self._warm = False
        self._joined_seq = 0
        self._latencies: deque = deque(maxlen=2048)
        self._ttfts: deque = deque(maxlen=2048)
        self._tok_window: deque = deque(maxlen=64)   # (t, n_generated)
        self._mounted: list = []
        # fallback sampling-key chain for submitters with an UNSEEDED
        # mx.random stream: that state is thread-local, so two fresh
        # HTTP worker threads would otherwise both start at PRNGKey(0)
        # and draw IDENTICAL keys for concurrent requests
        import secrets

        from jax import random as _jr

        self._master_key = _jr.PRNGKey(secrets.randbits(31))

    # -- bucket grids ------------------------------------------------------
    def _make_page_buckets(self):
        cap = pages_for(self._ctx_cap, self._page_size)
        out, b = [], 1
        while b < cap:
            out.append(b)
            b *= 2
        out.append(cap)
        return out

    def manifest(self):
        """The AOT signature manifest: every executable the server
        compiles at startup, with its operand avals and the
        dtype/AMP-epoch keying — the serving half of the deployment-IR
        boundary (the block half is ``serving.export_artifact``)."""
        V, ps = self._cfg.vocab_size, self._page_size
        sigs = []
        for L in self._prefill_buckets:
            P = bucket_for(pages_for(L, ps), self._page_buckets)
            sigs.append({"phase": "prefill", "tokens": L, "pages": P,
                         "inputs": [[1, L, "int32"]]})
        for B in self._batch_buckets:
            for P in self._page_buckets:
                sigs.append({"phase": "decode", "batch": B, "pages": P,
                             "context": P * ps})
            sigs.append({"phase": "sample", "batch": B})
        return {
            "model": self._name,
            "param_dtype": self._kv.dtype,
            "page_size": ps,
            "kv_pages": self._kv.pages,
            "context_cap": self._ctx_cap,
            "batch_buckets": self._batch_buckets,
            "prefill_buckets": self._prefill_buckets,
            "page_buckets": self._page_buckets,
            "signatures": sigs,
        }

    # -- executable bodies (pure; traced once each at AOT time) ------------
    def _prefill_body(self, L, P):
        import jax.numpy as jnp

        from ..gluon.model_zoo.language.llama import prefill_apply

        cfg, ps = self._cfg, self._page_size

        def fn(params, kp, vp, ids, n, table):
            # ids (1, L) right-padded prompt; n = true length; table (1, P)
            logits, ks, vs = prefill_apply(params, cfg, ids)
            j = jnp.arange(L)
            pids = jnp.where(j < n, table[0, j // ps], 0)  # pads -> scratch
            offs = j % ps
            kn = ks[:, 0].transpose(2, 0, 1, 3)      # (L, layers, Hkv, hd)
            vn = vs[:, 0].transpose(2, 0, 1, 3)
            kp = kp.at[:, pids, :, offs, :].set(kn.astype(kp.dtype))
            vp = vp.at[:, pids, :, offs, :].set(vn.astype(vp.dtype))
            return logits[0, n - 1], kp, vp

        return fn

    def _decode_body(self, B, P):
        import jax.numpy as jnp

        from ..gluon.model_zoo.language.llama import decode_apply

        cfg, ps = self._cfg, self._page_size
        Hkv, hd = cfg.num_kv_heads, cfg.head_dim

        def fn(params, kp, vp, ids, pos, table):
            # ids/pos (B,); table (B, P); padded rows point at scratch
            rows = jnp.arange(B)
            pids = table[rows, pos // ps]
            offs = pos % ps
            pools = {"k": kp, "v": vp}

            def kv_join(layer, k_new, v_new):
                kn = k_new[:, :, 0, :]               # (B, Hkv, hd)
                vn = v_new[:, :, 0, :]
                pools["k"] = pools["k"].at[layer, pids, :, offs, :].set(
                    kn.astype(pools["k"].dtype))
                pools["v"] = pools["v"].at[layer, pids, :, offs, :].set(
                    vn.astype(pools["v"].dtype))
                K = pools["k"][layer][table].transpose(0, 2, 1, 3, 4) \
                    .reshape(B, Hkv, P * ps, hd)
                V = pools["v"][layer][table].transpose(0, 2, 1, 3, 4) \
                    .reshape(B, Hkv, P * ps, hd)
                return K, V, pos + 1

            logits = decode_apply(params, cfg, ids, pos, kv_join)
            return logits, pools["k"], pools["v"]

        return fn

    @staticmethod
    def _sample_body(B):
        import jax
        import jax.numpy as jnp

        def fn(logits, keys, steps, temps):
            # greedy rows: pure argmax.  temperature rows: categorical
            # under fold_in(request key, draw index) — sampling is a
            # pure function of the request, NOT of batch composition,
            # so continuous batching / eviction cannot change a
            # sampled sequence
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

            def draw(lg, k, s, t):
                kk = jax.random.fold_in(k, s)
                return jax.random.categorical(
                    kk, lg / jnp.where(t > 0, t, 1.0))

            drawn = jax.vmap(draw)(logits.astype(jnp.float32), keys,
                                   steps, temps).astype(jnp.int32)
            return jnp.where(temps > 0, drawn, greedy)

        return fn

    # -- AOT compilation (the ONLY place jax tracing happens) --------------
    def _sig_key(self, phase, *dyn_avals):
        # dispatch-cache keying: avals + AMP epoch + ctx kind, so an AMP
        # flip or context move after warmup misses (and recompiles with
        # an attributed cause) instead of serving a stale executable
        return _dc.signature_key(f"serving:{self._name}", dyn_avals,
                                 extra=(phase,))

    def _avals(self, phase, **dims):
        import jax
        import numpy as np

        ps = self._page_size
        if phase == "prefill":
            L, P = dims["L"], dims["P"]
            return (jax.ShapeDtypeStruct((1, L), np.int32),
                    jax.ShapeDtypeStruct((), np.int32),
                    jax.ShapeDtypeStruct((1, P), np.int32))
        if phase == "decode":
            B, P = dims["B"], dims["P"]
            return (jax.ShapeDtypeStruct((B,), np.int32),
                    jax.ShapeDtypeStruct((B,), np.int32),
                    jax.ShapeDtypeStruct((B, P), np.int32))
        B = dims["B"]
        return (jax.ShapeDtypeStruct((B, self._cfg.vocab_size),
                                     np.dtype(self._kv.dtype)),
                jax.ShapeDtypeStruct((B, 2), np.uint32),
                jax.ShapeDtypeStruct((B,), np.int32),
                jax.ShapeDtypeStruct((B,), np.float32))

    def _aot_compile(self, phase, cause, **dims):
        """Lower + compile one signature and cache it under its key.
        ``cause`` is ``aot_warmup`` at startup; a steady-state call that
        lands here is a ``steady_state_miss`` — the smoke and bench
        assert there are none after warmup."""
        import jax

        t0 = time.perf_counter()
        dyn = self._avals(phase, **dims)
        key = self._sig_key(phase, *dyn)
        with self._lock:
            if key in self._exec:
                return self._exec[key]
        if self._plan is not None:
            # planner-sharded AOT: params carry their NamedSharding from
            # the placement at construction; pools and dynamic operands
            # replicate over the same mesh (every executable input must
            # live on one device set)
            rep = self._rep_sharding
            param_avals = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                                   sharding=v.sharding)
                           for k, v in self._params.items()}
            pool_aval = jax.ShapeDtypeStruct(self._kv.k_pool.shape,
                                             self._kv.k_pool.dtype,
                                             sharding=rep)
            dyn = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=rep) for a in dyn)
        else:
            param_avals = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                           for k, v in self._params.items()}
            pool_aval = jax.ShapeDtypeStruct(self._kv.k_pool.shape,
                                             self._kv.k_pool.dtype)
        # planner path: pin every output replicated — with a tp plan the
        # lm_head leaves logits vocab-sharded, and the sample executable
        # (plus the host-side token fetch) expects the full row; the
        # all-gather GSPMD inserts here is exactly tensor-parallel
        # serving's logits gather before sampling
        jit_kw = {} if self._plan is None else \
            {"out_shardings": self._rep_sharding}
        if phase == "prefill":
            jit_fn = jax.jit(self._prefill_body(dims["L"], dims["P"]),
                             donate_argnums=(1, 2), **jit_kw)
            aot_args = (param_avals, pool_aval, pool_aval) + tuple(dyn)
        elif phase == "decode":
            jit_fn = jax.jit(self._decode_body(dims["B"], dims["P"]),
                             donate_argnums=(1, 2), **jit_kw)
            aot_args = (param_avals, pool_aval, pool_aval) + tuple(dyn)
        else:
            jit_fn = jax.jit(self._sample_body(dims["B"]), **jit_kw)
            aot_args = tuple(dyn)
        compiled = jit_fn.lower(*aot_args).compile()
        # per-executable FLOPs, captured ONCE while the compiled object
        # is in hand (layer 1 of the introspection plane): steady-state
        # dispatch then accounts a known constant — no cost re-derive,
        # no host sync
        flops = _introspection.flops_of(compiled)
        with self._lock:
            self._exec[key] = compiled
            self._exec_flops[key] = flops
        label = ":".join([self._name, phase] +
                         [f"{k}{v}" for k, v in sorted(dims.items())])
        _telemetry.compile_event("serving", label,
                                 time.perf_counter() - t0, cause)
        return compiled

    def _aot_warmup(self):
        """Compile the full manifest grid.  Every steady-state signature
        the scheduler can produce is covered: prompt lengths pad to a
        prefill bucket, batch sizes to a batch bucket, page counts to a
        page bucket."""
        t0 = time.perf_counter()
        ps = self._page_size
        for L in self._prefill_buckets:
            P = bucket_for(pages_for(L, ps), self._page_buckets)
            self._aot_compile("prefill", "aot_warmup", L=L, P=P)
        for B in self._batch_buckets:
            for P in self._page_buckets:
                self._aot_compile("decode", "aot_warmup", B=B, P=P)
            self._aot_compile("sample", "aot_warmup", B=B)
        if 1 not in self._batch_buckets:
            self._aot_compile("sample", "aot_warmup", B=1)
        self._warm = True
        return time.perf_counter() - t0

    def _lookup_exec(self, phase, **dims):
        """``(compiled, flops)`` for one signature; flops is the
        compile-time cost_analysis count (None = unavailable)."""
        key = self._sig_key(phase, *self._avals(phase, **dims))
        with self._lock:
            compiled = self._exec.get(key)
        if compiled is None:
            # a post-warmup miss is a contract violation the tracer makes
            # visible (cause steady_state_miss) — but the request is
            # served, not dropped
            compiled = self._aot_compile(phase, "steady_state_miss",
                                         **dims)
        with self._lock:
            flops = self._exec_flops.get(key)
        return compiled, flops

    # -- replica handoff ---------------------------------------------------
    @classmethod
    def join_replica(cls, net, donor, **kw):
        """Replica scale-out handoff: build a new engine whose frozen
        params are DONATED by a running ``donor`` engine through the
        live-resharding transfer (donor plan → this engine's ``plan``
        kw, replicated when absent) instead of re-read from the net or
        loaded from disk.  The donor keeps serving throughout — its
        param arrays are immutable and the transfer only reads them.
        The join-to-first-token clock
        (``mxnet_serving_join_to_first_token_seconds``) starts at the
        handoff and stops at this replica's first generated token."""
        return cls(net, params_from=donor, **kw)

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        """AOT-compile the manifest and start the engine loop thread."""
        if self._thread is not None:
            return self
        import jax

        self._n_chips = max(1, jax.device_count())
        if self._plan is not None:
            # the executables expect every operand on the plan's mesh:
            # replicate the KV pools once up front (they stay replicated
            # through the donate round trip, so this is one-time work)
            self._kv.k_pool = jax.device_put(self._kv.k_pool,
                                             self._rep_sharding)
            self._kv.v_pool = jax.device_put(self._kv.v_pool,
                                             self._rep_sharding)
        self._aot_warmup()
        self._thread = threading.Thread(target=self._run_loop,
                                        name="mxnet-serving-engine",
                                        daemon=True)
        self._thread.start()
        return self

    def close(self, drain=True, timeout=60):
        """Stop the loop: with ``drain`` in-flight sequences finish and
        queued requests get a clean shutdown error; without, everything
        resolves with the shutdown error immediately."""
        self._drain = bool(drain)
        self._stop_evt.set()
        t = self._thread
        if t is not None:
            t.join(timeout)
            if t.is_alive():
                raise MXNetError(
                    f"serving engine loop did not stop within {timeout}s "
                    "(drain still in progress — call close() again or "
                    "close(drain=False) to abort in-flight work)")
            self._thread = None
        self.unmount_http()

    def running(self):
        t = self._thread
        return t is not None and t.is_alive()

    def join(self, timeout=None):
        """Block until the loop thread exits (SIGTERM drain path)."""
        t = self._thread
        if t is not None:
            t.join(timeout)

    # -- request surface ---------------------------------------------------
    def submit(self, prompt, max_new_tokens=16, temperature=0.0,
               eos_id=None, deadline_ms=None, trace_id=None):
        """Enqueue a generation request; returns the Request future.
        Raises QueueFullError at the admission bound and MXNetError
        when the server is shutting down or the prompt cannot fit.

        ``trace_id`` stitches cross-process traces: a fleet router
        stamps its own (numeric) trace id into the replica request so
        the replica-side spans land in the SAME tree the router's
        queue_wait/dispatch spans live in."""
        if self._stop_evt.is_set():
            raise MXNetError("serving engine is shutting down")
        if not self._warm:
            raise MXNetError("serving engine not started — call start()")
        req = Request(prompt, max_new_tokens=max_new_tokens,
                      temperature=temperature, eos_id=eos_id,
                      deadline_ms=deadline_ms if deadline_ms is not None
                      else (self._deadline_ms or None))
        if self._trace_enabled:
            from .tracing import RequestTrace

            req.trace = RequestTrace(
                int(trace_id) if trace_id is not None else req.id)
            req.trace.event("submitted", prompt_len=int(req.prompt.size),
                            max_new_tokens=req.max_new_tokens)
            req.on_resolve = self._trace_finished
        if req.temperature > 0:
            req.key = self._request_key()
        L = int(req.prompt.size)
        if bucket_for(L, self._prefill_buckets) is None:
            raise MXNetError(
                f"prompt length {L} exceeds the largest prefill bucket "
                f"{max(self._prefill_buckets)}")
        if pages_for(L, self._page_size) > self._kv.pages - 1:
            raise MXNetError(
                f"prompt length {L} can never fit the KV pool "
                f"({self._kv.pages - 1} allocatable pages)")
        self._queue.put(req)
        _G_QUEUE.set(len(self._queue))
        if self._drained:
            # raced past the stop check while the loop ran its FINAL
            # queue drain: nobody will ever pop this request — reject it
            # now instead of leaving the future to time out
            self._queue.drain(lambda r: MXNetError(
                f"request {r.id} rejected: server shutting down"))
            raise MXNetError("serving engine is shutting down")
        return req

    def _request_key(self):
        """Per-request sampling key.  A submitter whose thread seeded
        mx.random gets the next key of that stream (reproducible under
        mx.random.seed, the documented contract); an unseeded thread
        falls back to the engine's own split chain so concurrent
        requests from fresh threads never share a key."""
        from .. import random as _rnd

        if _rnd._S.key is not None:
            # mxtpu: noqa[MXT010] submit-time 8-byte key fetch, off-loop
            return _np.asarray(_rnd._next_key(), dtype=_np.uint32)
        from jax import random as _jr

        with self._lock:
            self._master_key, sub = _jr.split(self._master_key)
        # mxtpu: noqa[MXT010] submit-time 8-byte key fetch, off-loop
        return _np.asarray(sub, dtype=_np.uint32)

    # -- the steady-state loop (NO tracing allowed in here: MXT050) --------
    def _run_loop(self):
        from .. import lifecycle

        consec_fail = 0
        while True:
            if lifecycle.stop_requested():
                self._stop_evt.set()
            if self._stop_evt.is_set():
                if not self._drain:
                    self._abort_active()
                if not self._active:
                    break
            try:
                did_work = self._step()
                consec_fail = 0
            except Exception as e:
                # an engine step must never kill the loop thread: the
                # chaos seams (serving.decode_step) raise BEFORE any
                # KV/sequence mutation, so the step simply retries —
                # and a real bug becomes a counted, logged failure
                # instead of a silently dead server.  Bounded, not
                # blind: each failure backs off (no hot spin), the log
                # is rate-limited, and a PERSISTENT failure resolves
                # the wedged in-flight work with the error instead of
                # hanging its callers forever
                _C_STEP_FAIL.inc()
                consec_fail += 1
                if consec_fail <= 3 or consec_fail % 10 == 0:
                    _LOGGER.warning(
                        "serving engine step failed (%r); retrying "
                        "(%d consecutive)", e, consec_fail)
                if consec_fail >= self._MAX_CONSEC_STEP_FAILURES:
                    _LOGGER.critical(
                        "serving engine step failed %d times in a row "
                        "(%r); failing the wedged in-flight work so "
                        "callers unblock", consec_fail, e)
                    # a persistently broken serving step is an abnormal
                    # event: dump the ring (host-side file IO only) so
                    # the post-mortem shows what preceded the wedge
                    from .. import flight_recorder as _flight

                    _flight.record_event(
                        "lifecycle", event="serving_step_failure",
                        consecutive=consec_fail, error=repr(e)[:200])
                    _flight.dump_blackbox("serving_step_failure")
                    self._fail_active(e)
                    consec_fail = 0
                self._stop_evt.wait(0.05)
                did_work = False
                continue
            if not did_work and not self._stop_evt.is_set():
                self._queue.wait_nonempty(0.02)
        # flag BEFORE the final drain: a submit() that races past the
        # stop check either lands before this drain (drained here) or
        # observes the flag and self-drains — never stranded
        self._drained = True
        n = self._queue.drain(lambda r: MXNetError(
            f"request {r.id} rejected: server shutting down"))
        for _ in range(n):
            # distinct from the in-flight work the drain COMPLETED
            # (those finish with their normal outcome): these never ran.
            # Fleet-level retry accounting keys on this — a
            # drain_rejected completion is safe to resubmit elsewhere
            _C_REQS.labels(outcome="drain_rejected").inc()
        self._publish_gauges()

    def _step(self):
        did = False
        while (not self._stop_evt.is_set()
               and len(self._active) < self._max_batch):
            req = self._queue.pop_ready()
            if req is None:
                break
            with self._timed("prefill"):
                admitted = self._admit(req)
            did = True
            if not admitted:
                break    # pool full even after eviction: stop admitting
        if self._active:
            with self._timed("decode"):
                self._decode_step()
            did = True
        self._publish_gauges()
        return did

    class _Timed:
        __slots__ = ("name", "t0")

        def __init__(self, name):
            self.name = name

        def __enter__(self):
            self.t0 = time.perf_counter()
            return self

        def __exit__(self, *exc):
            _H_PHASE.labels(phase=self.name).observe(
                time.perf_counter() - self.t0)
            return False

    def _timed(self, name):
        return self._Timed(name)

    def _publish_gauges(self):
        _G_QUEUE.set(len(self._queue))
        _G_ACTIVE.set(len(self._active))
        _G_PAGES.labels(state="free").set(self._kv.pages_free)
        _G_PAGES.labels(state="used").set(self._kv.pages_used)
        win = self._tok_window
        if len(win) >= 2:
            dt = win[-1][0] - win[0][0]
            toks = sum(n for _, n in list(win)[1:])
            if dt > 0:
                _G_TOKS_S.set(toks / dt)
                _G_TOKS_CHIP.set(toks / dt / self._n_chips)

    def _admit(self, req):
        """Prefill one request (or its post-eviction continuation).
        Returns False when the pool cannot host it right now (request
        requeued)."""
        import jax.numpy as jnp

        tr = req.trace
        try:
            # chaos seam: a tripped admission loses nothing — the
            # request returns to the queue FRONT and the next loop
            # iteration retries it
            _fault.check("serving.admit")
        except Exception as e:
            _LOGGER.warning("serving.admit fault for request %s (%r); "
                            "requeued", req.id, e)
            if tr is not None:
                tr.event("requeued", reason="admit_fault")
                tr.last_enqueue_t = time.perf_counter()
            self._queue.requeue(req)
            return False
        if req.expired():
            if tr is not None:
                tr.event("deadline_expired", where="prefill")
            req.resolve(DeadlineExceededError(
                f"request {req.id} expired before prefill"))
            _C_REQS.labels(outcome="expired").inc()
            return True
        ids_full = req.full_ids()
        L = int(ids_full.size)
        if L >= self._ctx_cap or \
                bucket_for(L, self._prefill_buckets) is None:
            # an evicted continuation can outgrow the prefill grid even
            # though the original prompt fit — finish with what we have
            # rather than erroring a half-served request
            if req.tokens:
                self._finish(req, "length")
            else:
                req.resolve(MXNetError(
                    f"request {req.id}: prompt length {L} exceeds the "
                    f"serving context cap {self._ctx_cap}"))
                _C_REQS.labels(outcome="rejected").inc()
            return True
        # admission NEVER evicts: preempting an active sequence to start
        # a new one would let two sequences that cannot coexist in the
        # pool ping-pong each other (one token per full prefill).  New
        # work waits for free pages; eviction is reserved for GROWTH of
        # already-running sequences (_decode_step).
        if not self._kv.alloc(req.id, L):
            if tr is not None:
                tr.event("requeued", reason="pool_full")
                tr.last_enqueue_t = time.perf_counter()
            self._queue.requeue(req)
            return False
        Lb = bucket_for(L, self._prefill_buckets)
        P = bucket_for(pages_for(L, self._page_size), self._page_buckets)
        # close the queue span BEFORE the executable lookup: a
        # steady-state miss compiles for seconds, and that time must
        # read as a compile, never as queue congestion
        t_q_end = time.perf_counter()
        if tr is not None:
            tr.add_span("queue_wait", tr.last_enqueue_t, t_q_end,
                        prefills=req.prefills)
        compiled, flops = self._lookup_exec("prefill", L=Lb, P=P)
        t_pre = time.perf_counter()
        if tr is not None and t_pre - t_q_end > 1e-3:
            tr.add_span("compile_wait", t_q_end, t_pre, bucket=Lb)
        ids = jnp.asarray(_np.concatenate(
            [ids_full, _np.zeros(Lb - L, dtype=_np.int32)])[None, :])
        table = jnp.asarray(
            self._kv.table_rows([req.id], P), dtype=jnp.int32)
        last_logits, kp, vp = compiled(
            self._params, self._kv.k_pool, self._kv.v_pool, ids,
            _np.int32(L), table)
        self._kv.k_pool, self._kv.v_pool = kp, vp
        if flops:
            _introspection.account_flops(flops, kind="serving_prefill")
        req.prefills += 1
        if req.prefills == 1:
            _C_TOKENS.labels(kind="prompt").inc(L)
        t_sm = time.perf_counter()
        pid = tr.add_span("prefill", t_pre, t_sm, tokens=L, bucket=Lb) \
            if tr is not None else 0
        tok = self._sample([last_logits], [req])[0]
        if tr is not None:
            # the host-side clock: prefill dispatch is async, the
            # sample's fused token fetch is where the wall time lands
            tr.add_span("sample", t_sm, time.perf_counter(), parent=pid)
        if req.first_token_t is None:
            req.first_token_t = time.monotonic()
            _H_TTFT.observe(req.first_token_t - req.submitted)
            with self._lock:
                self._ttfts.append(req.first_token_t - req.submitted)
            if self._join_t0 is not None:
                # replica handoff acceptance metric: donated-params
                # join -> this replica's FIRST served token
                _H_JOIN.observe(req.first_token_t - self._join_t0)
                self._join_t0 = None
        req.tokens.append(tok)
        _C_TOKENS.labels(kind="generated").inc()
        if self._is_finished(req, tok, L):
            self._kv.free(req.id)
            self._finish(req, "stop" if tok == req.eos_id else "length")
            return True
        self._joined_seq += 1
        self._active.append(_Seq(req, L, tok, self._joined_seq))
        return True

    def _evictable(self, seq):
        """A sequence may be evicted only if its continuation (prompt +
        generated so far) can re-prefill later — evicting one that has
        outgrown the prefill grid would silently truncate it."""
        n = int(seq.req.full_ids().size)
        return n < self._ctx_cap and \
            bucket_for(n, self._prefill_buckets) is not None

    def _youngest_evictable(self, exclude=None):
        for seq in reversed(self._active):
            if seq is not exclude and self._evictable(seq):
                return seq
        return None

    def _evict(self, seq):
        """Return a sequence's pages and requeue its continuation (the
        prompt plus everything generated so far re-prefills later)."""
        self._active.remove(seq)
        self._kv.free(seq.req.id)
        tr = seq.req.trace
        if tr is not None:
            tr.event("evicted", cache_len=seq.cache_len,
                     generated=len(seq.req.tokens))
            tr.last_enqueue_t = time.perf_counter()
        self._queue.requeue(seq.req)
        _C_EVICT.inc()

    def _decode_step(self):
        import jax.numpy as jnp

        # chaos seam, checked BEFORE any KV/table/sequence mutation: a
        # trip unwinds to the loop guard with zero torn state and the
        # step retries next iteration
        _fault.check("serving.decode_step")
        # grow tables first; eviction inside can shrink the active set
        for seq in list(self._active):
            if seq not in self._active:
                continue
            while not self._kv.ensure(seq.req.id, seq.cache_len + 1):
                victim = self._youngest_evictable(exclude=seq)
                if victim is not None:
                    self._evict(victim)
                    continue
                if self._evictable(seq):
                    # nothing else to evict: hand this one back to the
                    # queue (its pages free the pool for smaller work)
                    self._evict(seq)
                else:
                    # unrestorable AND the pool is exhausted: finish at
                    # the current length rather than wedging the loop
                    self._active.remove(seq)
                    self._kv.free(seq.req.id)
                    self._finish(seq.req, "length")
                break
        if not self._active:
            return
        B = len(self._active)
        Bb = bucket_for(B, self._batch_buckets)
        max_pages = max(pages_for(s.cache_len + 1, self._page_size)
                        for s in self._active)
        P = bucket_for(max_pages, self._page_buckets)
        compiled, flops = self._lookup_exec("decode", B=Bb, P=P)
        pad = Bb - B
        sids = [s.req.id for s in self._active] + [None] * pad
        ids = jnp.asarray([s.last_token for s in self._active] + [0] * pad,
                          dtype=jnp.int32)
        pos = jnp.asarray([s.cache_len for s in self._active] + [0] * pad,
                          dtype=jnp.int32)
        table = jnp.asarray(self._kv.table_rows(sids, P), dtype=jnp.int32)
        t_dec = time.perf_counter()
        logits, kp, vp = compiled(self._params, self._kv.k_pool,
                                  self._kv.v_pool, ids, pos, table)
        self._kv.k_pool, self._kv.v_pool = kp, vp
        if flops:
            _introspection.account_flops(flops, kind="serving_decode")
        _H_OCCUPANCY.observe(B / Bb)
        rows = list(self._active)
        t_sm = time.perf_counter()
        toks = self._sample(logits, [s.req for s in rows], batched=True)
        t_done = time.perf_counter()
        now = time.monotonic()
        n_new = 0
        for seq, tok in zip(rows, toks):
            req = seq.req
            tr = req.trace
            if tr is not None:
                # per-decode-step residency: this request rode THIS
                # batched step (host-side stamps; the sample child is
                # where the one fused token fetch lands)
                did = tr.add_span("decode_step", t_dec, t_sm,
                                  step=len(req.tokens), batch=B,
                                  bucket=Bb)
                tr.add_span("sample", t_sm, t_done, parent=did)
            seq.cache_len += 1
            seq.last_token = tok
            req.tokens.append(tok)
            n_new += 1
            if self._is_finished(req, tok, seq.cache_len + 1):
                self._active.remove(seq)
                self._kv.free(req.id)
                self._finish(req, "stop" if tok == req.eos_id
                             else "length")
        _C_TOKENS.labels(kind="generated").inc(n_new)
        self._tok_window.append((now, n_new))

    def _sample(self, logits, reqs, batched=False):
        """Sample one token per row; returns python ints.  THE one host
        sync per engine step lives here (everything upstream stays
        lazily dispatched)."""
        import jax.numpy as jnp

        if batched:
            lg = logits
            B = lg.shape[0]
        else:
            lg = jnp.stack(logits)
            B = len(logits)
        pad = B - len(reqs)
        zero_key = _np.zeros(2, dtype=_np.uint32)
        temps = [r.temperature for r in reqs] + [0.0] * pad
        keys = [r.key if r.key is not None else zero_key
                for r in reqs] + [zero_key] * pad
        steps = [len(r.tokens) for r in reqs] + [0] * pad
        compiled, flops = self._lookup_exec("sample", B=B)
        toks = compiled(lg, jnp.asarray(_np.stack(keys)),
                        jnp.asarray(steps, dtype=jnp.int32),
                        jnp.asarray(temps, dtype=jnp.float32))
        if flops:
            _introspection.account_flops(flops, kind="serving_sample")
        # mxtpu: noqa[MXT010] ONE fused token fetch per engine step IS the design (has_overflow precedent)
        host = _np.asarray(toks)
        return [int(t) for t in host[:len(reqs)]]

    def _trace_finished(self, req):
        """Request.resolve hook: classify the outcome, file the trace
        in the tail-retention store, and merge its spans into the
        Chrome trace when the profiler is active.  Every resolution
        path (finish, queue/prefill deadline, shutdown drain, step
        failure) flows through resolve(), so this one hook sees them
        all — host-side work only."""
        tr = req.trace
        if tr is None:
            return
        err = req.error
        if err is None:
            outcome = req.finish_reason or "done"
        elif isinstance(err, DeadlineExceededError):
            outcome = "expired"
        else:
            outcome = "error"
        tr.finish(outcome, error=err)
        self._traces.add(tr)
        tr.emit_chrome()

    def _is_finished(self, req, tok, ctx_next):
        return (len(req.tokens) >= req.max_new_tokens
                or (req.eos_id is not None and tok == req.eos_id)
                or ctx_next >= self._ctx_cap)

    def _finish(self, req, reason):
        req.finish_reason = reason
        req.resolve()
        with self._lock:
            self._latencies.append(req.finished_t - req.submitted)
        _H_LATENCY.observe(req.finished_t - req.submitted)
        # outcome distinguishes how a request ENDED: "stop" (hit its
        # eos_id) vs "length" (max_new_tokens or the context/pool cap —
        # the signal an operator watches for silent truncation)
        _C_REQS.labels(outcome=reason).inc()

    # consecutive step failures before the loop stops retrying and
    # fails the in-flight work (at the 0.05s per-failure backoff this
    # is ~2.5s of a persistently broken step — far beyond any armed
    # chaos burst, far short of a caller's request timeout)
    _MAX_CONSEC_STEP_FAILURES = 50

    def _fail_active(self, error):
        """Resolve every in-flight sequence with ``error`` (persistent
        step failure): their pages free, their callers unblock with the
        real cause, and the loop keeps serving whatever work does not
        hit the broken path."""
        for seq in list(self._active):
            self._kv.free(seq.req.id)
            seq.req.resolve(MXNetError(
                f"request {seq.req.id} failed: serving engine step "
                f"persistently failing ({error!r})"))
            _C_REQS.labels(outcome="error").inc()
        self._active = []

    def _abort_active(self):
        for seq in list(self._active):
            self._kv.free(seq.req.id)
            seq.req.resolve(MXNetError(
                f"request {seq.req.id} aborted: server closed without "
                "drain"))
            _C_REQS.labels(outcome="aborted").inc()
        self._active = []

    # -- observability -----------------------------------------------------
    def stats(self):
        """JSON-able engine snapshot (served at /v1/serving)."""
        with self._lock:
            # snapshot under the lock: the loop thread appends to the
            # deque and iterating a mutating deque raises
            lat = sorted(self._latencies)
            ttft = sorted(self._ttfts)

        def _pct_of(xs, p):
            return xs[min(len(xs) - 1, int(p * len(xs)))] if xs else None

        def pct(p):
            return _pct_of(lat, p)

        with self._lock:
            n_exec = len(self._exec)
        return {
            "model": self._name,
            "queue_depth": len(self._queue),
            "active_sequences": len(self._active),
            "kv_pages": {"free": self._kv.pages_free,
                         "used": self._kv.pages_used,
                         "page_size": self._page_size,
                         "pool_bytes": self._kv.nbytes()},
            "compiled_signatures": n_exec,
            "warm": self._warm,
            "latency_s": {"p50": pct(0.50), "p99": pct(0.99),
                          "count": len(lat)},
            # the fleet router's health monitor feeds on these (queue
            # depth above + TTFT percentiles here) to score replicas
            "ttft_s": {"p50": _pct_of(ttft, 0.50),
                       "p99": _pct_of(ttft, 0.99), "count": len(ttft)},
            "tokens_per_s": _G_TOKS_S.value,
            "tokens_per_s_per_chip": _G_TOKS_CHIP.value,
            "context_cap": self._ctx_cap,
            "buckets": {"batch": self._batch_buckets,
                        "prefill": self._prefill_buckets,
                        "pages": self._page_buckets},
            "request_traces": {"enabled": self._trace_enabled,
                               "traced": self._traces.count()},
        }

    # -- HTTP plane (mounted beside /metrics on the telemetry server) ------
    def mount_http(self, prefix="/v1"):
        """Register ``{prefix}/completions`` (POST), ``{prefix}/serving``
        (GET), and the ``{prefix}/requests`` trace-debug route (GET:
        the tail-retained per-request span trees) on the telemetry HTTP
        endpoint."""
        comp, stat = prefix + "/completions", prefix + "/serving"
        reqs = prefix + "/requests"
        _telemetry.register_http_route(comp, self._http_completions)
        _telemetry.register_http_route(stat, self._http_stats)
        _telemetry.register_http_route(reqs, self._http_requests)
        self._mounted = [comp, stat, reqs]
        return self

    def unmount_http(self):
        for path in self._mounted:
            _telemetry.unregister_http_route(path)
        self._mounted = []

    def _http_stats(self, method, path, query, body):
        return 200, "application/json", json.dumps(self.stats()).encode()

    def _http_requests(self, method, path, query, body):
        doc = self._traces.snapshot()
        doc["enabled"] = self._trace_enabled
        return 200, "application/json", json.dumps(doc).encode()

    def _http_completions(self, method, path, query, body):
        from .scheduler import QueueFullError

        if method != "POST":
            return 405, "application/json", b'{"error": "POST only"}'
        try:
            data = json.loads(body or b"{}")
            prompt = data["prompt"]
        except (ValueError, KeyError) as e:
            return 400, "application/json", json.dumps(
                {"error": f"bad request: {e!r}"}).encode()
        try:
            req = self.submit(
                prompt,
                max_new_tokens=int(data.get("max_new_tokens", 16)),
                temperature=float(data.get("temperature", 0.0)),
                eos_id=data.get("eos_id"),
                deadline_ms=data.get("deadline_ms"),
                trace_id=data.get("trace_id"))
        except QueueFullError as e:
            _C_REQS.labels(outcome="rejected").inc()
            return 429, "application/json", json.dumps(
                {"error": str(e)}).encode()
        except MXNetError as e:
            return 400, "application/json", json.dumps(
                {"error": str(e)}).encode()
        try:
            res = req.result(timeout=float(data.get("timeout_s", 120)))
        except DeadlineExceededError as e:
            return 408, "application/json", json.dumps(
                {"error": str(e)}).encode()
        except MXNetError as e:
            return 503, "application/json", json.dumps(
                {"error": str(e)}).encode()
        if data.get("return_trace") and req.trace is not None:
            # cross-process span handoff: the caller (fleet router)
            # grafts this replica-side tree into its own trace so
            # /v1/requests stays end-to-end across the router hop
            res["trace"] = req.trace.to_dict()
        return 200, "application/json", json.dumps(res).encode()


def serve(net, port=None, install_signals=True, on_ready=None,
          **engine_kw):
    """Blocking server entrypoint: start the telemetry HTTP endpoint
    (serving routes mounted beside ``/metrics``), run the engine until a
    graceful stop (SIGTERM/SIGINT or ``lifecycle.request_stop``), drain,
    and return the lifecycle exit code (``EXIT_PREEMPTED`` after a stop
    request, 0 after ``close()``).

    ``on_ready(engine, bound_port)`` fires once the engine is warm and
    the routes are mounted (embedders, smoke tests).  The caller owns
    ``sys.exit(serve(...))``."""
    from .. import lifecycle

    if install_signals:
        lifecycle.install_signal_handlers()
    server = _telemetry.start_http_server(
        port if port is not None else (_env.serving_port() or 0))
    engine = ServingEngine(net, **engine_kw)
    engine.start()
    engine.mount_http()
    bound = server.server_address[1]
    print(f"mxnet_tpu serving: engine up on 127.0.0.1:{bound} "
          f"(/v1/completions, /v1/serving, /metrics)", flush=True)
    if on_ready is not None:
        on_ready(engine, bound)
    try:
        engine.join()
    finally:
        engine.close()
    if lifecycle.stop_requested():
        lifecycle.cancel_grace_deadline()
        return lifecycle.EXIT_PREEMPTED
    return 0
