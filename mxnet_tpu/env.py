"""MXNET_* environment-variable behavior layer.

Reference: ``docs/static_site/src/pages/api/faq/env_var.md`` + the scattered
``dmlc::GetEnv`` reads in src/ (SURVEY.md §6.6 "Config/flags").  The
reference configures its engine/executor/kvstore through ~60 MXNET_* vars;
the TPU build keeps the same names for the vars whose concern still exists,
maps each to the XLA-native mechanism, and documents the ones XLA subsumes
instead of silently ignoring them.

Wired vars (read at ``import mxnet_tpu``):

- ``MXNET_ENGINE_TYPE``: ``NaiveEngine`` = eager op-by-op determinism
  switch (jax_disable_jit) — see :mod:`mxnet_tpu.engine`.
- ``MXNET_TPU_MATMUL_PRECISION``: fp32 matmul/conv MXU precision policy —
  see :mod:`mxnet_tpu.engine`.
- ``MXNET_SEED``: seeds the global RNG (≙ reference mx.random.seed at
  process start).
- ``MXNET_CPU_WORKER_NTHREADS``: default decode/augment pool width for
  ImageRecordIter and the Gluon DataLoader prefetcher (≙ the reference's
  OMP worker pool size).
- ``MXNET_PROFILER_AUTOSTART``: start the profiler with profile_all=True
  at import (≙ reference profiler autostart).
- ``MXNET_KVSTORE_BIGARRAY_BOUND``: size threshold (elements) above which
  dist kvstore values get their own collective rather than riding a fused
  bucket.
- ``MXNET_COORDINATOR_ADDRESS``: jax.distributed coordinator override
  (read in parallel.distributed.init).
- ``MXNET_TEST_TPU``: selects the real-chip test lane (tests/conftest.py).
- ``MXNET_EAGER_JIT``: eager jit-cache fast path on the imperative dispatch
  seam (default 1; see ndarray/dispatch_cache.py, ≙ the reference's
  CachedOp amortization of per-op launch cost).
- ``MXNET_EAGER_JIT_CACHE_SIZE``: executable LRU capacity (default 1024).
- ``MXNET_MP_START_METHOD``: DataLoader process-worker start method
  (default ``spawn``; ``fork`` is an explicit opt-in — the parent is
  always multi-threaded and fork can deadlock children on inherited
  locks).
- ``MXNET_BENCH_FORCE_SWEEP``: run the TPU-gated bench sweep branch
  (resnet config sweep) on CPU too, so the sweep and
  headline-selection code paths are exercised before first chip contact.
- ``MXNET_FAULT_SPEC``: deterministic fault injection —
  ``<seam>:fail[:times[:Error]]``, comma-separated (e.g.
  ``checkpoint.write:fail:2``); see :mod:`mxnet_tpu.fault` for the seam
  list.  Read lazily at the first seam check so spawned DataLoader
  workers inherit it.
- ``MXNET_FAULT_MAX_RETRIES``: bounded retry budget for transient errors
  at the hardened seams (kvstore push/pull, host collectives,
  distributed.init; default 3).
- ``MXNET_FAULT_BACKOFF_MS``: first-retry backoff seed in ms (doubles per
  retry, full jitter, 30s cap; default 100).  Also seeds the
  between-restart backoff of ``checkpoint.run_with_recovery``.
- ``MXNET_TELEMETRY_PORT``: opt-in background HTTP telemetry endpoint
  (``/metrics`` Prometheus text, ``/snapshot`` JSON, ``/healthz``) on
  127.0.0.1:<port>, started at import.  Unset/0 = no server (metric
  RECORDING is always on and costs nothing on the op hot path — see
  :mod:`mxnet_tpu.telemetry`).
- ``MXNET_TELEMETRY_TIMELINE_STEPS``: step-timeline ring capacity
  (completed per-step phase records kept for snapshot(); default 256).
- ``MXNET_TELEMETRY_COMPILE_EVENTS``: compile-event ring capacity
  (fresh jax.jit traces kept with elapsed + cause; default 512).
- ``MXNET_TELEMETRY_AGG_EVERY``: cross-rank telemetry aggregation
  stride — every N-th step-boundary tick each rank publishes its
  snapshot to ``MXNET_TELEMETRY_AGG_DIR`` and rank 0 merges the peers'
  into rank-labeled families + per-phase skew histograms (default 0 =
  off; pure host-side file IO, never a device collective — see
  :mod:`mxnet_tpu.telemetry_agg`).
- ``MXNET_TELEMETRY_AGG_DIR``: the shared directory those per-rank
  snapshot files live in (unset = aggregation off).
- ``MXNET_TELEMETRY_AGG_TRANSPORT``: snapshot-gather transport for the
  cross-rank aggregator — ``file`` (default; the shared-directory
  gather above) or ``kv`` (the jax.distributed KV store, for pods
  without a shared filesystem).  Black-box crash dumps stay file-based
  either way: the distributed runtime is presumed dead when they are
  written.
- ``MXNET_FLIGHT_RECORDER``: the distributed flight recorder — an
  always-on preallocated ring stamping every collective issue site
  with a per-rank sequence number + tag digest, plus step/fault/
  compile/lifecycle context events (default 1; see
  :mod:`mxnet_tpu.flight_recorder` and README "Observability").
- ``MXNET_FLIGHT_RECORDER_CAP``: flight-recorder ring capacity in
  events (default 4096).
- ``MXNET_FLIGHT_DIR``: directory for ``blackbox.rank<N>.json`` crash
  dumps (default = ``MXNET_TELEMETRY_AGG_DIR``; with neither set the
  dumps are skipped).
- ``MXNET_TUNE``: the autotuning warm path — resolve knob values from
  the persistent tuning DB when a ``bench.py --tune`` run stored a
  winner for this signature/device/jax fingerprint (default 0; the
  warm path only ever REPLAYS, online exploration stays off — see
  :mod:`mxnet_tpu.tuning`).  Explicit env pins always beat the DB.
- ``MXNET_TUNE_DB_DIR``: directory for the persistent tuning DB the
  warm path reads and ``bench.py --tune`` writes (unset = no DB, the
  warm path resolves defaults even with ``MXNET_TUNE=1``).
- ``MXNET_LEDGER_SKEW_THRESHOLD``: cross-rank collective-ledger
  position divergence (max - min of
  ``mxnet_collective_ledger_position`` at a merge) that arms the
  pre-hang alert; sustained for ``MXNET_LEDGER_SKEW_WINDOWS``
  consecutive aggregation merges it fires one lifecycle alert per
  episode (default 0 = off; same SLO-hook pattern as the goodput
  breach — see :mod:`mxnet_tpu.telemetry_agg`).
- ``MXNET_LEDGER_SKEW_WINDOWS``: consecutive above-threshold merges
  before the ledger-skew alert fires (default 3).
- ``MXNET_GOODPUT_SLO``: goodput-ratio SLO in [0, 1] — when the
  per-window (per completed step) productive ratio stays below it for
  ``MXNET_GOODPUT_SLO_WINDOWS`` consecutive windows, a lifecycle
  alert event fires and ``mxnet_goodput_slo_breaches_total``
  increments (default 0 = off).
- ``MXNET_GOODPUT_SLO_WINDOWS``: consecutive below-SLO windows before
  the alert fires (default 3).
- ``MXNET_TRACE_REQUESTS``: per-request serving span traces (queue wait
  → prefill → per-decode-step → sample → finish; default 1 — see
  :mod:`mxnet_tpu.serving.tracing` and the ``/v1/requests`` route).
- ``MXNET_TRACE_KEEP_SLOWEST``: tail-based retention — the N slowest
  completed request traces are always kept (default 16; error/evicted
  traces are kept regardless).
- ``MXNET_DEVICE_PEAK_FLOPS``: per-device peak FLOP/s override for the
  online MFU gauge (default 0 = TPU device-kind table; unknown peak =
  the gauge stays absent — see :mod:`mxnet_tpu.introspection`).
- ``MXNET_PREFETCH_BUFFER``: device-prefetch queue depth for
  ``DataLoader(prefetch_to_device=...)`` / ``TrainStep.run`` (default 2;
  0 disables the background pipeline — see gluon/data/prefetcher.py).
- ``MXNET_ALLREDUCE_BUCKET_MB``: gradient-bucket size cap in MiB for the
  fused allreduce path (default 32; 0 disables fusion and every key gets
  its own collective — see parallel/bucketing.py).
- ``MXNET_ZERO``: ZeRO-1 optimizer-state sharding on the bucketed
  dense-grad path (default 0 = replicated optimizer state).  Each flat
  grad bucket becomes reduce-scatter → this-rank's-shard optimizer
  update → all-gather, with momentum/Adam moments permanently sharded
  1/dp per rank — see :mod:`mxnet_tpu.parallel.zero`.  Requires
  bucketing on (``MXNET_ALLREDUCE_BUCKET_MB`` > 0) and an optimizer
  with a flat sharded update (SGD/Adam); everything else falls back to
  the replicated path per key.
- ``MXNET_CHECKPOINT_ASYNC``: default for ``CheckpointManager.save``'s
  ``async_`` parameter (0/unset = synchronous saves; explicit
  ``async_=`` always wins).
- ``MXNET_WATCHDOG_TIMEOUT_S``: per-step stall deadline in seconds for the
  lifecycle watchdog (default 0 = off; ``env.apply_env`` starts the
  watchdog when set — see :mod:`mxnet_tpu.lifecycle`).
- ``MXNET_WATCHDOG_ABORT``: whether a tripped watchdog exits the process
  (status ``lifecycle.EXIT_STALLED``) after writing the diagnosis file
  (default 1; 0 = diagnose only).
- ``MXNET_WATCHDOG_DIR``: directory for watchdog stall-diagnosis files
  (default the working directory).
- ``MXNET_GRACE_PERIOD_S``: seconds between a preemption signal and a
  forced exit when the training loop has not honored the stop (default
  0 = no forced exit; match the scheduler's SIGTERM→SIGKILL grace).
- ``MXNET_PREEMPTION_CHECKPOINT``: publish a final synchronous checkpoint
  on a graceful preemption stop (default 1).
- ``MXNET_LIFECYCLE_SIGNALS``: ``parallel.distributed.init`` installs the
  graceful SIGTERM/SIGINT handlers for multi-process jobs (default 1;
  0 = the embedder owns signal dispositions).
- ``MXNET_STOP_SYNC_EVERY``: issue the multi-process stop-agreement
  collective every N-th ``lifecycle.check_stop()`` call (default 1;
  larger N amortizes the per-step scalar all-reduce, stop latency grows
  to at most N steps).
- ``MXNET_SERVING_PORT``: default port for ``serving.serve``'s HTTP
  endpoint (the inference routes mount beside the telemetry
  ``/metrics`` on one 127.0.0.1 server; 0/unset = pick a free port).
- ``MXNET_SERVING_MAX_BATCH``: decode-batch admission cap for the
  serving engine (default 8; must fit the largest batch bucket).
- ``MXNET_SERVING_BATCH_BUCKETS``: comma-separated decode batch-size
  buckets the engine AOT-compiles (default ``1,2,4,8``; active rows pad
  up to the nearest bucket so every step hits a compiled signature).
- ``MXNET_SERVING_PREFILL_BUCKETS``: comma-separated prompt-length
  buckets for the prefill executable (default ``32,64,128``; prompts
  right-pad up — causal attention keeps real-position logits exact).
- ``MXNET_SERVING_QUEUE``: admission-queue bound (default 64; a full
  queue rejects with a clean backpressure error, HTTP 429).
- ``MXNET_SERVING_KV_PAGES``: KV-cache pool size in pages (default 512;
  page 0 is the reserved scratch page — see serving/kvcache.py).
- ``MXNET_SERVING_PAGE_SIZE``: tokens per KV page (default 16).
- ``MXNET_SERVING_DEADLINE_MS``: default per-request deadline in ms
  covering queueing + generation (default 0 = none; per-request
  ``deadline_ms`` overrides).
- ``MXNET_FLEET_REPLICAS``: serving-fleet replica count behind the
  router (default 2; ``serving.fleet.serve_fleet`` spawns this many
  real engine processes — see :mod:`mxnet_tpu.serving.fleet`).
- ``MXNET_FLEET_HEDGE_MS``: floor in ms for the hedged-duplicate delay
  (default 50; the effective delay is max(this, observed p99 dispatch
  latency) — a slow replica gets one duplicate on a peer, first winner
  cancels the loser by request id).
- ``MXNET_FLEET_RETRY_BUDGET``: per-request transient-retry budget for
  router→replica dispatch (default 2; rides the fault.py
  ``call_with_retries`` policy with full-jitter backoff).
- ``MXNET_FLEET_PROBE_INTERVAL_MS``: router health-probe period in ms
  (default 250; a SIGKILLed replica is detected within ~4 missed
  probes, well under the 1s detection budget).
- ``MXNET_FLEET_EJECT_THRESHOLD``: consecutive dispatch/probe failures
  before the circuit breaker ejects a replica (default 3; re-admission
  goes through bounded half-open probe traffic).
- ``MXNET_PLANNER_MESH``: default mesh for the sharding planner
  (``auto`` or an explicit ``dp=4,tp=2`` spec — see
  :mod:`mxnet_tpu.parallel.planner`).
- ``MXNET_PLANNER_HBM_GB``: per-device HBM budget in GiB the planner's
  auto mesh selection plans against (default 16.0; config, not probed,
  so every SPMD peer selects the same mesh).
- ``MXNET_PLANNER_PIPELINE_IN_JIT``: feed traced pipeline stage params
  into shard_map with ``P(pp)`` in_specs instead of the jax-0.4.37
  GSPMD replicated workaround (default 0; the ROADMAP "re-test after
  jax upgrade" item is now this one flag).
- ``MXNET_PLANNER_REPORT``: print the planner's ``visualize_sharding``
  report whenever a plan is computed (default 0).
- ``MXNET_GRAPH_PASSES``: comma-separated graph-pass selection; plain
  names replace the default list, ``-name`` entries subtract from it
  (unset = the default catalog).
- ``MXNET_GRAPH_FUSE_CAP``: max ops per fused elementwise chain in the
  ``fuse_elemwise_chains`` pass (default 16; < 2 disables fusion).
- ``MXNET_SUBGRAPH_BACKEND``: subgraph backend applied automatically at
  Module bind time (see :mod:`mxnet_tpu.subgraph`; the backends are
  sugar over the graph-compiler pipeline; unset = none).
- ``MXNET_RESHARD_INFLIGHT_MB``: in-flight byte budget per live
  resharding transfer round (default 64 MiB; the arXiv:2112.01075
  memory bound — see :mod:`mxnet_tpu.parallel.resharding`).
- ``MXNET_NUM_WORKERS``: launcher-provided world size for
  ``parallel.distributed.init`` (``DMLC_NUM_WORKER`` is the legacy
  alias; default 1 = single process).
- ``MXNET_WORKER_ID``: launcher-provided rank for
  ``parallel.distributed.init`` and the checkpoint manager's
  primary-election sweep (``DMLC_WORKER_ID`` is the legacy alias).
  Read from the LAUNCHER env on purpose — rank must be knowable before
  the jax backend initializes.

Accepted-but-subsumed (XLA owns the concern; reads return the default and
``describe()`` says why):

- ``MXNET_EXEC_BULK_EXEC_TRAIN`` / ``MXNET_EXEC_BULK_EXEC_INFERENCE`` /
  ``MXNET_EXEC_ENABLE_INPLACE``: operator bulking/fusion/in-place planning
  is XLA's fusion + buffer-assignment pass.
- ``MXNET_ENFORCE_DETERMINISM``: XLA:TPU kernels are deterministic by
  construction (no atomics-race reductions); the switch therefore asserts
  rather than changes behavior.
- ``MXNET_GPU_MEM_POOL_RESERVE``: HBM pooling is the XLA allocator's
  (``XLA_PYTHON_CLIENT_MEM_FRACTION`` controls the reservation).
"""
from __future__ import annotations

import os

__all__ = ["get_int", "get_str", "get_bool", "cpu_worker_nthreads",
           "kvstore_bigarray_bound", "describe", "apply_env"]

_SUBSUMED = {
    "MXNET_EXEC_BULK_EXEC_TRAIN": "XLA fusion owns operator bulking",
    "MXNET_EXEC_BULK_EXEC_INFERENCE": "XLA fusion owns operator bulking",
    "MXNET_EXEC_ENABLE_INPLACE": "XLA buffer assignment owns in-place",
    "MXNET_ENFORCE_DETERMINISM": "XLA:TPU kernels are deterministic",
    "MXNET_GPU_MEM_POOL_RESERVE":
        "XLA allocator owns HBM pooling (XLA_PYTHON_CLIENT_MEM_FRACTION)",
}


def get_str(name, default=None):
    return os.environ.get(name, default)


def get_int(name, default=0):
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return int(v)
    except ValueError:
        import warnings

        warnings.warn(f"{name}={v!r} is not an integer; using {default}",
                      stacklevel=2)
        return default


def get_bool(name, default=False):
    v = os.environ.get(name)
    if v is None:
        return default
    return v.lower() in ("1", "true", "yes", "on")


def cpu_worker_nthreads():
    """Default worker-pool width for decode/augment stages
    (reference: MXNET_CPU_WORKER_NTHREADS, default 1 there — default 4
    here since the TPU input pipeline assumes a threaded decode stage)."""
    return max(1, get_int("MXNET_CPU_WORKER_NTHREADS", 4))


def kvstore_bigarray_bound():
    """Elements above which a kvstore value gets its own collective
    (reference: MXNET_KVSTORE_BIGARRAY_BOUND, default 1e6)."""
    return get_int("MXNET_KVSTORE_BIGARRAY_BOUND", 1000000)


def prefetch_buffer():
    """Device-prefetch queue depth (MXNET_PREFETCH_BUFFER, default 2;
    0 disables the background prefetch pipeline)."""
    return max(0, get_int("MXNET_PREFETCH_BUFFER", 2))


def allreduce_bucket_mb():
    """Fused-allreduce gradient-bucket cap in MiB
    (MXNET_ALLREDUCE_BUCKET_MB, default 32; 0 disables fusion)."""
    return max(0, get_int("MXNET_ALLREDUCE_BUCKET_MB", 32))


def zero_enabled():
    """ZeRO-1 optimizer-state sharding on the bucketed grad path
    (MXNET_ZERO, default off; parallel/zero.py)."""
    return get_bool("MXNET_ZERO", False)


def checkpoint_async_default():
    """Default for CheckpointManager.save(async_=None)
    (MXNET_CHECKPOINT_ASYNC, default off)."""
    return get_bool("MXNET_CHECKPOINT_ASYNC", False)


def get_float(name, default=0.0):
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return float(v)
    except ValueError:
        import warnings

        warnings.warn(f"{name}={v!r} is not a number; using {default}",
                      stacklevel=2)
        return default


def watchdog_timeout_s():
    """Per-step stall deadline for the lifecycle watchdog
    (MXNET_WATCHDOG_TIMEOUT_S, default 0 = watchdog off)."""
    return max(0.0, get_float("MXNET_WATCHDOG_TIMEOUT_S", 0.0))


def grace_period_s():
    """Signal→forced-exit deadline for graceful preemption
    (MXNET_GRACE_PERIOD_S, default 0 = no forced exit)."""
    return max(0.0, get_float("MXNET_GRACE_PERIOD_S", 0.0))


def preemption_checkpoint_default():
    """Whether a graceful preemption stop publishes a final synchronous
    checkpoint (MXNET_PREEMPTION_CHECKPOINT, default on)."""
    return get_bool("MXNET_PREEMPTION_CHECKPOINT", True)


def stop_sync_every():
    """Issue the multi-process stop-agreement collective every N-th
    check_stop() call (MXNET_STOP_SYNC_EVERY, default 1 = every step
    boundary; raise to amortize on very short steps — stop latency grows
    to at most N steps)."""
    return max(1, get_int("MXNET_STOP_SYNC_EVERY", 1))


def serving_port():
    """Default port for serving.serve's HTTP endpoint
    (MXNET_SERVING_PORT, default 0 = pick a free port)."""
    return max(0, get_int("MXNET_SERVING_PORT", 0))


def serving_max_batch():
    """Serving decode-batch admission cap (MXNET_SERVING_MAX_BATCH,
    default 8)."""
    return max(1, get_int("MXNET_SERVING_MAX_BATCH", 8))


def serving_batch_buckets():
    """Decode batch-size bucket spec (MXNET_SERVING_BATCH_BUCKETS,
    default "1,2,4,8")."""
    return get_str("MXNET_SERVING_BATCH_BUCKETS", "1,2,4,8")


def serving_prefill_buckets():
    """Prompt-length bucket spec (MXNET_SERVING_PREFILL_BUCKETS,
    default "32,64,128")."""
    return get_str("MXNET_SERVING_PREFILL_BUCKETS", "32,64,128")


def serving_queue_bound():
    """Serving admission-queue bound (MXNET_SERVING_QUEUE, default 64)."""
    return max(1, get_int("MXNET_SERVING_QUEUE", 64))


def serving_kv_pages():
    """KV-cache pool pages (MXNET_SERVING_KV_PAGES, default 512; page 0
    is the reserved scratch page)."""
    return max(2, get_int("MXNET_SERVING_KV_PAGES", 512))


def serving_page_size():
    """Tokens per KV-cache page (MXNET_SERVING_PAGE_SIZE, default 16)."""
    return max(1, get_int("MXNET_SERVING_PAGE_SIZE", 16))


def serving_deadline_ms():
    """Default per-request serving deadline in ms
    (MXNET_SERVING_DEADLINE_MS, default 0 = none)."""
    return max(0, get_int("MXNET_SERVING_DEADLINE_MS", 0))


def fleet_replicas():
    """Serving-fleet replica count behind the router
    (MXNET_FLEET_REPLICAS, default 2; serving/fleet)."""
    return max(1, get_int("MXNET_FLEET_REPLICAS", 2))


def fleet_hedge_ms():
    """Hedged-duplicate delay floor in ms (MXNET_FLEET_HEDGE_MS,
    default 50; the router hedges at max(floor, observed p99))."""
    return max(0, get_int("MXNET_FLEET_HEDGE_MS", 50))


def fleet_retry_budget():
    """Per-request transient-retry budget for router→replica dispatch
    (MXNET_FLEET_RETRY_BUDGET, default 2)."""
    return max(0, get_int("MXNET_FLEET_RETRY_BUDGET", 2))


def fleet_probe_interval_ms():
    """Router health-probe period in ms (MXNET_FLEET_PROBE_INTERVAL_MS,
    default 250 — four missed probes still detect a dead replica well
    inside the 1s budget)."""
    return max(10, get_int("MXNET_FLEET_PROBE_INTERVAL_MS", 250))


def fleet_eject_threshold():
    """Consecutive dispatch/probe failures before the circuit breaker
    ejects a replica (MXNET_FLEET_EJECT_THRESHOLD, default 3)."""
    return max(1, get_int("MXNET_FLEET_EJECT_THRESHOLD", 3))


def planner_mesh():
    """Default mesh for PlannerConfig(mesh=None): "auto" or an explicit
    "dp=4,tp=2"-style spec (MXNET_PLANNER_MESH, default auto;
    parallel/planner)."""
    return get_str("MXNET_PLANNER_MESH", "auto")


def planner_hbm_gb():
    """Per-device HBM budget in GiB for the planner's auto mesh
    selection (MXNET_PLANNER_HBM_GB, default 16.0 — a v5e-class chip;
    the budget is config, not probed, so every SPMD peer plans against
    the same number)."""
    v = get_float("MXNET_PLANNER_HBM_GB", 16.0)
    return v if v > 0 else 16.0


def planner_pipeline_in_jit():
    """Use P(pp) in_specs for traced pipeline stage params instead of
    the jax-0.4.37 GSPMD replicated workaround
    (MXNET_PLANNER_PIPELINE_IN_JIT, default 0 — flip after a jax
    upgrade proves the weight-stationary in-jit sharding correct; see
    parallel/pipeline_parallel.py)."""
    return get_bool("MXNET_PLANNER_PIPELINE_IN_JIT", False)


def planner_report():
    """Print the visualize_sharding report whenever a plan is computed
    (MXNET_PLANNER_REPORT, default 0)."""
    return get_bool("MXNET_PLANNER_REPORT", False)


def graph_passes():
    """Graph-pass selection spec (MXNET_GRAPH_PASSES; unset = default
    catalog, "-name" subtracts — parsed by graph.selected_pass_names)."""
    return get_str("MXNET_GRAPH_PASSES", "")


def graph_fuse_cap():
    """Max ops per fused elementwise chain (MXNET_GRAPH_FUSE_CAP,
    default 16; < 2 disables the fusion pass)."""
    return get_int("MXNET_GRAPH_FUSE_CAP", 16)


def reshard_inflight_mb():
    """Bounded in-flight byte budget per live-resharding transfer
    round (MXNET_RESHARD_INFLIGHT_MB, default 64 MiB; see
    parallel/resharding.py — the arXiv:2112.01075 memory bound)."""
    return max(1, get_int("MXNET_RESHARD_INFLIGHT_MB", 64))


def launcher_rank():
    """Launcher-provided rank from MXNET_WORKER_ID / DMLC_WORKER_ID —
    the LAUNCHER env on purpose, never ``jax.process_index()``: rank
    must be knowable without initializing the jax backend (the PR 2
    checkpoint-primary-election precedent).  One implementation shared
    by the telemetry aggregator and the flight recorder, so a dump's
    rank filename and the snapshot's rank label can never disagree."""
    for name in ("MXNET_WORKER_ID", "DMLC_WORKER_ID"):
        v = os.environ.get(name)
        if v:
            try:
                return int(v)
            except ValueError:
                pass
    return 0


def launcher_world():
    """Launcher-provided world size (MXNET_NUM_WORKERS /
    DMLC_NUM_WORKER; default 1) — same backend-free contract as
    :func:`launcher_rank`."""
    for name in ("MXNET_NUM_WORKERS", "DMLC_NUM_WORKER"):
        v = os.environ.get(name)
        if v:
            try:
                return max(1, int(v))
            except ValueError:
                pass
    return 1


def telemetry_agg_every():
    """Cross-rank telemetry aggregation stride: publish/merge per-rank
    snapshots every N-th step-boundary tick (MXNET_TELEMETRY_AGG_EVERY,
    default 0 = aggregation off; mxnet_tpu/telemetry_agg.py)."""
    return max(0, get_int("MXNET_TELEMETRY_AGG_EVERY", 0))


def telemetry_agg_dir():
    """Shared directory for the per-rank snapshot files the cross-rank
    aggregator gathers (MXNET_TELEMETRY_AGG_DIR; required for
    aggregation — unset leaves it off even with a stride set)."""
    return get_str("MXNET_TELEMETRY_AGG_DIR")


def telemetry_agg_transport():
    """Cross-rank snapshot-gather transport: ``file`` (shared-dir
    gather, the default) or ``kv`` (jax.distributed KV store —
    MXNET_TELEMETRY_AGG_TRANSPORT; black-box dumps stay file-based
    regardless, the runtime is presumed dead when they are written)."""
    v = (get_str("MXNET_TELEMETRY_AGG_TRANSPORT", "file") or
         "file").strip().lower()
    return v if v in ("file", "kv") else "file"


def flight_recorder_enabled():
    """Distributed flight recorder gate (MXNET_FLIGHT_RECORDER,
    default on; mxnet_tpu/flight_recorder.py)."""
    return get_bool("MXNET_FLIGHT_RECORDER", True)


def flight_recorder_cap():
    """Flight-recorder ring capacity in events
    (MXNET_FLIGHT_RECORDER_CAP, default 4096)."""
    return max(8, get_int("MXNET_FLIGHT_RECORDER_CAP", 4096))


def flight_dir():
    """Directory for black-box crash dumps (MXNET_FLIGHT_DIR, default
    = MXNET_TELEMETRY_AGG_DIR — the same gather the telemetry
    aggregation uses; None when neither is set → dumps are skipped)."""
    return get_str("MXNET_FLIGHT_DIR") or telemetry_agg_dir()


def tune_enabled():
    """Autotuning warm-path gate (MXNET_TUNE, default off): resolve
    knob values from the persistent tuning DB.  Replay only — the warm
    path never searches (mxnet_tpu/tuning)."""
    return get_bool("MXNET_TUNE", False)


def tune_db_dir():
    """Directory for the persistent tuning DB (MXNET_TUNE_DB_DIR;
    unset = no DB — bench.py --tune needs it to persist winners and
    the warm path needs it to replay them)."""
    return get_str("MXNET_TUNE_DB_DIR")


def ledger_skew_threshold():
    """Cross-rank collective-ledger position divergence that arms the
    pre-hang alert (MXNET_LEDGER_SKEW_THRESHOLD, default 0 = off;
    telemetry_agg's merge hook)."""
    return max(0, get_int("MXNET_LEDGER_SKEW_THRESHOLD", 0))


def ledger_skew_windows():
    """Consecutive above-threshold aggregation merges before the
    ledger-skew alert fires (MXNET_LEDGER_SKEW_WINDOWS, default 3)."""
    return max(1, get_int("MXNET_LEDGER_SKEW_WINDOWS", 3))


def goodput_slo():
    """Goodput-ratio SLO threshold in [0, 1] (MXNET_GOODPUT_SLO,
    default 0 = alerting off)."""
    return min(1.0, max(0.0, get_float("MXNET_GOODPUT_SLO", 0.0)))


def goodput_slo_windows():
    """Consecutive below-SLO windows (completed steps) before the
    goodput alert fires (MXNET_GOODPUT_SLO_WINDOWS, default 3)."""
    return max(1, get_int("MXNET_GOODPUT_SLO_WINDOWS", 3))


def trace_requests():
    """Per-request serving trace recording (MXNET_TRACE_REQUESTS,
    default 1; 0 disables span/event capture — the bench A/B knob;
    serving/tracing.py)."""
    return get_bool("MXNET_TRACE_REQUESTS", True)


def trace_keep_slowest():
    """Tail-based retention: how many of the SLOWEST completed request
    traces are always kept alongside the recent ring and the
    error/evicted set (MXNET_TRACE_KEEP_SLOWEST, default 16)."""
    return max(1, get_int("MXNET_TRACE_KEEP_SLOWEST", 16))


def guard_enabled():
    """Numerical-integrity guard master gate (MXNET_GUARD, default 0;
    mxnet_tpu/guard.py — the fused sentinel check + skip/rewind
    remediation ladder)."""
    return get_bool("MXNET_GUARD", False)


def guard_window():
    """Trailing robust-window length for the guard's loss/grad-norm
    spike baselines and the anomaly counter (MXNET_GUARD_WINDOW,
    default 64 steps)."""
    return max(8, get_int("MXNET_GUARD_WINDOW", 64))


def guard_loss_spike():
    """Robust-z threshold above the window median that classifies a
    loss as loss_spike (MXNET_GUARD_LOSS_SPIKE, default 10.0;
    <= 0 disables the loss-spike sentinel)."""
    return get_float("MXNET_GUARD_LOSS_SPIKE", 10.0)


def guard_grad_spike():
    """Robust-z threshold above the window median that classifies a
    global grad-norm as grad_anomaly (MXNET_GUARD_GRAD_SPIKE,
    default 10.0; <= 0 disables the grad-anomaly sentinel)."""
    return get_float("MXNET_GUARD_GRAD_SPIKE", 10.0)


def guard_skip():
    """Skip-step tier of the remediation ladder: zero the update on an
    anomalous verdict (MXNET_GUARD_SKIP, default 1; 0 = verdict-only
    observation mode, updates always commit)."""
    return get_bool("MXNET_GUARD_SKIP", True)


def guard_rewind_after():
    """Anomalous verdicts within the trailing window before the ladder
    escalates from skip to a latest-valid-checkpoint rewind
    (MXNET_GUARD_REWIND_AFTER, default 0 = rewind tier off; needs
    Guard.bind_rewind)."""
    return max(0, get_int("MXNET_GUARD_REWIND_AFTER", 0))


def guard_sync_every():
    """Issue the guard's agreement collective + host sync every N-th
    check (MXNET_GUARD_SYNC_EVERY, default 1 = every guarded step;
    off-cycle checks return the last agreed verdict — anomaly latency
    grows to at most N steps, the MXNET_STOP_SYNC_EVERY shape)."""
    return max(1, get_int("MXNET_GUARD_SYNC_EVERY", 1))


def guard_checksum():
    """Quarantine tier: stamp post-allreduce per-bucket checksums into
    the flight recorder for offline cross-rank SDC blame
    (MXNET_GUARD_CHECKSUM, default 0; independent of MXNET_GUARD so
    evidence collection can be armed without changing step
    semantics)."""
    return get_bool("MXNET_GUARD_CHECKSUM", False)


def guard_canary_every():
    """Deterministic canary-microbatch recompute + cross-rank digest
    vote every N guarded steps (MXNET_GUARD_CANARY_EVERY, default 0 =
    canary off; a minority digest raises NumericalDivergence on every
    rank)."""
    return max(0, get_int("MXNET_GUARD_CANARY_EVERY", 0))


def device_peak_flops_override():
    """Manual per-device peak FLOP/s for online MFU accounting
    (MXNET_DEVICE_PEAK_FLOPS, default 0 = use the TPU device-kind
    table; required on backends the table does not know — without a
    peak the MFU gauge stays absent; mxnet_tpu/introspection.py)."""
    return max(0.0, get_float("MXNET_DEVICE_PEAK_FLOPS", 0.0))


def describe():
    """One line per known var: current value and what it maps to."""
    lines = []
    wired = [
        ("MXNET_ENGINE_TYPE", "determinism switch (engine.set_engine_type)"),
        ("MXNET_NAN_CHECK", "NaN/Inf sanitizer at the dispatch seam "
         "(engine.set_nan_check)"),
        ("MXNET_TPU_MATMUL_PRECISION",
         "fp32 MXU precision (engine.set_matmul_precision)"),
        ("MXNET_SEED", "global RNG seed at import (random.seed)"),
        ("MXNET_CPU_WORKER_NTHREADS", "decode/augment pool width"),
        ("MXNET_PROFILER_AUTOSTART", "start profiler at import"),
        ("MXNET_KVSTORE_BIGARRAY_BOUND", "dist kvstore bucket threshold"),
        ("MXNET_COORDINATOR_ADDRESS", "jax.distributed coordinator"),
        ("MXNET_TEST_TPU", "real-chip test lane"),
        ("MXNET_EAGER_JIT", "eager jit-cache fast path (default 1; "
         "ndarray/dispatch_cache.py)"),
        ("MXNET_EAGER_JIT_CACHE_SIZE", "dispatch-cache LRU capacity "
         "(default 1024)"),
        ("MXNET_MP_START_METHOD", "DataLoader process-worker start method "
         "(default spawn)"),
        ("MXNET_BENCH_FORCE_SWEEP", "run TPU-gated bench sweeps on CPU"),
        ("MXNET_FAULT_SPEC", "deterministic fault injection spec "
         "(<seam>:fail[:times[:Error]], comma-separated; mxnet_tpu.fault)"),
        ("MXNET_FAULT_MAX_RETRIES", "transient-error retry budget at "
         "hardened seams (default 3)"),
        ("MXNET_FAULT_BACKOFF_MS", "retry/restart backoff seed in ms "
         "(default 100; doubles per retry, full jitter)"),
        ("MXNET_TELEMETRY_PORT", "opt-in HTTP telemetry endpoint "
         "(/metrics Prometheus, /snapshot JSON; unset/0 = off)"),
        ("MXNET_TELEMETRY_TIMELINE_STEPS", "step-timeline ring capacity "
         "(default 256; mxnet_tpu.telemetry)"),
        ("MXNET_TELEMETRY_COMPILE_EVENTS", "compile-event ring capacity "
         "(default 512; mxnet_tpu.telemetry)"),
        ("MXNET_TELEMETRY_AGG_EVERY", "cross-rank snapshot aggregation "
         "stride in step-boundary ticks (default 0 = off; "
         "mxnet_tpu/telemetry_agg.py)"),
        ("MXNET_TELEMETRY_AGG_DIR", "shared directory for per-rank "
         "snapshot files the aggregator merges (unset = aggregation "
         "off)"),
        ("MXNET_TELEMETRY_AGG_TRANSPORT", "cross-rank snapshot gather "
         "transport: file (shared dir, default) or kv (jax.distributed "
         "KV store; black-box dumps stay file-based)"),
        ("MXNET_FLIGHT_RECORDER", "distributed flight recorder: "
         "per-rank collective ledger ring (default 1; "
         "mxnet_tpu/flight_recorder.py)"),
        ("MXNET_FLIGHT_RECORDER_CAP", "flight-recorder ring capacity "
         "in events (default 4096)"),
        ("MXNET_FLIGHT_DIR", "directory for blackbox.rank<N>.json "
         "crash dumps (default = MXNET_TELEMETRY_AGG_DIR; neither set "
         "= dumps skipped)"),
        ("MXNET_TUNE", "autotuning warm path: replay stored winners "
         "from the tuning DB (default 0; env pins always win; "
         "mxnet_tpu/tuning)"),
        ("MXNET_TUNE_DB_DIR", "directory for the persistent tuning DB "
         "(bench.py --tune writes, MXNET_TUNE=1 replays; unset = no "
         "DB)"),
        ("MXNET_LEDGER_SKEW_THRESHOLD", "cross-rank ledger-position "
         "divergence arming the pre-hang alert (default 0 = off; "
         "sustained N merges fires once per episode)"),
        ("MXNET_LEDGER_SKEW_WINDOWS", "consecutive above-threshold "
         "aggregation merges before the ledger-skew alert fires "
         "(default 3)"),
        ("MXNET_GOODPUT_SLO", "goodput-ratio SLO threshold (default 0 "
         "= alerting off; below it for N windows fires the breach "
         "alert)"),
        ("MXNET_GOODPUT_SLO_WINDOWS", "consecutive below-SLO windows "
         "(completed steps) before the goodput alert fires "
         "(default 3)"),
        ("MXNET_TRACE_REQUESTS", "per-request serving span traces "
         "(default 1; 0 = no capture; serving/tracing.py)"),
        ("MXNET_TRACE_KEEP_SLOWEST", "slowest-N request traces always "
         "retained (tail-based retention; default 16)"),
        ("MXNET_DEVICE_PEAK_FLOPS", "per-device peak FLOP/s override "
         "for online MFU (default 0 = TPU device-kind table; "
         "mxnet_tpu/introspection.py)"),
        ("MXNET_GUARD", "numerical-integrity guard: fused sentinel "
         "check + skip/rewind ladder (default 0; mxnet_tpu/guard.py)"),
        ("MXNET_GUARD_WINDOW", "trailing robust-window length for the "
         "guard's spike baselines and anomaly counter (default 64)"),
        ("MXNET_GUARD_LOSS_SPIKE", "robust-z loss-spike threshold over "
         "the window median (default 10.0; <= 0 = sentinel off)"),
        ("MXNET_GUARD_GRAD_SPIKE", "robust-z grad-norm anomaly "
         "threshold over the window median (default 10.0; <= 0 = "
         "sentinel off)"),
        ("MXNET_GUARD_SKIP", "skip-step tier: zero the update on an "
         "anomalous verdict (default 1; 0 = observe only)"),
        ("MXNET_GUARD_REWIND_AFTER", "anomalies in the window before "
         "skip escalates to a latest-valid-checkpoint rewind "
         "(default 0 = rewind tier off)"),
        ("MXNET_GUARD_SYNC_EVERY", "guard agreement collective + host "
         "sync every N-th check (default 1; off-cycle returns the "
         "last agreed verdict)"),
        ("MXNET_GUARD_CHECKSUM", "quarantine tier: post-allreduce "
         "per-bucket checksum stamps for offline SDC blame "
         "(default 0)"),
        ("MXNET_GUARD_CANARY_EVERY", "deterministic canary recompute + "
         "cross-rank digest vote every N guarded steps (default 0 = "
         "off; minority digest raises NumericalDivergence)"),
        ("MXNET_PREFETCH_BUFFER", "device-prefetch queue depth "
         "(default 2; 0 = no background pipeline; "
         "gluon/data/prefetcher.py)"),
        ("MXNET_ALLREDUCE_BUCKET_MB", "fused-allreduce bucket cap in MiB "
         "(default 32; 0 = per-key collectives; parallel/bucketing.py)"),
        ("MXNET_ZERO", "ZeRO-1 optimizer-state sharding on the bucketed "
         "grad path (default 0 = replicated; parallel/zero.py)"),
        ("MXNET_CHECKPOINT_ASYNC", "default for CheckpointManager.save "
         "async_ (unset/0 = synchronous saves)"),
        ("MXNET_WATCHDOG_TIMEOUT_S", "per-step stall deadline in seconds "
         "(default 0 = watchdog off; mxnet_tpu.lifecycle)"),
        ("MXNET_WATCHDOG_ABORT", "tripped watchdog exits the process after "
         "the diagnosis dump (default 1; 0 = diagnose only)"),
        ("MXNET_WATCHDOG_DIR", "directory for watchdog stall-diagnosis "
         "files (default cwd)"),
        ("MXNET_GRACE_PERIOD_S", "preemption-signal → forced-exit deadline "
         "(default 0 = none; match the scheduler's SIGTERM grace)"),
        ("MXNET_PREEMPTION_CHECKPOINT", "final synchronous checkpoint on a "
         "graceful preemption stop (default 1)"),
        ("MXNET_LIFECYCLE_SIGNALS", "distributed.init installs graceful "
         "SIGTERM/SIGINT handlers (default 1)"),
        ("MXNET_STOP_SYNC_EVERY", "stop-agreement collective every N-th "
         "check_stop (default 1; N steps max stop latency)"),
        ("MXNET_SERVING_PORT", "serving.serve HTTP endpoint port "
         "(default 0 = pick free; routes mount beside /metrics)"),
        ("MXNET_SERVING_MAX_BATCH", "serving decode-batch admission cap "
         "(default 8)"),
        ("MXNET_SERVING_BATCH_BUCKETS", "decode batch-size buckets the "
         "engine AOT-compiles (default 1,2,4,8)"),
        ("MXNET_SERVING_PREFILL_BUCKETS", "prompt-length prefill buckets "
         "(default 32,64,128)"),
        ("MXNET_SERVING_QUEUE", "serving admission-queue bound "
         "(default 64; full = clean 429 rejection)"),
        ("MXNET_SERVING_KV_PAGES", "KV-cache pool pages (default 512; "
         "page 0 reserved as scratch; serving/kvcache.py)"),
        ("MXNET_SERVING_PAGE_SIZE", "tokens per KV-cache page "
         "(default 16)"),
        ("MXNET_SERVING_DEADLINE_MS", "default per-request serving "
         "deadline in ms (default 0 = none)"),
        ("MXNET_FLEET_REPLICAS", "serving-fleet replica count behind "
         "the router (default 2; serving/fleet)"),
        ("MXNET_FLEET_HEDGE_MS", "hedged-duplicate delay floor in ms "
         "(default 50; effective delay = max(floor, observed p99))"),
        ("MXNET_FLEET_RETRY_BUDGET", "per-request transient-retry "
         "budget for router→replica dispatch (default 2)"),
        ("MXNET_FLEET_PROBE_INTERVAL_MS", "router health-probe period "
         "in ms (default 250; dead-replica detection < 1s)"),
        ("MXNET_FLEET_EJECT_THRESHOLD", "consecutive failures before "
         "the circuit breaker ejects a replica (default 3)"),
        ("MXNET_PLANNER_MESH", "default planner mesh: auto or "
         "\"dp=4,tp=2\"-style spec (parallel/planner)"),
        ("MXNET_PLANNER_HBM_GB", "per-device HBM budget in GiB for "
         "planner auto mesh selection (default 16.0)"),
        ("MXNET_PLANNER_PIPELINE_IN_JIT", "P(pp) in_specs for traced "
         "pipeline stage params instead of the GSPMD replicated "
         "workaround (default 0; flip after a jax upgrade)"),
        ("MXNET_PLANNER_REPORT", "print the visualize_sharding report "
         "at plan time (default 0)"),
        ("MXNET_GRAPH_PASSES", "graph-pass selection (csv; \"-name\" "
         "subtracts from the default catalog; unset = defaults)"),
        ("MXNET_GRAPH_FUSE_CAP", "max ops per fused elementwise chain "
         "(default 16; < 2 disables fusion)"),
        ("MXNET_RESHARD_INFLIGHT_MB", "in-flight byte budget per live "
         "resharding transfer round (default 64 MiB; "
         "parallel/resharding.py)"),
        ("MXNET_SUBGRAPH_BACKEND", "subgraph backend applied at Module "
         "bind time (mxnet_tpu.subgraph; unset = none)"),
        ("MXNET_NUM_WORKERS", "launcher world size for distributed.init "
         "(alias DMLC_NUM_WORKER; default 1)"),
        ("MXNET_WORKER_ID", "launcher rank for distributed.init + "
         "checkpoint primary election (alias DMLC_WORKER_ID)"),
    ]
    for name, what in wired:
        lines.append(f"{name}={os.environ.get(name, '<unset>')} — {what}")
    for name, why in _SUBSUMED.items():
        lines.append(f"{name}={os.environ.get(name, '<unset>')} — subsumed: "
                     f"{why}")
    return "\n".join(lines)


def apply_env():
    """Apply import-time vars (called once from mxnet_tpu/__init__)."""
    seed = os.environ.get("MXNET_SEED")
    if seed:
        from . import random as _random

        _random.seed(int(seed))
    if get_bool("MXNET_PROFILER_AUTOSTART"):
        from . import profiler

        profiler.set_config(profile_all=True)
        profiler.start()
    if watchdog_timeout_s() > 0:
        from . import lifecycle

        lifecycle.start_watchdog()
    port = get_int("MXNET_TELEMETRY_PORT", 0)
    if port > 0:
        from . import telemetry

        try:
            telemetry.start_http_server(port)
        except OSError as e:
            # spawned DataLoader workers and same-host multi-rank peers
            # inherit the env var but cannot bind the parent's port —
            # telemetry recording still works, only the endpoint is theirs
            # to miss; crashing the import would kill the worker pool
            import warnings

            warnings.warn(
                f"MXNET_TELEMETRY_PORT={port}: endpoint not started "
                f"({e}); another process on this host (parent/rank 0?) "
                "likely holds the port", stacklevel=2)
