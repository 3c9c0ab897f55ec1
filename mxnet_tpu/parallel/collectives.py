"""Collective primitives over the device mesh.

Reference mapping (SURVEY.md §6.8): these replace the reference's reducers —
``CommCPU/CommDevice`` (src/kvstore/comm.h), tree allreduce (comm_tree.h),
NCCL (kvstore_nccl.h) and the ps-lite push/pull — with XLA collectives that
ride ICI/DCN.  Inside ``shard_map`` use the ``p*`` wrappers; at the array
level use the host-sharding helpers.

The equal-call-count contract
-----------------------------
Every SPMD peer must issue the SAME collectives in the SAME program
order — XLA collectives rendezvous by issue order, not by name, so a
rank that issues one extra (or one fewer) collective pairs every later
collective with the wrong peer op and the mesh hangs or computes
garbage.  Machine-enforced by ``python -m tools.check`` (pass
``collective-safety``, codes MXT001-MXT003; see README "Static
analysis").  Concretely:

- never issue a collective under a rank-conditional branch
  (``jax.process_index()``, ``kv.rank``, launcher-rank env vars).
  Uniform guards — ``jax.process_count()``, configuration every process
  constructs identically — are fine: all ranks take the same arm.
- never retry a collective unilaterally (PR 2): the peers never issue
  the matching re-run.  A transient interconnect failure escalates to
  ``checkpoint.run_with_recovery``'s whole-job restart; only
  single-process paths retry locally (see ``_combine_with_seam``).
- branches whose arms issue different collective counts must derive
  their condition from rank-uniform state.  Audited examples of the
  uniform kind: ``lifecycle.check_stop``'s agreement stride is a pure
  function of the per-process call COUNT (never of the local stop
  flag), and both of its loop call sites (``TrainStep.run``,
  ``Estimator.fit``) poll it exactly once per step on every rank;
  kvstore fusion plans are a deterministic function of the push-order
  (key, shape, dtype) signature, identical on every peer (PR 4).

Because issue order IS the rendezvous key, every Python-level issue
site here also stamps the distributed flight recorder
(:mod:`mxnet_tpu.flight_recorder`): a monotonic per-rank sequence
number + a digest of (op, shape, dtype, axis, generation), so a hang
or desync is blamable post-mortem from the per-rank black-box rings
(machine-enforced by mxtpu-check pass ``ledger-discipline``, MXT100).
"""
from __future__ import annotations

from functools import partial

__all__ = ["psum", "pmean", "all_gather", "reduce_scatter", "ppermute",
           "all_to_all", "allreduce_hosts", "allreduce_hosts_quantized",
           "allreduce_hosts_quantized_multi", "allreduce_any",
           "barrier", "shard_map", "place_global", "fetch_global"]


def place_global(host, sharding):
    """Place a host array as a global array with ``sharding`` without
    cross-host transfers.

    ``jax.device_put(x, sharding)`` raises in a multi-process job when
    the sharding spans non-addressable devices; build the global array
    from each process's addressable shards instead (every process holds
    the full value, the callback slices out the local shards).  Shared
    by every sharded-state owner (ShardedOptimizerUpdater,
    ZeroBucketEngine) so the multi-process placement workaround lives in
    exactly one place.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.process_count() == 1:
        return jax.device_put(jnp.asarray(host), sharding)
    host = np.asarray(host)
    return jax.make_array_from_callback(
        host.shape, sharding, lambda idx: host[idx])


def fetch_global(arr):
    """Host copy of a global array — the inverse of :func:`place_global`.

    ``np.asarray`` on an array whose sharding spans non-addressable
    devices raises in a multi-process job; gather the full value to
    every host first.  The gather is itself a collective, so callers
    must reach this uniformly on every process (harvest/save points
    already are: replans are deterministic plan functions and
    checkpoint saves happen at the same step on every peer).
    """
    import jax
    import numpy as np

    if jax.process_count() == 1:
        return np.asarray(arr)
    from jax.experimental import multihost_utils

    from .. import flight_recorder as _flight

    with _flight.collective("fetch_global",
                            shape=getattr(arr, "shape", None),
                            dtype=getattr(arr, "dtype", None)):
        return np.asarray(multihost_utils.process_allgather(arr,
                                                            tiled=True))


def shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-axes check off: every shard_map
    in this repo makes replication explicit through its collectives."""
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def shard_map_over_batch(fn, mesh, batch_axes):
    """``fn`` applied to each batch shard: every operand and every result
    is split on dim 0 over ``batch_axes`` of ``mesh`` and whole otherwise."""
    from jax.sharding import PartitionSpec as P

    spec = P(tuple(batch_axes))
    return shard_map(fn, mesh, in_specs=spec, out_specs=spec)


def psum(x, axis_name="dp"):
    import jax

    return jax.lax.psum(x, axis_name)


def pmean(x, axis_name="dp"):
    import jax

    return jax.lax.pmean(x, axis_name)


def all_gather(x, axis_name="dp", axis=0, tiled=True):
    import jax

    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name="dp", scatter_dimension=0):
    import jax

    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension,
                                tiled=True)


def ppermute(x, perm, axis_name="sp"):
    import jax

    return jax.lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name="sp", split_axis=0, concat_axis=0, tiled=True):
    import jax

    return jax.lax.all_to_all(x, axis_name, split_axis, concat_axis, tiled=tiled)


import functools


@functools.lru_cache(maxsize=64)
def _jitted_combine(combine_fn, mesh, n_local, static_args):
    """One jit per (combine_fn identity, mesh, n_local, static args) —
    host collectives sit on the training hot path, so per-call retracing
    (a fresh closure each push) must not happen."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.jit(lambda *leaves: combine_fn(*leaves, n_local,
                                              *static_args),
                   out_shardings=NamedSharding(mesh, P()))


def _cross_process_combine(local_leaves, combine_fn, static_args=()):
    """Shared scaffold for host-value collectives: ship each leaf as a
    global array sharded over all devices ('w' axis, one contribution per
    process replicated across its local devices), then run the cached
    jitted combine_fn over the stacked leaves.  combine_fn must be a
    MODULE-LEVEL function (stable identity for the jit cache) with
    signature (leaves..., n_local, *static_args)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(jax.devices(), ("w",))
    n_local = jax.local_device_count()

    def rep(a):
        a = jnp.asarray(a)
        return jnp.broadcast_to(a[None], (n_local,) + a.shape)

    globals_ = [jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("w")), rep(leaf)) for leaf in local_leaves]
    fn = _jitted_combine(combine_fn, mesh, n_local, tuple(static_args))
    return fn(*globals_)


def _sum_combine(a, nl):
    return a.sum(axis=0) / nl


def _combine_with_seam(local_leaves, combine_fn, static_args=(),
                       op="allreduce"):
    """Route a host-value collective through the ``collectives.allreduce``
    fault seam.  Single-process (tests, _testing_force paths): the full
    retry policy applies, so injected transient faults are absorbed end
    to end.  Multi-process SPMD: seam check only, NO local retry — a
    unilateral re-issue desyncs the peers' collective issue counts (they
    never issue the matching one, so the retry hangs the mesh); a real
    transient interconnect failure instead escalates to
    checkpoint.run_with_recovery, which restarts every process together —
    bounded backoff at the scope where retry is actually safe.

    Flight-recorder stamp: this is the single funnel every host-value
    collective flows through, so the ledger entry (``op`` + the lead
    leaf's shape/dtype) is stamped HERE — seam trip included, so a
    failed issue shows in the ring with its error."""
    import jax

    from .. import fault
    from .. import flight_recorder as _flight

    lead = local_leaves[0] if local_leaves else None
    with _flight.collective(op, shape=getattr(lead, "shape", None),
                            dtype=getattr(lead, "dtype", None),
                            axis="world"):
        if jax.process_count() == 1:
            return fault.call_with_retries(
                "collectives.allreduce", _cross_process_combine,
                local_leaves, combine_fn, static_args=static_args)
        fault.check("collectives.allreduce")
        return _cross_process_combine(local_leaves, combine_fn,
                                      static_args=static_args)


def allreduce_hosts(value, _testing_force=False):
    """Allreduce a host-local array across all processes' devices: builds a
    global array sharded over processes and psums it.  Used by the
    dist_tpu_sync KVStore (single psum ≙ push+pull, SURVEY.md §4.4), and
    by the numerical-integrity guard as its verdict-agreement primitive
    (one summed sentinel vector / one-hot canary-digest table per check;
    mxnet_tpu/guard.py — call-count-uniform like every collective here).

    Fault seam ``collectives.allreduce``; see ``_combine_with_seam`` for
    why transient-error retry happens here only single-process (SPMD
    retry is run_with_recovery's whole-job restart).  ``_testing_force``
    runs the real combine path on one process (tests and the bench's
    fused-vs-per-key curve, like the quantized variants)."""
    import jax

    from .. import fault

    if jax.process_count() == 1 and not _testing_force:
        fault.guard("collectives.allreduce")
        return value
    return _combine_with_seam((value,), _sum_combine, op="allreduce")


def allreduce_any(flag, _testing_force=False):
    """Cross-process logical-OR of a host-local bool in ONE collective —
    the agreement primitive for coordinated preemption stops
    (``lifecycle.check_stop``): every SPMD peer must call it at the same
    step boundary, and every peer sees the same verdict, so they all
    exit at the same step.  Single-process it is just the local flag
    (seam-guarded like its siblings)."""
    import jax

    from .. import fault

    if jax.process_count() == 1 and not _testing_force:
        fault.guard("collectives.allreduce")
        return bool(flag)
    import numpy as np
    import jax.numpy as jnp

    out = allreduce_hosts(jnp.asarray(bool(flag), jnp.float32),
                          _testing_force=_testing_force)
    return bool(np.asarray(out) > 0)


def barrier():
    import jax

    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        from .. import flight_recorder as _flight

        with _flight.collective("barrier"):
            multihost_utils.sync_global_devices("mxnet_tpu_barrier")


def _int8_quantize(v):
    """Per-tensor symmetric int8 quantization (scale, payload)."""
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(v)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(v / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dequant_sum_combine(qa, sa, nl, out_dtype):
    import jax.numpy as jnp

    # dequantize each contribution with its own scale, then sum;
    # the int8 payload is what crossed the network
    deq = qa.astype(jnp.float32) * sa.reshape(
        (-1,) + (1,) * (qa.ndim - 1))
    return (deq.sum(axis=0) / nl).astype(out_dtype)


def allreduce_hosts_quantized(value, _testing_force=False):
    """Bandwidth-compressed cross-process allreduce: each process ships an
    int8 payload + fp32 scale instead of fp32 (~4x less DCN/ICI traffic),
    dequantize-sum on receipt; result keeps the input dtype.

    Inspired by EQuARX (PAPERS.md: "Efficient Quantized AllReduce in XLA")
    — the XLA-native take on the reference's 2-bit kvstore compression,
    applied inside the collective rather than before it.  Max error per
    contribution is scale/2 = max|v|/254.
    """
    import jax

    from .. import fault

    if jax.process_count() == 1 and not _testing_force:
        fault.guard("collectives.allreduce")
        return value
    q, scale = _int8_quantize(value)
    return _combine_with_seam((q, scale), _dequant_sum_combine,
                              static_args=(value.dtype,),
                              op="allreduce_q8")


def _dequant_multi_combine(qa, sa, nl, sizes):
    import jax.numpy as jnp

    # per-segment scales: repeat each tensor's scale across its payload
    reps = jnp.repeat(sa, jnp.asarray(sizes), axis=1,
                      total_repeat_length=int(sum(sizes)))
    deq = qa.astype(jnp.float32) * reps
    return deq.sum(axis=0) / nl


def allreduce_hosts_quantized_multi(values, _testing_force=False):
    """Fused int8 allreduce of several tensors in ONE collective, with a
    PER-TENSOR scale — small-magnitude gradients bucketed next to a large
    one keep their own resolution (a single bucket-wide scale would round
    them to zero)."""
    import jax
    import jax.numpy as jnp

    from .. import fault

    if jax.process_count() == 1 and not _testing_force:
        fault.guard("collectives.allreduce")
        return list(values)
    qs, scales = zip(*[_int8_quantize(v.ravel()) for v in values])
    sizes = tuple(int(v.size) for v in values)
    flat_q = jnp.concatenate(qs)
    summed = _combine_with_seam((flat_q, jnp.stack(scales)),
                                _dequant_multi_combine,
                                static_args=(sizes,),
                                op="allreduce_q8_multi")
    out, off = [], 0
    for v, n in zip(values, sizes):
        out.append(summed[off:off + n].reshape(v.shape).astype(v.dtype))
        off += n
    return out
