#!/usr/bin/env python
"""Allreduce / pushpull bandwidth harness.

Reference: ``tools/bandwidth/measure.py`` (the kvstore bandwidth tool the
BASELINE.md binding table cites: "KVStore allreduce BW" GB/s vs message
size).  TPU-native: the reduction is one jit'd ``psum`` over the device
mesh (what ``dist_tpu_sync`` pushpull lowers to), so the measured number is
the ICI/DCN collective bandwidth GSPMD achieves at each message size.

Usage::

    python tools/bandwidth_measure.py [--sizes-mb 1,4,16,64,256]
                                      [--iters 10] [--json]

On the virtual CPU mesh (JAX_PLATFORMS=cpu +
--xla_force_host_platform_device_count=8) the numbers are memcpy-bound —
useful for validating the harness, not the interconnect.

Reported metric: algorithmic bus bandwidth ``2*(n-1)/n * bytes / time``
(the standard allreduce accounting, comparable to nccl-tests / the
reference's tool).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# runnable from anywhere: the repo root (= mxnet_tpu's parent) sits next
# to tools/
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure_allreduce(size_bytes, iters=10, warmup=2, mesh=None):
    """Time a psum of `size_bytes` over all devices; returns (seconds/iter,
    bus_bandwidth_GB/s)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if mesh is None:
        mesh = Mesh(np.array(jax.devices()), ("dp",))
    n = mesh.size
    elems = max(size_bytes // 4, 1)
    # every device contributes its own `elems`-float vector and receives
    # the elementwise sum — the canonical allreduce setup (nccl-tests
    # semantics).  shard_map + lax.psum guarantees a true all-reduce in
    # the HLO (a reshard-to-replicated would compile to all-gather and
    # overstate bandwidth ~2x).
    x = jax.device_put(
        jnp.ones((n, elems), dtype=jnp.float32),
        NamedSharding(mesh, P("dp", None)))

    @jax.jit
    def allreduce(v):
        def f(local):
            return jax.lax.psum(local, "dp")

        return shard_map(f, mesh=mesh, in_specs=P("dp", None),
                         out_specs=P("dp", None))(v)

    out = allreduce(x)
    out.block_until_ready()
    if n > 1 and "all-reduce" not in \
            allreduce.lower(x).compile().as_text():
        raise RuntimeError("collective did not lower to all-reduce")
    for _ in range(warmup):
        allreduce(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = allreduce(x)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    bus_bytes = 2.0 * (n - 1) / n * elems * 4
    return dt, bus_bytes / dt / 1e9


def measure_pushpull(size_bytes, iters=10, warmup=2):
    """End-to-end kvstore pushpull (includes frontend overhead): GB/s of
    gradient bytes synchronized per second.

    Note: in a single-process single-worker session the dist kvstore's
    pushpull degenerates to a local buffer update (as in the reference), so
    this number reflects frontend/dispatch overhead; the interconnect
    figure is ``measure_allreduce`` / a real multi-process launch."""
    import mxnet_tpu as mx

    kv = mx.kv.create("dist_tpu_sync")
    elems = max(size_bytes // 4, 1)
    g = mx.nd.ones((elems,))
    kv.init(0, g)
    out = mx.nd.zeros((elems,))
    for _ in range(warmup):
        kv.push(0, g)
        kv.pull(0, out)
        out.wait_to_read()
    t0 = time.perf_counter()
    for _ in range(iters):
        kv.push(0, g)
        kv.pull(0, out)
    out.wait_to_read()
    dt = (time.perf_counter() - t0) / iters
    return dt, elems * 4 / dt / 1e9


# per-chip ICI bandwidth (GB/s, all links) by device kind substring —
# public figures, for the vs_peak column only
_ICI_PEAK = (("v5 lite", 400.0), ("v5e", 400.0), ("v5p", 1200.0),
             ("v4", 1200.0), ("v3", 700.0))


def _ici_peak():
    import jax

    try:
        kind = jax.devices()[0].device_kind.lower()
    except Exception:
        return None
    for sub, peak in _ICI_PEAK:
        if sub in kind:
            return peak
    return None


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes-mb", default="1,4,16,64",
                    help="comma-separated message sizes in MiB")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--mode", choices=["allreduce", "pushpull", "both"],
                    default="both")
    ap.add_argument("--json", action="store_true",
                    help="one JSON line per measurement")
    args = ap.parse_args(argv)

    import jax

    n = len(jax.devices())
    peak = _ici_peak()
    results = []
    for mb in [float(s) for s in args.sizes_mb.split(",")]:
        size = int(mb * 1024 * 1024)
        row = {"size_mb": mb, "devices": n}
        if args.mode in ("allreduce", "both"):
            dt, bw = measure_allreduce(size, iters=args.iters)
            row["allreduce_gbps"] = round(bw, 3)
            row["allreduce_ms"] = round(dt * 1e3, 3)
            if peak:
                row["vs_ici_peak"] = round(bw / peak, 4)
        if args.mode in ("pushpull", "both"):
            dt, bw = measure_pushpull(size, iters=args.iters)
            row["pushpull_gbps"] = round(bw, 3)
        results.append(row)
        if args.json:
            print(json.dumps(row), flush=True)
        else:
            print("  ".join(f"{k}={v}" for k, v in row.items()), flush=True)
    return results


if __name__ == "__main__":
    main()
