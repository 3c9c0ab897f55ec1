"""What the check reads when a term of the delta rule is left out of **the
program**, and what its control reads, at the cell's own size on the chip:

    python3 chipbench/configs/ling3_flash_vl/faults.py --seeds 1,2 \
        --faults no_decay,no_delta --control

For each seed the plain float32 reference follows the cell's first step
once.  Against it are held, each through ``harness/check.py`` under the
cell's own limits: the program's first step with a fault planted in its path
(``planted``: the decay dropped, ``alpha = 1``, or the delta term dropped,
``beta k k^T`` left out of the state's update), which has to come out NOT
CORRECT by ``loss_gap``; with ``--sound`` the program as it is; with
``--control`` the reference one precision down.  One JSON line each
(``PERF.md`` keeps the readings).  ``--reference-only`` computes the faulty
losses with the reference's own ``drop`` instead (no program is built)."""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from contextlib import contextmanager
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)
CELL = "ling3_flash_vl.causal_seq8192.fused"
FAULTS = {"no_decay": ("decay",), "no_delta": ("delta",)}


@contextmanager
def planted(fault):
    """The program's chunked delta rule (``mxnet_tpu/ops/kda.py``) with one
    term left out, for what is traced inside the block.  ``"no_decay"``: the
    op sees a log-decay of 0, so ``alpha = 1`` in every product, forward
    and backward.  ``"no_delta"``: a chunk's ``beta k k^T`` is left out,
    inside the chunk (no triangular solve: ``U = beta V``) and across chunks
    (``W = 0``, so ``V~ = U``); the state is then ``S_t = Diag(alpha_t)
    S_{t-1} + beta_t k_t v_t^T``."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import kda

    make, prepare = kda._make_kda, kda._prepare

    def no_decay(chunk):
        op = make(chunk)
        return lambda q, k, v, g, beta: op(q, k, v, jnp.zeros_like(g), beta)

    def no_delta(q, k, v, g, beta):
        qg, kl, w, _, aqk, decay = prepare(q, k, v, g, beta)
        u = v.astype(jnp.float32) * beta.astype(jnp.float32)[..., None]
        return qg, kl, jnp.zeros_like(w), u, aqk, decay

    if fault == "no_decay":
        kda._make_kda = no_decay
    elif fault == "no_delta":
        kda._prepare = no_delta
    elif fault is not None:
        raise ValueError(f"no fault {fault!r}: {sorted(FAULTS)}")
    try:
        yield
    finally:
        kda._make_kda, kda._prepare = make, prepare


def first_step(cell, seed, fault=None):
    """What ``check.compare`` takes of the program's first ``check_steps``
    steps from ``seed``, with ``fault`` planted (None: the program as it
    is), through the cell's own driver and feed."""
    from chipbench.harness import loop

    spec, cfg = cell.spec, cell.cfg
    with planted(fault):
        runner = cell.driver.Runner(spec, cfg, cell.build,
                                    cell.reference.init_params(cfg, seed))
        feed = loop.open_feed(loop.make_pool(cell.build, cfg, spec, seed))
        try:
            got = loop.first_steps(runner, feed, spec["check_steps"])
        finally:
            feed.close()
    del runner
    gc.collect()
    return got


def reference_losses(reference, cfg, params, batch):
    """``{fault: loss}`` of one batch by the float32 reference's own
    ``drop``, a sample at a time; ``"whole"`` is the sound loss."""
    import jax
    import jax.numpy as jnp

    from chipbench.harness.precision import ops as make_ops

    ops = make_ops("float32")
    out = {}
    for name, drop in dict(FAULTS, whole=()).items():
        fn = jax.jit(partial(reference.loss_fn, cfg, ops, drop=drop))
        with jax.default_matmul_precision(ops.matmul):
            out[name] = float(jnp.mean(jnp.stack([
                fn(params, jnp.asarray(ids), jnp.asarray(labels))
                for ids, labels in zip(*batch)])))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="no_decay,no_delta")
    ap.add_argument("--sound", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--reference-only", action="store_true")
    args = ap.parse_args(argv)

    import mxnet_tpu  # noqa: F401  places the compile cache
    import jax

    from chipbench.harness import check, loop
    from chipbench.harness.cell import Cell, find_chips
    from chipbench.harness.precision import BELOW

    cell = Cell(args.workload)
    if find_chips(cell) is None:
        return 3
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec, cfg, reference = cell.spec, cell.cfg, cell.reference
    faults = [f for f in args.faults.split(",") if f]
    for seed in (int(s) for s in args.seeds.split(",")):
        pool = loop.make_pool(cell.build, cfg, spec, seed)
        if args.reference_only:
            got = reference_losses(reference, cfg,
                                   reference.init_params(cfg, seed), pool[0])
            print(json.dumps({"seed": seed, "losses": got, "loss_gap": {
                name: abs(loss - got["whole"]) / got["whole"]
                for name, loss in got.items() if name != "whole"}}),
                flush=True)
            continue
        # the program first, each a step of its own, then the reference:
        # its optimizer's step needs the chip to itself
        legs = {name: first_step(cell, seed, name) for name in faults}
        if args.sound:
            legs["sound"] = first_step(cell, seed)

        def follow(precision):
            return check.follow(reference, cfg, precision,
                                reference.init_params(cfg, seed),
                                pool[:spec["check_steps"]], spec)

        ref = follow("float32")
        if args.control:
            legs["control"] = follow(BELOW[spec["precision"]])
        for name, got in legs.items():
            stats, lines = check.compare(got, ref), []
            correct = check.verdict(stats, spec["limits"], lines.append)
            print(json.dumps({"seed": seed, "leg": name, "correct": correct,
                              "losses": got["losses"],
                              "ref_losses": ref["losses"], "stats": stats,
                              "failed": [ln.split(":")[0][6:] for ln in lines
                                         if "NOT CORRECT" in ln]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
