"""What the check reads when a term of the model is left out of **the
program**, and what its control reads, at the cell's own size on the chip:

    python3 chipbench/configs/phi4_mini_flash/faults.py --seeds 1,2 --control

For each seed the plain float32 reference follows the cell's first step
once.  Against it are held, each through ``harness/check.py`` under the
cell's own limits: the program as it is (``sound``); the program with one
fault planted in its path (``planted``: the decay dropped, ``A = 0``; the
memory unit's gate dropped; ``lambda = 0``; the cross layer reading K and V
of its own normed input through the full layer's weights; the memory taken
after the gate), a step of its own each, which has to come out NOT CORRECT by
at least one limit; with ``--control`` the reference one precision down.  One
JSON line each (``PERF.md`` keeps the readings).  The reference can leave the
same five terms out on its side (``cfg["drop"]``): tier-1's tests use that,
where a program's compile a case is too dear."""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from contextlib import contextmanager

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)
CELL = "phi4_mini_flash.causal_seq8192.fused"
FAULTS = ("decay", "memory_gate", "lambda", "cross_kv", "memory_after_gate")


@contextmanager
def planted(fault):
    """The program's decoder (``model_zoo/language/llama.py`` and its ops)
    with one term left out, for what is traced inside the block.
    ``"decay"``: ``F.ssm_rate`` gives ``A = 0``, so no state decays, forward
    and backward.  ``"memory_gate"``: the memory unit is ``M W2``, its
    ``silu(u W1)`` left out.  ``"lambda"``: the pairs' second maps are taken
    at zero, ``o_p = A1 V``.  ``"cross_kv"``: the cross layer takes K and V
    of its own normed input through the projections of the layer that hands
    its pair on.  ``"memory_after_gate"``: the state-space layer hands on ``y
    * silu(z)``."""
    import jax.numpy as jnp

    from mxnet_tpu.gluon.model_zoo.language import llama
    from mxnet_tpu.ops.registry import get_op

    undo = []

    def patch(owner, name, value):
        undo.append((owner, name, getattr(owner, name)))
        setattr(owner, name, value)

    if fault == "decay":
        patch(get_op("ssm_rate"), "fn",
              lambda a_log: jnp.zeros(a_log.shape, jnp.float32))
    elif fault == "memory_gate":
        patch(llama.LlamaGatedMemory, "hybrid_forward",
              lambda self, F, x, memory: self.out_proj(memory))
    elif fault == "lambda":
        combine = get_op("diff_attn_combine")
        real = combine.fn

        def first_maps_alone(o, *rest, **kw):
            seen = jnp.arange(o.shape[1]) < o.shape[1] // 2
            return real(o * seen[None, :, None, None].astype(o.dtype), *rest,
                        **kw)

        patch(combine, "fn", first_maps_alone)
    elif fault == "cross_kv":
        attend, cross = (llama.LlamaAttention.hybrid_forward,
                         llama.LlamaCrossAttention.hybrid_forward)
        source = []

        def remember(self, F, x, *rest):
            if self._hands_on:
                source.append(self)
            return attend(self, F, x, *rest)

        def own_pair(self, F, x, k, v):
            cfg = self._cfg
            k = llama._heads_first(source[-1].k_proj(x), cfg.num_kv_heads,
                                   True)
            v = llama._heads_first(source[-1].v_proj(x),
                                   cfg.num_kv_heads // 2, False)
            return cross(self, F, x, k, v)

        patch(llama.LlamaAttention, "hybrid_forward", remember)
        patch(llama.LlamaCrossAttention, "hybrid_forward", own_pair)
    elif fault == "memory_after_gate":
        walk = llama.LlamaStateSpace.hybrid_forward

        def gated_memory(self, F, x, *rest, **params):
            out = walk(self, F, x, *rest, **params)
            if not self._hands_on:
                return out
            inner = self._cfg.ssm_inner_size
            gate = F.slice_axis(self.in_proj(x), axis=2, begin=inner,
                                end=2 * inner)
            return out[0], F.swiglu(gate, out[1])

        patch(llama.LlamaStateSpace, "hybrid_forward", gated_memory)
    elif fault is not None:
        raise ValueError(f"no fault {fault!r}: {FAULTS}")
    try:
        yield
    finally:
        for owner, name, value in reversed(undo):
            setattr(owner, name, value)


def first_step(cell, seed, pool, fault=None):
    """What ``check.compare`` takes of the program's first ``check_steps``
    steps from ``seed``, with ``fault`` planted (None: the program as it
    is), through the cell's own driver and feed."""
    from chipbench.harness import loop

    spec, cfg = cell.spec, cell.cfg
    with planted(fault):
        runner = cell.driver.Runner(spec, cfg, cell.build,
                                    cell.reference.init_params(cfg, seed))
        feed = loop.open_feed(pool)
        try:
            got = loop.first_steps(runner, feed, spec["check_steps"])
        finally:
            feed.close()
    del runner
    gc.collect()
    return got


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--control", action="store_true")
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
        # the program first, each a step of its own, then the reference:
        # its optimizer's step needs the chip to itself
        legs = {name or "sound": first_step(cell, seed, pool, name)
                for name in [None] + faults}

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
