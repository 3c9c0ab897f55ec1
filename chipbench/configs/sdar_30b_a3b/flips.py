"""How much of the routing a rounding decides:

    python3 chipbench/configs/sdar_30b_a3b/flips.py --seeds 1,2,3

For each seed, the configuration's reference chooses every token's experts
twice on the cell's first batch, once in float32 and once with every
product's operands rounded to bfloat16, and the share of tokens whose set of
experts differs is printed layer by layer: near-ties in the router flip with
rounding, so a statistic of ``correct`` that moved with them would move
with the seed (``PERF.md`` keeps the readings)."""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)
CELL = "sdar_30b_a3b.bd4_seq4096.fused"


def flipped_share(reference, cfg, params, ids):
    """Per layer, the share of the batch's tokens whose chosen experts, as
    a set, differ between float32 and bfloat16 operands."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def share(params, ids):
        sets = [jnp.sort(reference.routes(cfg, precision, params, ids), -1)
                for precision in ("float32", "bfloat16")]
        differ = jnp.any(sets[0] != sets[1], axis=-1)   # samples, layers, 2L
        return jnp.mean(differ, axis=(0, 2))

    return [float(x) for x in share(params, ids)]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    import mxnet_tpu  # noqa: F401  places the compile cache

    from chipbench.harness import loop
    from chipbench.harness.cell import Cell, find_chips

    cell = Cell(args.workload)
    if find_chips(cell) is None:
        return 3
    for seed in (int(s) for s in args.seeds.split(",")):
        batch = loop.make_pool(cell.build, cell.cfg, cell.spec, seed)[0]
        shares = flipped_share(cell.reference, cell.cfg,
                               cell.reference.init_params(cell.cfg, seed),
                               batch[0])
        print(json.dumps({"seed": seed, "tokens_with_another_expert_set":
                          shares}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
