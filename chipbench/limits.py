"""Reads what the limits of ``correct`` are set from, on the chip at the
cell's own size, in one process:

    python3 chipbench/limits.py --workload <cell> --seeds 101,102,...

For every seed: the program's first steps against the plain reference (the
sound reading), and the reference computed in the nearest precision below
the one the cell states against the plain reference (the control, which has
to come out as not correct).  Prints one JSON line a seed and a summary.
``PERF.md`` records the readings and the limit set from them.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def read_seed(cell, seed, control=True, per_leaf=False):
    """``{"sound": stats, "control": stats}`` for one seed."""
    from chipbench.harness import check, loop
    from chipbench.harness.precision import BELOW

    spec, cfg = cell.spec, cell.cfg
    runner = cell.driver.Runner(spec, cfg, cell.build,
                                cell.reference.init_params(cfg, seed))
    pool = loop.make_pool(cell.build, cfg, spec, seed)
    feed = loop.open_feed(pool)
    try:
        got = loop.first_steps(runner, feed, spec["check_steps"])
    finally:
        feed.close()
    del runner
    gc.collect()

    def follow(precision):
        return check.follow(cell.reference, cfg, precision,
                            cell.reference.init_params(cfg, seed),
                            pool[:spec["check_steps"]], spec)

    ref = follow("float32")
    out = {"seed": seed, "losses": got["losses"], "ref_losses": ref["losses"],
           "sound": check.compare(got, ref)}
    if per_leaf:
        out["sound_leaves"] = check.leaf_numbers(got, ref)
    if control:
        low = follow(BELOW[spec["precision"]])
        out["control"] = check.compare(low, ref)
        if per_leaf:
            out["control_leaves"] = check.leaf_numbers(low, ref)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="read the control on the first N seeds only")
    ap.add_argument("--per-leaf", default=None,
                    help="write every leaf's numbers to this JSON-lines file")
    args = ap.parse_args(argv)

    import mxnet_tpu  # noqa: F401
    import jax

    from chipbench.harness.cell import Cell, find_chips

    cell = Cell(args.workload)
    if find_chips(cell) is None:
        return 3
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = [int(s) for s in args.seeds.split(",")]
    n_control = len(seeds) if args.control_seeds is None else args.control_seeds
    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        row = read_seed(cell, seed, control=i < n_control,
                        per_leaf=bool(args.per_leaf))
        row["seconds"] = round(time.perf_counter() - t0, 1)
        if args.per_leaf:
            with open(args.per_leaf, "a") as f:
                f.write(json.dumps({"seed": seed, "sound": row.pop(
                    "sound_leaves"), "control": row.pop("control_leaves",
                                                        None)}) + "\n")
        rows.append(row)
        print(json.dumps(row), flush=True)
    for name in rows[0]["sound"]:
        sound = [r["sound"][name][0] for r in rows]
        control = [r["control"][name][0] for r in rows if "control" in r]
        print(f"chipbench: {cell.name} {name}: sound largest {max(sound):.6g} "
              f"(median {sorted(sound)[len(sound) // 2]:.6g}), control "
              f"smallest {min(control) if control else float('nan'):.6g}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
