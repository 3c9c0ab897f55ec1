"""A copy of the benchmark with the ``phi4_mini_flash`` configuration at a toy
width and a cell of it beside the real ones, added the way a later PR adds
them: new files and new entries only."""
from __future__ import annotations

import json
import os
import shutil

import toy

# the same shape of net, small: the five published layers 15-19 (window,
# state-space, full, gated memory, cross), 4 query heads over 2 key-value
# heads of 16 (two query pairs on one key-value pair), a window of 8, 128
# channels of 4 states through a rank of 4, a vocabulary of 96;
# test_phi4.py cuts the scan's chunks to 16 rows
PHI4 = {"vocab_size": 96, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 96,
        "sliding_window": 8}
STATE_SPACE = {"d_state": 4, "d_conv": 4, "expand": 2, "dt_rank": 4}
# The toy cell states float32, so its control is bfloat16 (on the CPU a bf16
# step differs from the chip's).  Limits as PERF.md sets the real ones:
# between the sound runs' largest and the control's smallest over seeds
# 1..6 at these widths (test_phi4.py reads both again)
LIMITS = {"loss_gap": 2e-6, "first_gradient_gap": 1.5e-4,
          "first_gradient_error": 2e-4, "change_gap": 1.3e-3}
LIKE = "phi4_mini_flash.causal_seq8192.fused"
CELL = "toy_phi4.causal_seq32.fused"


def make_root(tmp):
    """``toy.make_root``'s copy of the benchmark with the toy decoder and its
    cell added as new files and entries."""
    root = toy.make_root(tmp)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    src = os.path.join(root, "chipbench", "configs", "phi4_mini_flash")
    dst = os.path.join(root, "chipbench", "configs", "toy_phi4")
    shutil.copytree(src, dst)
    with open(os.path.join(dst, "config.json")) as f:
        cfg = json.load(f)
    cfg.update(PHI4, name="toy_phi4")
    cfg["assumed"] = dict(cfg["assumed"], state_space=STATE_SPACE)
    with open(os.path.join(dst, "config.json"), "w") as f:
        json.dump(cfg, f)
    bench["configs"].append({"name": "toy_phi4", "source": "toy", "file":
                             "chipbench/configs/toy_phi4/config.json",
                             "reduced": [], "why": "toy width"})
    with open(os.path.join(root, "chipbench", "workloads",
                           LIKE + ".json")) as f:
        like = json.load(f)
    spec = dict(like, batch=2, seq=32, amp_dtype=None, precision="float32",
                check_steps=2, config="toy_phi4", limits=LIMITS)
    with open(os.path.join(root, "chipbench", "workloads", CELL + ".json"),
              "w") as f:
        json.dump(spec, f)
    bench["workloads"].append({
        "name": CELL, "config": "toy_phi4",
        "traffic": CELL.split(".", 1)[1], "chips": 1, "why": "toy width"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if LIKE in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
