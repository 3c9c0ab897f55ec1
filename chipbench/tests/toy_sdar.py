"""A copy of the benchmark with the ``sdar_30b_a3b`` configuration at a toy
width and a cell of it beside the real ones, added the way a later PR adds
them: new files and new entries only."""
from __future__ import annotations

import json
import os
import shutil

import toy

# the same shape of layer, small: top-2 of 8 experts with 4 held (the second
# share), GQA 4 over 2 heads of 16, two layers, a vocabulary of 96 whose last
# id is the mask token
SDAR = {"vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "moe_intermediate_size": 32, "num_experts": 4, "router_width": 8,
        "num_experts_per_tok": 2, "experts_first": 4}
# The toy cell states float32, so its control is bfloat16 (on the CPU a bf16
# step differs from the chip's).  Limits as PERF.md sets the real ones:
# above the sound runs' largest over seeds 1..8 at these widths (1.1e-7,
# 1.7e-7, 5.1e-7, 6.6e-5), below the control's smallest (1.7e-5, 1.3e-3,
# 6.5e-3, 9.2e-4) (test_sdar.py reads both again)
LIMITS = {"loss_gap": 1.5e-6, "first_gradient_gap": 2e-5,
          "first_gradient_error": 6e-5, "change_gap": 2.5e-4}
LIKE = "sdar_30b_a3b.bd4_seq4096.fused"
CELL = "toy_sdar.bd4_seq32.fused"


def make_root(tmp):
    """``toy.make_root``'s copy of the benchmark with the toy decoder and its
    cell added as new files and entries."""
    root = toy.make_root(tmp)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    src = os.path.join(root, "chipbench", "configs", "sdar_30b_a3b")
    dst = os.path.join(root, "chipbench", "configs", "toy_sdar")
    shutil.copytree(src, dst)
    with open(os.path.join(dst, "config.json")) as f:
        cfg = json.load(f)
    cfg.update(SDAR, name="toy_sdar")
    cfg["assumed"] = dict(cfg["assumed"],
                          mask_token_id=SDAR["vocab_size"] - 1)
    with open(os.path.join(dst, "config.json"), "w") as f:
        json.dump(cfg, f)
    bench["configs"].append({"name": "toy_sdar", "source": "toy", "file":
                             "chipbench/configs/toy_sdar/config.json",
                             "reduced": [], "why": "toy width"})
    with open(os.path.join(root, "chipbench", "workloads",
                           LIKE + ".json")) as f:
        like = json.load(f)
    spec = dict(like, batch=2, seq=32, amp_dtype=None, precision="float32",
                check_steps=2, config="toy_sdar", limits=LIMITS)
    with open(os.path.join(root, "chipbench", "workloads", CELL + ".json"),
              "w") as f:
        json.dump(spec, f)
    bench["workloads"].append({
        "name": CELL, "config": "toy_sdar", "traffic": CELL.split(".", 1)[1],
        "chips": 1, "why": "toy width"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if LIKE in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
