"""A copy of the benchmark with the ``trinity_mini`` configuration at a toy
width and a cell of it beside the real ones, added the way a later PR adds
them: new files and new entries only."""
from __future__ import annotations

import json
import os
import shutil

import toy

# the same shape of layer, small: five layers (the first dense; window,
# window, window, full, window under a window of 8), GQA 4 over 2 heads of
# 16, top-2 of 8 routed experts with 2 held (the first of 4 shares, of which
# the bias favours 2), a shared expert, a vocabulary of 96
TRINITY = {"vocab_size": 96, "hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
           "moe_intermediate_size": 32, "num_experts": 2, "router_width": 8,
           "num_experts_per_tok": 2, "experts_first": 0, "sliding_window": 8}
BIAS = {"value": 1.0, "shares": [0, 1]}
# The toy cell states float32, so its control is bfloat16 (on the CPU a bf16
# step differs from the chip's).  Limits as PERF.md sets the real ones:
# above the sound runs' largest over seeds 1..6 at these widths (1.0e-7,
# 9.2e-8, 5.8e-7, 1.1e-4), below the control's smallest (6.4e-5, 3.1e-3,
# 1.9e-2, 2.7e-3) (test_trinity.py reads both again)
LIMITS = {"loss_gap": 3e-6, "first_gradient_gap": 4e-5,
          "first_gradient_error": 1.5e-4, "change_gap": 4e-4}
LIKE = "trinity_mini.causal_seq8192.fused"
CELL = "toy_trinity.causal_seq32.fused"


def make_root(tmp):
    """``toy.make_root``'s copy of the benchmark with the toy decoder and its
    cell added as new files and entries."""
    root = toy.make_root(tmp)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    src = os.path.join(root, "chipbench", "configs", "trinity_mini")
    dst = os.path.join(root, "chipbench", "configs", "toy_trinity")
    shutil.copytree(src, dst)
    with open(os.path.join(dst, "config.json")) as f:
        cfg = json.load(f)
    cfg.update(TRINITY, name="toy_trinity")
    cfg["assumed"] = dict(cfg["assumed"], expert_bias=BIAS)
    with open(os.path.join(dst, "config.json"), "w") as f:
        json.dump(cfg, f)
    bench["configs"].append({"name": "toy_trinity", "source": "toy", "file":
                             "chipbench/configs/toy_trinity/config.json",
                             "reduced": [], "why": "toy width"})
    with open(os.path.join(root, "chipbench", "workloads",
                           LIKE + ".json")) as f:
        like = json.load(f)
    spec = dict(like, batch=2, seq=32, amp_dtype=None, precision="float32",
                check_steps=2, config="toy_trinity", limits=LIMITS)
    with open(os.path.join(root, "chipbench", "workloads", CELL + ".json"),
              "w") as f:
        json.dump(spec, f)
    bench["workloads"].append({
        "name": CELL, "config": "toy_trinity",
        "traffic": CELL.split(".", 1)[1], "chips": 1, "why": "toy width"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if LIKE in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
