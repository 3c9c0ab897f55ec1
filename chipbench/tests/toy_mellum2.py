"""A copy of the benchmark with the ``mellum2_12b_a2p5b`` configuration at a
toy width and a cell of it beside the real ones, added the way a later PR
adds them: new files and new entries only."""
from __future__ import annotations

import json
import os
import shutil

import toy

# the same shape of layer, small: four layers (window, window, window, full
# under a window of 8), GQA 4 over 2 heads of 16, top-4 of 8 softmax-routed
# experts with 2 held (the first of 4 shares), a vocabulary of 96; YaRN at
# base 100 over an original context of 64, so that the ramp (dimensions
# 2..6) lies inside the head's 8 rotated pairs
MELLUM = {"vocab_size": 96, "hidden_size": 64, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
          "moe_intermediate_size": 32, "num_experts": 2, "router_width": 8,
          "num_experts_per_tok": 4, "experts_first": 0, "sliding_window": 8}
ROPE = {"full_attention": {
    "rope_type": "yarn", "rope_theta": 100, "factor": 4,
    "original_max_position_embeddings": 64, "beta_fast": 2, "beta_slow": 0.5,
    "attention_factor": 1.1386294361119891},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000}}
DOCUMENTS = [13, 9, 5, 3, 2]
# The toy cell states float32, so its control is bfloat16 (on the CPU a bf16
# step differs from the chip's).  Limits as PERF.md sets the real ones:
# above the sound runs' largest over seeds 1..6 at these widths (2.1e-7,
# 1.2e-7, 5.1e-7, 2.1e-5), below the control's smallest (1.3e-5, 1.7e-3,
# 1.2e-2, 2.5e-3) (test_mellum2.py reads both again)
LIMITS = {"loss_gap": 2e-6, "first_gradient_gap": 1.5e-5,
          "first_gradient_error": 8e-5, "change_gap": 2.5e-4}
LIKE = "mellum2_12b_a2p5b.packed_seq16384.fused"
CELL = "toy_mellum2.packed_seq32.fused"


def make_root(tmp):
    """``toy.make_root``'s copy of the benchmark with the toy decoder and its
    cell added as new files and entries."""
    root = toy.make_root(tmp)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    src = os.path.join(root, "chipbench", "configs", "mellum2_12b_a2p5b")
    dst = os.path.join(root, "chipbench", "configs", "toy_mellum2")
    shutil.copytree(src, dst)
    with open(os.path.join(dst, "config.json")) as f:
        cfg = json.load(f)
    cfg.update(MELLUM, name="toy_mellum2", rope_parameters=ROPE)
    with open(os.path.join(dst, "config.json"), "w") as f:
        json.dump(cfg, f)
    bench["configs"].append({"name": "toy_mellum2", "source": "toy", "file":
                             "chipbench/configs/toy_mellum2/config.json",
                             "reduced": [], "why": "toy width"})
    with open(os.path.join(root, "chipbench", "workloads",
                           LIKE + ".json")) as f:
        like = json.load(f)
    spec = dict(like, batch=2, seq=32, documents=DOCUMENTS, amp_dtype=None,
                precision="float32", check_steps=2, reference_block_rows=8,
                config="toy_mellum2", limits=LIMITS)
    with open(os.path.join(root, "chipbench", "workloads", CELL + ".json"),
              "w") as f:
        json.dump(spec, f)
    bench["workloads"].append({
        "name": CELL, "config": "toy_mellum2",
        "traffic": CELL.split(".", 1)[1], "chips": 1, "why": "toy width"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if LIKE in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
