"""A copy of the benchmark with the ``ling3_flash_vl`` configuration at a toy
width and a cell of it beside the real ones, added the way a later PR adds
them: new files and new entries only."""
from __future__ import annotations

import json
import os
import shutil

import toy

# the same shape of net, small: seven layers (the first dense; KDA x5, MLA
# at index 5, KDA), 2 of 4 heads held (heads of 16; MLA q and k 16 + 8, v
# 16, latent 32), top-4 of 32 routed experts in 4 groups of which 2 stay, 4
# held (the first of 8 shares: half a pair a token in the mean, as the real
# cell's independent columns give an eighth), a shared expert, a vocabulary
# of 96; test_ling3.py cuts the delta rule's chunks to 16 rows
LING3 = {"vocab_size": 96, "hidden_size": 64, "num_attention_heads": 2,
         "heads_first": 0, "head_dim": 16, "intermediate_size": 96,
         "moe_intermediate_size": 32,
         "moe_shared_expert_intermediate_size": 32, "num_experts": 4,
         "router_width": 32, "num_experts_per_tok": 4, "n_group": 4,
         "topk_group": 2, "experts_first": 0, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "qk_head_dim": 24,
         "v_head_dim": 16}
PUBLISHED_HEADS = 4
# The toy cell states float32, so its control is bfloat16 (on the CPU a bf16
# step differs from the chip's).  Limits as PERF.md sets the real ones:
# between the sound runs' largest and the control's smallest over seeds
# 1..6 at these widths (test_ling3.py reads both again)
LIMITS = {"loss_gap": 3e-6, "first_gradient_gap": 1e-3,
          "first_gradient_error": 2e-3, "change_gap": 1e-3}
LIKE = "ling3_flash_vl.causal_seq8192.fused"
CELL = "toy_ling3.causal_seq64.fused"


def make_root(tmp):
    """``toy.make_root``'s copy of the benchmark with the toy decoder and its
    cell added as new files and entries."""
    root = toy.make_root(tmp)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    src = os.path.join(root, "chipbench", "configs", "ling3_flash_vl")
    dst = os.path.join(root, "chipbench", "configs", "toy_ling3")
    shutil.copytree(src, dst)
    with open(os.path.join(dst, "config.json")) as f:
        cfg = json.load(f)
    cfg.update(LING3, name="toy_ling3")
    cfg["published"] = dict(cfg["published"],
                            num_attention_heads=PUBLISHED_HEADS)
    with open(os.path.join(dst, "config.json"), "w") as f:
        json.dump(cfg, f)
    bench["configs"].append({"name": "toy_ling3", "source": "toy", "file":
                             "chipbench/configs/toy_ling3/config.json",
                             "reduced": [], "why": "toy width"})
    with open(os.path.join(root, "chipbench", "workloads",
                           LIKE + ".json")) as f:
        like = json.load(f)
    spec = dict(like, batch=2, seq=64, amp_dtype=None, precision="float32",
                check_steps=2, config="toy_ling3", limits=LIMITS)
    with open(os.path.join(root, "chipbench", "workloads", CELL + ".json"),
              "w") as f:
        json.dump(spec, f)
    bench["workloads"].append({
        "name": CELL, "config": "toy_ling3",
        "traffic": CELL.split(".", 1)[1], "chips": 1, "why": "toy width"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if LIKE in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
