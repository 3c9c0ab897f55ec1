"""A copy of the benchmark with a toy-width configuration and two cells of
it beside the real ones, added the way a later PR adds them: new files and
new entries only."""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BERT = {"vocab_size": 64, "hidden_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 2, "intermediate_size": 64,
        "max_position_embeddings": 32, "type_vocab_size": 2,
        "layer_norm_eps": 1e-12, "hidden_dropout_prob": 0.0}
# The toy cells state float32, so their control is bfloat16: on the CPU a
# bf16 step differs from the chip's (reductions accumulate differently), and
# the float32 pair shows the same machinery.  Limits as PERF.md sets the
# real ones: above the sound runs' largest over seeds 1..12 at these
# widths, below the control's smallest
# (test_check_separates_the_lower_precision reads both again).
LIMITS = {"loss_gap": 7e-7, "first_gradient_gap": 3e-5,
          "first_gradient_error": 7e-5, "change_gap": 2.4e-4}
FLOAT32 = {"amp_dtype": None, "precision": "float32"}
LIKE = "bert_base.seq512.fused"
# the second cell is another traffic mix of the same configuration: a data
# file and an entry, nothing else
CELLS = {"toy_bert.fused": {"batch": 4, "seq": 16, "reference_block_rows": 2},
         "toy_bert.short": {"batch": 2, "seq": 8, "reference_block_rows": 2}}


def make_root(tmp):
    """Copy ``BENCHMARK.json`` and ``chipbench/`` to ``tmp`` and add the toy
    configurations and cells as new files and entries."""
    root = os.path.join(str(tmp), "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    src = os.path.join(root, "chipbench", "configs", "bert_base")
    dst = os.path.join(root, "chipbench", "configs", "toy_bert")
    shutil.copytree(src, dst)
    with open(os.path.join(dst, "config.json")) as f:
        cfg = json.load(f)
    cfg.update(BERT, name="toy_bert")
    with open(os.path.join(dst, "config.json"), "w") as f:
        json.dump(cfg, f)
    bench["configs"].append({"name": "toy_bert", "source": "toy", "file":
                             "chipbench/configs/toy_bert/config.json",
                             "reduced": [], "why": "toy width"})
    with open(os.path.join(root, "chipbench", "workloads",
                           LIKE + ".json")) as f:
        like = json.load(f)
    for name, sizes in CELLS.items():
        spec = dict(like, **sizes, **FLOAT32, config="toy_bert", limits=LIMITS)
        with open(os.path.join(root, "chipbench", "workloads",
                               name + ".json"), "w") as f:
            json.dump(spec, f)
        bench["workloads"].append({
            "name": name, "config": "toy_bert",
            "traffic": name.split(".", 1)[1], "chips": 1, "why": "toy width"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if LIKE in metric.get("workloads", []):
            metric["workloads"].append("toy_bert.fused")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
