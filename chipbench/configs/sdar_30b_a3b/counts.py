"""Operations and bytes the ``sdar_30b_a3b`` configuration requires, from
shapes alone: only the pairs the block-diffusion mask lets through, only the
(token, expert) pairs routed to experts held here.  A multiply-add is two
operations; a training step is the forward pass and twice as much again for
the backward pass; nothing recomputed is counted, and no whole tile."""
from __future__ import annotations


def visible_pairs(length, block):
    """(query, key) pairs the block-diffusion mask shows in a row ``[noised
    ; clean]`` of ``2 * length`` with blocks of ``block``: with n blocks,
    each noised query sees its own block (block keys) and the clean blocks
    before its own; each clean query its own and the earlier clean blocks."""
    n = length // block
    own = length * block                       # noised x noised, own block
    before = block * block * n * (n - 1) // 2  # noised x earlier clean blocks
    upto = block * block * n * (n + 1) // 2    # clean x clean blocks up to own
    return own + before + upto


def attention_fwd_flops(cfg, length, block):
    """QK^T and PV over the visible pairs, every query head, one sample."""
    return (4 * cfg["num_attention_heads"] * visible_pairs(length, block)
            * cfg["head_dim"])


def attention_fwd_bytes(cfg, length, itemsize):
    """One sample: q and o (query heads) and k and v (as many heads: the
    program repeats the key-value heads before the kernel) read or written
    once, and the float32 log-sum-exp a query row."""
    rows, heads = 2 * length, cfg["num_attention_heads"]
    return heads * rows * (4 * cfg["head_dim"] * itemsize + 4)


def routed_pair_fwd_flops(cfg):
    """The three products of one (token, expert) pair: hidden x width,
    twice, and width x hidden."""
    return 6 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_weight_bytes(cfg, itemsize):
    """The held experts' three matrices of one layer."""
    return (3 * cfg["num_experts"] * cfg["hidden_size"]
            * cfg["moe_intermediate_size"] * itemsize)


def routed_pair_bytes(cfg, itemsize):
    """One pair's token read and result written once."""
    return 2 * cfg["hidden_size"] * itemsize


def expected_pairs_per_token(cfg):
    """Routed pairs a token that land on the experts held here when the
    router spreads its choices evenly (its random initial state)."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_width"]


def forward_flops_per_sample(cfg, length, block):
    """One sample's forward pass: ``2 * length`` rows through the layers
    (projections, visible attention pairs, router, the expected routed pairs
    held here), then ``length`` rows through the output head."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    rows = 2 * length
    proj = 2 * rows * h * hd * 2 * (cfg["num_attention_heads"]
                                    + cfg["num_key_value_heads"])
    router = 2 * rows * h * cfg["router_width"]
    experts = rows * expected_pairs_per_token(cfg) * routed_pair_fwd_flops(cfg)
    layer = proj + attention_fwd_flops(cfg, length, block) + router + experts
    head = 2 * length * h * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * layer + head


def train_flops_per_sample(cfg, length, block):
    return 3 * forward_flops_per_sample(cfg, length, block)
