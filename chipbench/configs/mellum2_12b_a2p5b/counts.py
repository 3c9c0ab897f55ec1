"""Operations and bytes the ``mellum2_12b_a2p5b`` configuration requires,
from shapes and the cell's document lengths alone: only the pairs a layer's
mask shows inside a document, only the (token, expert) pairs routed to
experts held here.  A multiply-add is two operations; a training step is
the forward pass and twice as much again for the backward pass; nothing
recomputed is counted, and no whole tile.  The order of a row's documents
changes none of these counts."""
from __future__ import annotations


def causal_pairs(length):
    """(query, key) pairs of the causal mask over ``length`` rows."""
    return length * (length + 1) // 2


def window_pairs(length, window):
    """(query, key) pairs of the causal mask under a window: query ``i``
    sees the keys ``i - window < j <= i``."""
    w = min(window, length)
    return w * (w + 1) // 2 + (length - w) * w


def layer_types(cfg):
    """The kinds of the layers that are here."""
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def visible_pairs(cfg, documents, kind):
    """Pairs a layer of ``kind`` shows in a row of ``documents`` (their
    lengths): each document's own causal pairs, under the window on a
    sliding layer."""
    if kind == "sliding_attention":
        return sum(window_pairs(n, cfg["sliding_window"]) for n in documents)
    return sum(causal_pairs(n) for n in documents)


def attention_fwd_flops(cfg, documents, kind):
    """QK^T and PV over the pairs a layer of ``kind`` shows, every query
    head, one sample."""
    return (4 * cfg["num_attention_heads"]
            * visible_pairs(cfg, documents, kind) * cfg["head_dim"])


def attention_fwd_bytes(cfg, length, itemsize):
    """One sample: q and o (query heads) and k and v (as many heads: the
    program repeats the key-value heads before the kernel) read or written
    once, the float32 log-sum-exp a query row, and the int32 segment id a
    key."""
    return cfg["num_attention_heads"] * length * (
        4 * cfg["head_dim"] * itemsize + 4) + 4 * length


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


def pairs_per_token(cfg):
    """Routed pairs a token a layer that land on this share under the
    assumed routers (``config.json``, ``assumed.router``): a token's
    ``num_experts_per_tok`` choices are one expert on each of the shares."""
    shares = cfg["router_width"] // cfg["num_experts"]
    return cfg["num_experts_per_tok"] / shares


def forward_flops_per_sample(cfg, documents):
    """One sample's forward pass: the documents' rows through the layers
    (the four projections, the visible attention pairs, the router and the
    routed pairs held here) and through the output head."""
    length = sum(documents)
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    proj = 2 * length * h * hd * 2 * (cfg["num_attention_heads"]
                                      + cfg["num_key_value_heads"])
    attention = sum(attention_fwd_flops(cfg, documents, kind)
                    for kind in layer_types(cfg))
    sparse = length * (2 * h * cfg["router_width"]
                       + pairs_per_token(cfg) * routed_pair_fwd_flops(cfg))
    head = 2 * length * h * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * (proj + sparse) + attention + head


def train_flops_per_sample(cfg, documents):
    return 3 * forward_flops_per_sample(cfg, documents)
