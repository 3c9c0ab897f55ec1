"""Operations and bytes the ``trinity_mini`` configuration requires, from
shapes alone: only the pairs a layer's mask lets through, only the (token,
expert) pairs routed to experts held here.  A multiply-add is two
operations; a training step is the forward pass and twice as much again for
the backward pass; nothing recomputed is counted, and no whole tile."""
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


def sparse_layers(cfg):
    """Layers whose FFN is the expert layer."""
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def visible_pairs(cfg, length, kind):
    return window_pairs(length, cfg["sliding_window"]) \
        if kind == "sliding_attention" else causal_pairs(length)


def attention_fwd_flops(cfg, length, kind):
    """QK^T and PV over the pairs a layer of ``kind`` shows, every query
    head, one sample."""
    return (4 * cfg["num_attention_heads"] * visible_pairs(cfg, length, kind)
            * cfg["head_dim"])


def attention_fwd_bytes(cfg, length, itemsize):
    """One sample: q and o (query heads) and k and v (as many heads: the
    program repeats the key-value heads before the kernel) read or written
    once, and the float32 log-sum-exp a query row."""
    return cfg["num_attention_heads"] * length * (
        4 * cfg["head_dim"] * itemsize + 4)


def routed_pair_fwd_flops(cfg):
    """The three products of one (token, expert) pair: hidden x width,
    twice, and width x hidden."""
    return 6 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_expert_fwd_flops(cfg):
    """The shared expert's three products for one token."""
    return cfg["num_shared_experts"] * routed_pair_fwd_flops(cfg)


def pairs_per_token(cfg):
    """Routed pairs a token a sparse layer that land on this share under
    the assumed routers (``config.json``, ``assumed.router``): a token's
    ``num_experts_per_tok`` choices are one expert on each of the shares
    the selection bias favours."""
    shares = cfg["assumed"]["expert_bias"]["shares"]
    here = cfg["experts_first"] // cfg["num_experts"]
    return cfg["num_experts_per_tok"] / len(shares) if here in shares \
        else 0.0


def forward_flops_per_sample(cfg, length):
    """One sample's forward pass: ``length`` rows through the layers (the
    five projections, the visible attention pairs, then the dense SwiGLU,
    or the router, the shared expert and the routed pairs held here) and
    through the output head."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    proj = 2 * length * h * hd * (3 * cfg["num_attention_heads"]
                                  + 2 * cfg["num_key_value_heads"])
    attention = sum(attention_fwd_flops(cfg, length, kind)
                    for kind in layer_types(cfg))
    dense = 6 * length * h * cfg["intermediate_size"]
    sparse = length * (2 * h * cfg["router_width"]
                       + shared_expert_fwd_flops(cfg)
                       + pairs_per_token(cfg) * routed_pair_fwd_flops(cfg))
    head = 2 * length * h * cfg["vocab_size"]
    return (cfg["num_hidden_layers"] * proj + attention
            + cfg["num_dense_layers"] * dense + sparse_layers(cfg) * sparse
            + head)


def train_flops_per_sample(cfg, length):
    return 3 * forward_flops_per_sample(cfg, length)
