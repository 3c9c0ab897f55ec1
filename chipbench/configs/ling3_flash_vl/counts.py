"""Operations and bytes the ``ling3_flash_vl`` configuration requires, from
shapes alone: only the heads and the (token, expert) pairs that are here,
only the pairs the causal mask lets through.  A multiply-add is two
operations; a training step is the forward pass and twice as much again for
the backward pass; nothing recomputed is counted, and no whole tile."""
from __future__ import annotations

KDA_CHUNK = 64   # rows of a chunk as the program computes the delta rule


def layer_kinds(cfg):
    """``"mla"`` or ``"kda"`` of each layer that is here."""
    return ["mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda"
            for i in range(cfg["num_hidden_layers"])]


def sparse_layers(cfg):
    """Layers whose FFN is the expert layer."""
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def causal_pairs(length):
    """(query, key) pairs of the causal mask over ``length`` rows."""
    return length * (length + 1) // 2


def kda_fwd_flops(cfg, length):
    """One sample's forward pass of the chunked delta rule (``ops/kda.py``),
    every head here, chunks of ``C`` rows of heads of ``K`` (k) and ``V``
    (v).  A chunk a head: the decayed products ``P(k)`` and ``P(q)`` over
    the pairs ``j <= i`` (``2 K`` a pair each), the triangular solve of
    ``K + V`` right-hand columns (``C (C - 1) / 2`` multiply-adds a
    column), ``W S`` and ``(q e^b) S`` (``2 C K V`` each), ``lower(P(q))
    V~`` over the pairs, and ``(k e^{b_last - b})^T V~`` (``2 C K V``).  The
    exponentials and the running sums are not counted."""
    c, k, v = KDA_CHUNK, cfg["head_dim"], cfg["head_dim"]
    pairs = c * (c + 1) // 2
    chunk = (2 * 2 * k * pairs + (k + v) * c * (c - 1)
             + 3 * 2 * c * k * v + 2 * v * pairs)
    return cfg["num_attention_heads"] * (length // c) * chunk


def kda_fwd_bytes(cfg, length, itemsize):
    """One sample: q, k, v read and o written once in the step's dtype, the
    float32 log-decay (as wide as k) and beta read once, and every chunk's
    state (``K x V`` float32) written once for the backward."""
    k = v = cfg["head_dim"]
    rows = cfg["num_attention_heads"] * length
    return (rows * ((2 * k + 2 * v) * itemsize + 4 * k + itemsize)
            + cfg["num_attention_heads"] * (length // KDA_CHUNK)
            * k * v * 4)


def mla_attention_fwd_flops(cfg, length):
    """QK^T over 192 and PV over 128 for the causal pairs, every head here,
    one sample."""
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (2 * cfg["num_attention_heads"] * causal_pairs(length)
            * (width + cfg["v_head_dim"]))


def mla_attention_fwd_bytes(cfg, length, itemsize):
    """One sample: q and k (192 a head: the program hands the kernel the
    shared 64 repeated a head) and v and o (128) read or written once, and
    the float32 log-sum-exp a query row."""
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return cfg["num_attention_heads"] * length * (
        2 * (width + cfg["v_head_dim"]) * itemsize + 4)


def routed_pair_fwd_flops(cfg):
    """The three products of one (token, expert) pair."""
    return 6 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_weight_bytes(cfg, itemsize):
    """The held experts' three matrices of one sparse layer."""
    return (3 * cfg["num_experts"] * cfg["hidden_size"]
            * cfg["moe_intermediate_size"] * itemsize)


def routed_pair_bytes(cfg, itemsize):
    """One pair's token read and result written once."""
    return 2 * cfg["hidden_size"] * itemsize


def pairs_per_token(cfg):
    """Routed pairs a token a sparse layer that land on this share in the
    mean under the assumed routers (``config.json``, ``assumed.router``):
    independent columns and no bias make every output as likely as any
    other, so the share sees its even part of a token's
    ``num_experts_per_tok`` choices."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_width"])


def forward_flops_per_sample(cfg, length):
    """One sample's forward pass: ``length`` rows through the layers (a
    mixer's projections and its core, then the dense SwiGLU, or the router,
    the shared expert and the routed pairs held here) and the output
    head.  The convolutions, norms and gates' element-wise work is not
    counted."""
    h, heads, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["head_dim"])
    nope, turned, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
    latent = cfg["kv_lora_rank"]
    kda = 2 * length * h * heads * (6 * hd + 1) + kda_fwd_flops(cfg, length)
    mla = 2 * length * (h * heads * (nope + turned) + h * (latent + turned)
                        + latent * heads * (nope + dv) + h * heads
                        + heads * dv * h) \
        + mla_attention_fwd_flops(cfg, length)
    kinds = layer_kinds(cfg)
    dense = 6 * length * h * cfg["intermediate_size"]
    sparse = length * (2 * h * cfg["router_width"]
                       + 6 * h * cfg["moe_shared_expert_intermediate_size"]
                       + pairs_per_token(cfg) * routed_pair_fwd_flops(cfg))
    head = 2 * length * h * cfg["vocab_size"]
    return (kinds.count("kda") * kda + kinds.count("mla") * mla
            + cfg["first_k_dense_replace"] * dense
            + sparse_layers(cfg) * sparse + head)


def train_flops_per_sample(cfg, length):
    return 3 * forward_flops_per_sample(cfg, length)
