"""Operations and bytes the ``phi4_mini_flash`` configuration requires, from
shapes alone: only the pairs a layer's mask lets through.  A multiply-add is
two operations; a training step is the forward pass and twice as much again
for the backward pass; nothing recomputed is counted, and no whole tile."""
from __future__ import annotations

SSM_CHUNK = 64   # rows of a chunk as the program walks the scan


def layer_kinds(cfg):
    """The kind of each layer that is here, as ``LlamaConfig.attention_types``
    names it, by the published index ``layers_first + i`` of ``n`` published
    layers in periods of ``mb_per_layer``: even indices are state-space
    blocks up to ``n / 2`` and gated memory units after it, odd indices
    window attention below ``n / 2``, full attention right after it and cross
    attention from there on (``config.json``, ``assumed.layer_kinds``)."""
    n, period = cfg["published"]["num_hidden_layers"], cfg["mb_per_layer"]
    half = n // 2
    kinds = []
    for i in range(cfg["layers_first"],
                   cfg["layers_first"] + cfg["num_hidden_layers"]):
        if i % period == 0:
            kinds.append("ssm" if i <= half else "gmu")
        elif i < half:
            kinds.append("window")
        else:
            kinds.append("full" if i == half + 1 else "cross")
    return kinds


def ssm_sizes(cfg):
    """``(channels, state, taps, rank)`` of a state-space block."""
    given = cfg["assumed"]["state_space"]
    return (given["expand"] * cfg["hidden_size"], given["d_state"],
            given["d_conv"], given["dt_rank"])


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def causal_pairs(length):
    """(query, key) pairs of the causal mask over ``length`` rows."""
    return length * (length + 1) // 2


def window_pairs(length, window):
    """Pairs of a causal window: row ``i`` sees ``min(i + 1, window)``."""
    w = min(window, length)
    return w * (w + 1) // 2 + (length - w) * w


def visible_pairs(cfg, length, kind):
    return window_pairs(length, cfg["sliding_window"]) \
        if kind in ("window", "sliding_attention") else causal_pairs(length)


def attention_fwd_flops(cfg, length, kind):
    """One differential attention call of one sample: every query head's map
    (``QK^T`` over the head size) times the pair's values side by side (``PV``
    over twice the head size), over the pairs a layer of ``kind`` shows."""
    return (2 * cfg["num_attention_heads"] * visible_pairs(cfg, length, kind)
            * 3 * head_dim(cfg))


def attention_fwd_bytes(cfg, length, itemsize):
    """One call of one sample as the program hands it to the kernel: q and k
    of the head size, v and o of twice it, a key-value head repeated for
    every query head that reads it, read or written once, and the float32
    log-sum-exp a query row."""
    return cfg["num_attention_heads"] * length * (
        6 * head_dim(cfg) * itemsize + 4)


def ssm_scan_fwd_flops(cfg, length):
    """One sample's forward scan: a row a channel a state index the step
    times ``A`` (1), the decayed state plus the input's term (2 products, 1
    sum) and the read through ``C`` (2); a row a channel the step times the
    input and the skip (3).  The exponentials are not counted."""
    channels, state, _, _ = ssm_sizes(cfg)
    return length * channels * (6 * state + 3)


def ssm_scan_fwd_bytes(cfg, length, itemsize):
    """One sample: x read and y written in the step's dtype, the float32
    step read, ``B`` and ``C`` read, ``A`` and the skip read once, and every
    chunk's state (``channels x state`` float32) written for the backward."""
    channels, state, _, _ = ssm_sizes(cfg)
    return (length * channels * (2 * itemsize + 4)
            + length * 2 * state * itemsize + (channels * state + channels) * 4
            + length // SSM_CHUNK * channels * state * 4)


def ssm_scan_bwd_flops(cfg, length):
    """One sample's backward scan, the walk forward again not counted: a row
    a channel a state index the decay's argument (1), the cotangent's update
    (2), the sums for ``dB``, ``dC`` and the input's term (2 each), what goes
    through the decay (2) into the step's and ``A``'s cotangents (2 each) and
    the cotangent handed to the row before (1); a row a channel the input's,
    the step's and the skip's cotangents (7)."""
    channels, state, _, _ = ssm_sizes(cfg)
    return length * channels * (16 * state + 7)


def ssm_scan_bwd_bytes(cfg, length, itemsize):
    """One sample: x and the output's cotangent read and x's written in the
    step's dtype, the float32 step read and its cotangent written, ``B`` and
    ``C`` read and their cotangents written, ``A`` read and its cotangent
    written, and every chunk's state read."""
    channels, state, _, _ = ssm_sizes(cfg)
    return (length * channels * (3 * itemsize + 8)
            + length * 4 * state * itemsize
            + 2 * (channels * state + channels) * 4
            + length // SSM_CHUNK * channels * state * 4)


def mixer_fwd_flops(cfg, length, kind):
    """One sample through one layer's mixer: its projections and its core."""
    h, heads, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"])
    hd = head_dim(cfg)
    channels, state, _, rank = ssm_sizes(cfg)
    if kind == "ssm":
        return 2 * length * (
            h * 2 * channels + channels * (rank + 2 * state)
            + rank * channels + channels * h) \
            + ssm_scan_fwd_flops(cfg, length)
    if kind == "gmu":
        return 2 * length * 2 * h * channels
    proj = 2 * heads * hd * h + (0 if kind == "cross" else 2 * kv * hd * h)
    return 2 * length * proj + attention_fwd_flops(cfg, length, kind)


def forward_flops_per_sample(cfg, length):
    """One sample's forward pass: ``length`` rows through the layers (a
    mixer, then the SwiGLU) and the tied output head.  The convolution,
    norms and gates' element-wise work is not counted."""
    h = cfg["hidden_size"]
    swiglu = 6 * length * h * cfg["intermediate_size"]
    return (sum(mixer_fwd_flops(cfg, length, kind) + swiglu
                for kind in layer_kinds(cfg))
            + 2 * length * h * cfg["vocab_size"])


def train_flops_per_sample(cfg, length):
    return 3 * forward_flops_per_sample(cfg, length)


def diff_attention_fwd_flops(cfg, length):
    """One full-length causal call of differential attention, one sample (the
    full layer's, and the cross layer's on the full layer's K and V)."""
    return attention_fwd_flops(cfg, length, "full")


def diff_attention_fwd_bytes(cfg, length, itemsize):
    return attention_fwd_bytes(cfg, length, itemsize)
