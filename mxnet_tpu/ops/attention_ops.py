"""Transformer/NLP operator family.

Reference: ``src/operator/contrib/transformer.cc`` (1.6 interleaved-matmul
self-attention ops — a fusion, not a parallelism strategy, SURVEY.md §3.2)
plus net-new LLM ops (RMSNorm, RoPE) required by the BASELINE Llama config.
All pure jax; the fused-attention hot path is ops/flash_attention.py.
"""
from __future__ import annotations

import functools

import numpy as _np

from .registry import register


def _jnp():
    import jax.numpy as jnp

    return jnp


# --------------------------------------------------------------------------
# interleaved-matmul attention ops (reference: transformer.cc).  Layout:
# qkv (L, B, 3*H*D) interleaved per head — the reference's memory layout.
# --------------------------------------------------------------------------
def _split_interleaved(qkv, heads, n):
    jnp = _jnp()
    L, B, E = qkv.shape
    d = E // (n * heads)
    x = qkv.reshape(L, B, heads, n, d)
    return [x[:, :, :, i, :] for i in range(n)]


@register("_contrib_interleaved_matmul_selfatt_qk",
          aliases=("interleaved_matmul_selfatt_qk",))
def interleaved_matmul_selfatt_qk(qkv, heads=1):
    """(L,B,3HD) -> scores (B*H, L, L), scaled by 1/sqrt(d)."""
    jnp = _jnp()
    q, k, _ = _split_interleaved(qkv, heads, 3)
    L, B, H, d = q.shape
    qt = q.transpose(1, 2, 0, 3).reshape(B * H, L, d)
    kt = k.transpose(1, 2, 0, 3).reshape(B * H, L, d)
    return jnp.einsum("xld,xmd->xlm", qt, kt) / _np.sqrt(d)


@register("_contrib_interleaved_matmul_selfatt_valatt",
          aliases=("interleaved_matmul_selfatt_valatt",))
def interleaved_matmul_selfatt_valatt(qkv, att, heads=1):
    """att (B*H,L,L) x V from qkv -> (L,B,H*D)."""
    jnp = _jnp()
    _, _, v = _split_interleaved(qkv, heads, 3)
    L, B, H, d = v.shape
    vt = v.transpose(1, 2, 0, 3).reshape(B * H, L, d)
    out = jnp.einsum("xlm,xmd->xld", att, vt)
    return out.reshape(B, H, L, d).transpose(2, 0, 1, 3).reshape(L, B, H * d)


@register("_contrib_interleaved_matmul_encdec_qk",
          aliases=("interleaved_matmul_encdec_qk",))
def interleaved_matmul_encdec_qk(q, kv, heads=1):
    jnp = _jnp()
    Lq, B, E = q.shape
    d = E // heads
    k, _ = _split_interleaved(kv, heads, 2)
    Lk = k.shape[0]
    qt = q.reshape(Lq, B, heads, d).transpose(1, 2, 0, 3).reshape(
        B * heads, Lq, d)
    kt = k.transpose(1, 2, 0, 3).reshape(B * heads, Lk, d)
    return jnp.einsum("xld,xmd->xlm", qt, kt) / _np.sqrt(d)


@register("_contrib_interleaved_matmul_encdec_valatt",
          aliases=("interleaved_matmul_encdec_valatt",))
def interleaved_matmul_encdec_valatt(kv, att, heads=1):
    jnp = _jnp()
    _, v = _split_interleaved(kv, heads, 2)
    Lk, B, H, d = v.shape
    Lq = att.shape[1]
    vt = v.transpose(1, 2, 0, 3).reshape(B * H, Lk, d)
    out = jnp.einsum("xlm,xmd->xld", att, vt)
    return out.reshape(B, H, Lq, d).transpose(2, 0, 1, 3).reshape(Lq, B, H * d)


# --------------------------------------------------------------------------
# LLM building-block ops (net-new capability, BASELINE config #5)
# --------------------------------------------------------------------------
@register("rms_norm")
def rms_norm(x, gamma, eps=1e-6):
    """RMSNorm (Llama-family normalization) — fp32 accumulation."""
    jnp = _jnp()
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    from jax import lax

    y = xf * lax.rsqrt(ms + eps)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)


def yarn_rope_parameters(head_dim, base, factor, original_max_position,
                         beta_fast=32.0, beta_slow=1.0, attention_factor=None,
                         truncate=True):
    """``(inv_freq, magnitude)`` of YaRN (Peng et al. 2023, as
    ``transformers``' ``_compute_yarn_parameters`` has it): ``head_dim / 2``
    inverse frequencies, a blend of the interpolated ones (``base``'s
    divided by ``factor``) and the extrapolated ones (``base``'s own) by a
    ramp over the rotated dimensions, and the factor that scales cos and
    sin (``0.1 ln(factor) + 1`` where ``attention_factor`` is None).  The
    ramp runs from the dimension that turns ``beta_fast`` times over
    ``original_max_position`` positions to the one that turns ``beta_slow``
    times, rounded outwards to whole dimensions when ``truncate``.  Computed
    on the host in float64; ``inv_freq`` is a tuple of Python floats, so
    that it can be a static attribute of ``rope``."""
    import math

    def dim_of(rotations):
        return (head_dim * math.log(original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low, high = dim_of(beta_fast), dim_of(beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001   # the ramp's width may not be 0
    n = _np.arange(head_dim // 2, dtype=_np.float64)
    extrapolated = base ** (-2.0 * n / head_dim)
    interpolated = extrapolated / factor
    ramp = _np.clip((n - low) / (high - low), 0.0, 1.0)
    inv_freq = interpolated * ramp + extrapolated * (1.0 - ramp)
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return tuple(float(f) for f in inv_freq), float(attention_factor)


def rope_angles(positions, l, d, base=10000.0, scale=1.0, inv_freq=None):
    """float32 angles of RoPE, ``(l, d / 2)`` for positions ``(l,)``
    (``None``: arange) and ``(b, l, d / 2)`` for ``(b, l)``."""
    jnp = _jnp()
    if positions is None:
        positions = jnp.arange(l)
    positions = jnp.asarray(positions) * scale
    if inv_freq is None:
        freqs = base ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    else:
        freqs = jnp.asarray(inv_freq, dtype=jnp.float32)
        if freqs.shape != (d // 2,):
            from ..base import MXNetError

            raise MXNetError(f"rope: inv_freq holds {freqs.shape} numbers "
                             f"for a head of {d}; wants {d // 2}")
    return positions[..., None] * freqs                    # (..., L, d/2)


@register("rope")
def rope(x, positions=None, base=10000.0, scale=1.0, inv_freq=None,
         magnitude=1.0, interleave=False):
    """Rotary position embedding over the last dim.

    x (B, H, L, D) with D even; positions (L,) or (B, L) (defaults to
    arange).  Half-split convention (Llama): pair ``n`` is ``(x[n], x[n +
    D / 2])``; with ``interleave`` it is ``(x[2n], x[2n + 1])``, at the
    same frequencies.  ``inv_freq`` (D / 2 numbers)
    takes the place of ``base``'s ``base ** (-2n / D)`` where a scaling of
    the frequencies gives its own (``yarn_rope_parameters``); ``magnitude``
    multiplies cos and sin."""
    jnp = _jnp()
    b, h, l, d = x.shape
    angles = rope_angles(positions, l, d, base, scale, inv_freq)
    if angles.ndim == 2:        # (L, d/2): shared across batch and heads
        angles = angles[None, None]
    elif angles.ndim == 3:      # (B, L, d/2): per-batch, broadcast over heads
        angles = angles[:, None]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if magnitude != 1.0:
        cos, sin = cos * magnitude, sin * magnitude
    cos, sin = cos.astype(x.dtype), sin.astype(x.dtype)
    if interleave:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


@register("segment_positions", differentiable=False)
def segment_positions(segment_ids):
    """Positions that start again at each document of a packed row:
    ``segment_ids`` (B, L) integers, a document a run of equal ids;
    ``pos[i] = i - (index of the first token of i's run)``, int32.  The
    first index of a run is the running maximum of the boundaries'."""
    import jax

    jnp = _jnp()
    ids = jnp.asarray(segment_ids)
    index = jnp.arange(ids.shape[-1], dtype=jnp.int32)
    starts = jnp.where(ids[..., 1:] != ids[..., :-1], index[1:], 0)
    starts = jnp.concatenate([jnp.zeros_like(starts[..., :1]), starts], -1)
    return index - jax.lax.cummax(starts, axis=ids.ndim - 1)


@register("swiglu")
def swiglu(gate, up):
    """SwiGLU gate: silu(gate) * up (Llama MLP)."""
    from jax import nn

    return nn.silu(gate) * up


@register("_contrib_moe_swiglu", aliases=("moe_swiglu",))
def moe_swiglu(x, router_weight, gate_proj, up_proj, down_proj,
               select_bias=None, capacity_factor=1.25, aux_loss_weight=0.0,
               top_k=1, renormalize=False, experts_first=0, score="softmax",
               route_scale=1.0, renorm_eps=0.0, n_group=1, topk_group=1):
    """MoE SwiGLU FFN over stacked expert weights (net-new vs the
    reference).  Registered as a first-class op so MoE models trace to
    Symbol and export/SymbolBlock-import like any other graph (fused RNN
    set the precedent for stateful library ops).

    x (B, L, H); router (H, E); gate/up (N, H, I); down (N, I, H).

    ``capacity_factor > 0``: switch top-1 routing with capacity dropping
    over all ``N = E`` experts (Mixtral-style stacking); the aux
    load-balance loss rides the backward pass via inject_aux_loss when
    aux_loss_weight > 0 (Switch Transformer eq. 4).

    ``capacity_factor = 0``: dropless ``top_k`` routing over the router's
    ``E`` outputs, of which this layer holds the ``N`` experts from
    ``experts_first`` on and computes their part of the result, by grouped
    products over the pairs sorted by expert
    (``parallel.expert_parallel.moe_apply``, which says how ``score``
    (``"softmax"`` or ``"sigmoid"``), ``select_bias (E,)``, ``renormalize``
    with ``renorm_eps`` and ``route_scale`` make the gates, and how
    ``n_group > 1`` confines the choice to a token's ``topk_group`` best
    groups of outputs).  Router logits,
    scores, bias and gates are float32 whatever the products' dtype, which
    is the expert weights' (under AMP the target dtype: ``x``, the router
    and the bias are exempt from the cast, contrib/amp/lists.py).  The
    layer's routed pairs, the rows and the parts its routing walked and its
    load imbalance go to ``telemetry.step_scalar``."""
    from jax import nn

    from .. import telemetry
    from ..parallel.expert_parallel import inject_aux_loss, moe_apply
    from .grouped_matmul import group_plan, grouped_dot, swiglu as gated

    capacity_factor = float(capacity_factor)
    aux_loss_weight = float(aux_loss_weight)
    params = {"g": gate_proj, "u": up_proj, "d": down_proj}
    b, l, h = x.shape
    toks = x.reshape(-1, h)

    if capacity_factor <= 0:
        def grouped_fn(p, rows, sizes):
            rows = rows.astype(p["g"].dtype)
            # the kernels' walk of the part, made once for its three
            # products, their transposes and SwiGLU between them; None
            # where the gate sends the products to ``lax.ragged_dot`` and
            # SwiGLU to ``nn.silu`` (``ops/grouped_matmul.py``)
            plan = group_plan(sizes, rows, p["g"])
            dot = functools.partial(grouped_dot, sizes=sizes, plan=plan)
            return dot(gated(dot(rows, p["g"]), dot(rows, p["u"]), plan),
                       p["d"])

        out, aux = moe_apply(
            grouped_fn, params, router_weight, toks, capacity_factor=None,
            top_k=int(top_k), renormalize=bool(renormalize),
            held=(int(experts_first), gate_proj.shape[0]), score=score,
            select_bias=select_bias, scale=float(route_scale),
            renorm_eps=float(renorm_eps),
            groups=(int(n_group), int(topk_group)) if int(n_group) > 1
            else None)
        if int(n_group) > 1:
            telemetry.MOE_GROUP_LIMITED_CALLS.inc()
        telemetry.step_scalar(telemetry.MOE_ROUTED_PAIRS.name,
                              aux["routed_pairs"])
        telemetry.step_scalar(telemetry.MOE_WALKED_ROWS.name,
                              aux["walked_rows"])
        telemetry.step_scalar(telemetry.MOE_ADDED_ROWS.name,
                              aux["added_rows"])
        telemetry.step_scalar(telemetry.MOE_LIVE_PARTS.name,
                              aux["live_parts"])
        telemetry.step_scalar(telemetry.MOE_PARTS.name, aux["parts"])
        telemetry.step_scalar(telemetry.MOE_LOAD_MAX_OVER_MEAN.name,
                              aux["load_max_over_mean"])
        return out.reshape(b, l, h)

    def expert_fn(p, toks):
        dt = p["g"].dtype
        toks = toks.astype(dt)
        return (nn.silu(toks @ p["g"]) * (toks @ p["u"])) @ p["d"]

    out, aux = moe_apply(expert_fn, params, router_weight, toks,
                         capacity_factor=capacity_factor)
    out = out.reshape(b, l, h)
    if aux_loss_weight:
        # router balance term rides the backward pass; without it routing
        # collapses onto few experts
        out = inject_aux_loss(
            out, aux_loss_weight
            * aux["load_balance_loss"].astype(out.dtype))
    return out
