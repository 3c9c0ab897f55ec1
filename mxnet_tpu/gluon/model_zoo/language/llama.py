"""Llama-3-family decoder as Gluon HybridBlocks.

Net-new vs the reference (MXNet 1.x predates LLMs — SURVEY.md §6.7); this is
BASELINE config #5: "Llama-3-8B under Gluon HybridBlock, stressing
hybridize()→HLO".  TPU-first choices: RMSNorm/RoPE/SwiGLU as registry ops
(fp32 accumulation inside, bf16 activations outside), attention through the
flash-attention kernel (ops/flash_attention.py), weights laid out so tp/fsdp
sharding specs map cleanly onto the two matmul dimensions.
"""
from __future__ import annotations

import math

import numpy as _np

from ....base import MXNetError
from ....profiler import (SCOPE_ATTENTION_PROJ, SCOPE_EMBED, SCOPE_FFN,
                          SCOPE_HEAD, SCOPE_KDA, SCOPE_MIXER_GATE,
                          SCOPE_MOE_SHARED, SCOPE_NORM, SCOPE_ROPE,
                          SCOPE_SSM_SCAN)
from ...block import HybridBlock
from ...parameter import Parameter
from ... import nn

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM", "llama3_8b",
           "llama_tiny", "RMSNorm", "serving_params", "prefill_apply",
           "decode_apply"]


class LlamaConfig:
    """The zoo's decoder, by its numbers (the comments in ``__init__`` say
    what each group means).

    ``remat=True``: under any jax trace of the net (``TrainStep``,
    ``hybridize()``, ``jax.jit`` / ``jax.grad`` over it) every decoder layer
    is one ``jax.checkpoint`` whose policy keeps, beside the layer's input,
    what the attention op names: its output ``o`` and row statistics ``lse``
    (``ops.flash_attention.KEPT_O`` / ``KEPT_LSE``), and of a ``"kda"`` layer
    the delta rule's output and chunk states (``ops.kda.KEPT_O`` /
    ``KEPT_STATES``), and of an expert layer the router's choice
    (``parallel.expert_parallel.KEPT``: the experts chosen, their scores or
    a softmax router's logits, the sorted walks' plan: a megabyte a layer,
    a softmax router's logits ``rows x experts`` float32 more).  The
    backward computes everything else of the layer again (norms,
    projections, q/k norm and RoPE, the experts, q, k and v) and runs the
    attention's backward kernel on the kept values: no attention forward,
    no router product, ``top_k`` or sort runs twice.  Kept a layer, for ``rows
    = batch x length``: ``rows x hidden`` (the input) plus ``rows x heads x
    head size`` (``o``), both in the activations' dtype, plus ``rows x
    heads`` float32 (``lse``): 130 MiB for ``o`` and ``lse`` at 16,384 rows
    and 32 heads of 128 in bf16; a ``"kda"`` layer keeps ``o`` and ``rows /
    64 x heads x key size x head size`` float32 of states.  One path, no
    option beside ``remat`` itself.  A net without ``remat``, the eager
    autograd tape and ``export()`` keep every activation as before (the last
    two warn that ``remat`` has no effect there); ``TrainStep(remat=True)``'s
    own whole-net checkpoint has no policy, so the names keep nothing inside
    it."""

    def __init__(self, vocab_size=128256, hidden_size=4096, num_layers=32,
                 num_heads=32, num_kv_heads=8, intermediate_size=14336,
                 rope_base=500000.0, max_seq_len=8192, rms_eps=1e-5,
                 dtype="float32", tie_embeddings=False, remat=False,
                 num_experts=0, moe_capacity_factor=1.25,
                 moe_aux_loss_weight=0.01, head_dim=None, qk_norm=False,
                 moe_top_k=1, moe_renormalize=False, moe_experts_held=None,
                 moe_intermediate_size=None, block_diffusion=0,
                 attention_types=None, attention_window=0,
                 rope_attention_types=("full", "window"),
                 attention_gate=False, post_norms=False, embed_scale=1.0,
                 num_dense_layers=0, moe_shared_intermediate_size=0,
                 moe_score="softmax", moe_route_scale=1.0,
                 moe_renorm_eps=0.0, moe_select_bias=False,
                 rope_parameters=None, moe_groups=None,
                 attention_heads_held=None, kv_lora_rank=0,
                 qk_nope_head_dim=None, qk_rope_head_dim=None,
                 v_head_dim=None, rope_interleave=False, kda_conv_size=4,
                 kda_lower_bound=-5.0, differential=False, norm="rms",
                 attention_bias=False, ssm_state_size=16, ssm_conv_size=4,
                 ssm_expand=2, ssm_dt_rank=None, first_layer_index=0):
        # num_experts > 0: an MoE FFN (parallel.expert_parallel) replaces
        # the dense SwiGLU MLP in every layer after the first
        # num_dense_layers; num_experts is the router's width.
        # moe_capacity_factor=<number>: switch top-1 routing with
        # capacity dropping (Mixtral-style; shard the expert dim over the
        # 'ep' mesh axis in TrainStep specs).  moe_capacity_factor=None:
        # dropless routing of moe_top_k experts a token (gates divided by
        # their sum when moe_renormalize), of which this net holds
        # moe_experts_held = (first, count) -- one chip's share of an
        # expert-parallel deployment; default all -- and adds their part
        # of the result only.  moe_intermediate_size is the experts' width
        # where it differs from the dense intermediate_size.
        self.num_experts = num_experts
        self.moe_capacity_factor = moe_capacity_factor
        self.moe_top_k = moe_top_k
        self.moe_renormalize = moe_renormalize
        self.moe_experts_held = tuple(moe_experts_held) \
            if moe_experts_held is not None else (0, num_experts)
        self.moe_intermediate_size = moe_intermediate_size \
            or intermediate_size
        # Switch load-balance loss coefficient, injected into the backward
        # via parallel.expert_parallel.inject_aux_loss (0 disables; the
        # dropless path has none)
        self.moe_aux_loss_weight = moe_aux_loss_weight
        # the dropless router beyond softmax top-k: moe_score "sigmoid"
        # scores each output by its own sigmoid; moe_select_bias gives the
        # layer a vector of the router's width (a parameter no gradient
        # reaches, grad_req "null": in checkpoints, out of the optimizer)
        # that is added to the scores for the choice of experts and never
        # to a gate; gates are divided by their sum plus moe_renorm_eps
        # (moe_renormalize) and multiplied by moe_route_scale.
        # moe_shared_intermediate_size > 0: a dense SwiGLU of that width
        # that every token passes, added to the routed part (on a share of
        # an expert-parallel deployment it is computed whole).
        # moe_groups = (n_group, topk_group): group-limited routing, the
        # choice confined to a token's topk_group best of n_group runs of
        # the router's outputs, a group scored by the sum of its two
        # largest biased scores (parallel.expert_parallel.limit_to_groups).
        self.moe_groups = tuple(moe_groups) if moe_groups else None
        self.num_dense_layers = num_dense_layers
        self.moe_score = moe_score
        self.moe_route_scale = moe_route_scale
        self.moe_renorm_eps = moe_renorm_eps
        self.moe_select_bias = moe_select_bias
        self.moe_shared_intermediate_size = moe_shared_intermediate_size
        # a layer's mixer is "full" (causal softmax attention), "window"
        # (causal over the last attention_window keys), "kda" (Kimi delta
        # attention, LlamaDeltaAttention: a gated delta rule over a state a
        # head, no positions) or "mla" (latent attention,
        # LlamaLatentAttention): attention_types names each layer's,
        # default all full; RoPE turns the q and k of the "full" and
        # "window" kinds in rope_attention_types, and always "mla"'s
        # qk_rope_head_dim.  attention_gate: o * sigmoid(x Wz) before the
        # output projection, Wz as wide as o, or with "head_wise" one
        # number a head ("mla" alone; "kda" has its own gate).  post_norms:
        # an RMSNorm on each sublayer's output before it joins the
        # residual, beside the one on its input.  embed_scale multiplies
        # the embeddings.
        # attention_heads_held = (first, count): the heads of num_heads
        # that this net holds, one chip's share of a deployment that
        # divides the heads (default all), for the kinds whose heads stand
        # alone ("kda", "mla"): projections, gates and the output
        # projection's rows are those heads', and what the absent heads
        # would add to the mixer's output is left out.
        # "mla": kv_lora_rank the latent's width (normed, shared by the
        # heads), qk_nope_head_dim + qk_rope_head_dim a head's q and k,
        # v_head_dim its v; rope_interleave: RoPE's pairs are neighbours.
        # "kda": heads of head_dim for k and v, a causal depthwise
        # convolution of kda_conv_size taps with SiLU on q, k and v, the
        # log-decay in (kda_lower_bound, 0); computed in chunks of KDA_CHUNK
        # rows, so a row is a whole number of them.
        # "ssm": a state-space block (LlamaStateSpace: Mamba-1, a scan over
        # a state of ssm_state_size a channel of ssm_expand x hidden_size
        # channels, a convolution of ssm_conv_size taps, steps through a
        # rank of ssm_dt_rank, default hidden_size / 16; no positions).
        # "gmu": a gated memory unit (LlamaGatedMemory), a gate on the scan
        # output that layer memory_layer hands on.  "cross": attention whose
        # K and V are those of layer kv_layer (LlamaCrossAttention: Wq and Wo
        # alone), causal.  memory_layer / kv_layer are the last "ssm" layer
        # before the first "gmu" and the last "full" or "window" layer
        # before the first "cross"; such a layer returns what it hands on
        # beside the residual stream, through its checkpoint too.
        # differential: "full", "window" and "cross" layers take the
        # difference of two softmax maps (arXiv:2410.05258, a pair's values
        # side by side): query heads 2p and 2p + 1 read key heads 2g and
        # 2g + 1 of the key-value pair g = p // (pairs of queries a pair of
        # keys), lambda from four learned vectors a layer and lambda_init
        # = 0.8 - 0.6 exp(-0.3 i) at the published index i = first_layer_index
        # + the layer's own, an RMSNorm over the pair's output.
        # norm "layer": LayerNorm with a bias for RMSNorm, in every layer and
        # after the last.  attention_bias: a bias on the q, k, v and output
        # projections.
        self.differential = differential
        self.norm = norm
        self.attention_bias = attention_bias
        self.ssm_state_size = ssm_state_size
        self.ssm_conv_size = ssm_conv_size
        self.ssm_inner_size = ssm_expand * hidden_size
        self.ssm_dt_rank = ssm_dt_rank or -(-hidden_size // 16)
        self.first_layer_index = first_layer_index
        self.attention_heads_held = tuple(attention_heads_held) \
            if attention_heads_held is not None else (0, num_heads)
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_interleave = rope_interleave
        self.kda_conv_size = kda_conv_size
        self.kda_lower_bound = float(kda_lower_bound)
        self.attention_types = tuple(attention_types) \
            if attention_types is not None else ("full",) * num_layers
        self.attention_window = attention_window
        self.rope_attention_types = tuple(rope_attention_types)
        self.attention_gate = attention_gate
        self.post_norms = post_norms
        self.embed_scale = embed_scale
        # rope_parameters: RoPE by attention kind, {"full" | "window":
        # {"rope_theta": base, and for YaRN "rope_type": "yarn", "factor",
        # "original_max_position_embeddings", "beta_fast", "beta_slow",
        # "attention_factor", "truncate"}} (the keys of a published
        # config's rope_parameters); a kind it does not name turns by
        # rope_base, and rope_attention_types still says which kinds turn
        self.rope_parameters = {k: dict(v) for k, v in
                                (rope_parameters or {}).items()}
        for kind, given in self.rope_parameters.items():
            if kind not in ("full", "window") or given.get(
                    "rope_type", "default") not in ("default", "yarn"):
                raise MXNetError(
                    "rope_parameters names the kinds 'full' and 'window' "
                    f"and the rope_type 'default' or 'yarn'; got {kind!r}: "
                    f"{given}")
        if self.rope_parameters and block_diffusion:
            raise MXNetError(
                "the block-diffusion layout (block_diffusion > 0) turns both "
                "halves of its row by rope_base; it takes no rope_parameters "
                "by kind")
        kinds = set(self.attention_types)
        if len(self.attention_types) != num_layers or kinds - {
                "full", "window", "kda", "mla", "ssm", "gmu", "cross"}:
            raise MXNetError(
                f"attention_types names each of the {num_layers} layers "
                "'full', 'window', 'kda', 'mla', 'ssm', 'gmu' or 'cross'; "
                f"got {self.attention_types}")
        self.memory_layer = self._source("gmu", ("ssm",))
        self.kv_layer = self._source("cross", ("full", "window"))
        if self.kv_layer is not None and (
                qk_norm or self.attention_types[self.kv_layer]
                in self.rope_attention_types):
            raise MXNetError(
                "a 'cross' layer's queries take no positions and no q/k "
                "norm, so the layer whose K and V it reads takes none "
                "(rope_attention_types, qk_norm)")
        if norm not in ("rms", "layer"):
            raise MXNetError(f"norm is 'rms' or 'layer'; got {norm!r}")
        if differential and (num_heads % 2 or num_kv_heads % 2
                             or block_diffusion or qk_norm or attention_gate):
            raise MXNetError(
                "differential attention pairs the query heads and the "
                "key-value heads (both even) of a causal layout, and is "
                "written without q/k norm and the attention gate")
        first, count = self.attention_heads_held
        if not (0 <= first and 0 < count and first + count <= num_heads):
            raise MXNetError(
                f"attention_heads_held {self.attention_heads_held} is no "
                f"run of the {num_heads} heads (num_heads)")
        if count != num_heads and kinds & {"full", "window", "cross"}:
            raise MXNetError(
                "a share of the heads (attention_heads_held) is written for "
                "the kinds 'kda' and 'mla'; 'full', 'window' and 'cross' "
                "layers share key-value heads and hold them all")
        if kinds & {"kda", "mla", "ssm", "gmu", "cross"} and block_diffusion:
            raise MXNetError(
                "'kda', 'mla', 'ssm', 'gmu' and 'cross' layers are causal: "
                "they do not take the block-diffusion layout "
                "(block_diffusion > 0)")
        if "mla" in kinds and not (
                kv_lora_rank and qk_nope_head_dim and qk_rope_head_dim
                and v_head_dim):
            raise MXNetError(
                "an 'mla' layer needs kv_lora_rank, qk_nope_head_dim, "
                "qk_rope_head_dim and v_head_dim")
        if attention_gate not in (False, True, "head_wise") or (
                attention_gate == "head_wise" and kinds - {"mla", "kda"}):
            raise MXNetError(
                "attention_gate is False, True or 'head_wise' (one number a "
                "head, for 'mla' layers; 'kda' has its own gate); got "
                f"{attention_gate!r} with {self.attention_types}")
        if "window" in self.attention_types and (
                attention_window < 1 or block_diffusion):
            raise MXNetError(
                "a window layer needs attention_window >= 1 and the causal "
                "layout (block_diffusion=0)")
        # remat: each decoder layer is a jax.checkpoint that keeps its input
        # and the attention op's output and row statistics (the delta rule's
        # output and chunk states) and computes the rest again in backward:
        # one more forward of the layer less the op's, (1 - a) / 3 more
        # FLOPs than no remat where a is the op's share of the layer's
        # forward FLOPs (1/3 more when the op ran twice), for O(num_layers)
        # less activation HBM; the class docstring has the bytes a layer
        self.remat = remat
        # qk_norm: an RMSNorm over the head size on q and on k before RoPE
        # (a learned vector each, shared by the heads)
        self.qk_norm = qk_norm
        # block_diffusion = B > 0: the training layout of block diffusion
        # (Arriola et al. 2025).  The net takes rows [xt ; x0] of a noised
        # copy and the clean copy of L tokens each, both halves at
        # positions 0..L-1, attends under the block-diffusion mask of
        # blocks of B (ops/flash_attention.py) and returns the logits of
        # the noised half.  0: causal, one token a position.
        self.block_diffusion = block_diffusion
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.intermediate_size = intermediate_size
        self.rope_base = rope_base
        self.max_seq_len = max_seq_len
        self.rms_eps = rms_eps
        self.dtype = dtype
        self.tie_embeddings = tie_embeddings
        if head_dim is None and hidden_size % num_heads:
            raise MXNetError(
                f"num_heads ({num_heads}) must divide hidden_size "
                f"({hidden_size})")
        if num_heads % num_kv_heads:
            raise MXNetError(
                f"num_kv_heads ({num_kv_heads}) must divide num_heads "
                f"({num_heads}) for GQA")
        self.head_dim = head_dim or hidden_size // num_heads

    def _source(self, reader, sources):
        """The layer that hands on what the ``reader`` layers read: the last
        layer of ``sources`` before the first reader; None where there is no
        reader."""
        kinds = self.attention_types
        if reader not in kinds:
            return None
        before = [i for i in range(kinds.index(reader))
                  if kinds[i] in sources]
        if not before:
            raise MXNetError(
                f"a {reader!r} layer reads what an earlier layer of "
                f"{sources} hands on; got {self.attention_types}")
        return before[-1]

    def rope_kwargs(self, kind):
        """What ``F.rope`` takes for a layer of ``kind``: ``base`` alone,
        or YaRN's inverse frequencies and magnitude."""
        given = self.rope_parameters.get(kind, {})
        base = float(given.get("rope_theta", self.rope_base))
        if given.get("rope_type", "default") == "default":
            return {"base": base}
        from ....ops.attention_ops import yarn_rope_parameters

        inv_freq, magnitude = yarn_rope_parameters(
            self.head_dim, base, given["factor"],
            given["original_max_position_embeddings"],
            given.get("beta_fast", 32.0), given.get("beta_slow", 1.0),
            given.get("attention_factor"), given.get("truncate", True))
        return {"inv_freq": inv_freq, "magnitude": magnitude}

    def sparse_layer(self, i):
        """Is layer ``i``'s FFN the expert layer?"""
        return self.num_experts > 0 and i >= self.num_dense_layers

    def layers_alike(self):
        """Are all layers of one kind (what a scan over stacked layers or a
        pipeline of equal stages needs)?"""
        n = self.num_layers
        return len(set(self.attention_types)) <= 1 and len(
            {self.sparse_layer(i) for i in range(n)}) <= 1


class RMSNorm(HybridBlock):
    def __init__(self, dim, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        self.weight = self.params.get("weight", shape=(dim,), init="ones")

    def hybrid_forward(self, F, x, weight):
        return F.rms_norm(x, weight, eps=self._eps)

    def scale(self):
        """The weight as ``hybrid_forward`` gets it (under a trace its
        stand-in), for a caller whose own op holds the norm."""
        return self._resolve_params()["weight"]


def _block_norm(cfg, prefix):
    """A decoder's norm over the hidden size: RMSNorm, or LayerNorm with a
    bias (``cfg.norm``)."""
    if cfg.norm == "layer":
        return nn.LayerNorm(epsilon=cfg.rms_eps, in_channels=cfg.hidden_size,
                            prefix=prefix)
    return RMSNorm(cfg.hidden_size, cfg.rms_eps, prefix=prefix)


def _heads_first(t, heads, paired):
    """``(B, L, heads * D) -> (B, heads, L, D)``; ``paired`` (differential
    attention): the pairs' first members, then their second."""
    b, l, width = t.shape
    if not paired:
        return t.reshape((b, l, heads, width // heads)).transpose(
            (0, 2, 1, 3))
    return t.reshape((b, l, heads // 2, 2, width // heads)).transpose(
        (0, 3, 2, 1, 4)).reshape((b, heads, l, width // heads))


class _DifferentialMaps(HybridBlock):
    """What a differential attention layer learns beside its projections:
    the four vectors of ``lambda`` and the norm of a pair's output, with the
    layer's ``lambda_init`` (``LlamaConfig``, ``differential``)."""

    def __init__(self, cfg, index, **kwargs):
        super().__init__(**kwargs)
        hd = cfg.head_dim
        self._eps = cfg.rms_eps
        self._lambda_init = 0.8 - 0.6 * math.exp(
            -0.3 * (cfg.first_layer_index + index))
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, name, self.params.get(name, shape=(hd,)))
        self.subln = self.params.get("subln_weight", shape=(2 * hd,),
                                     init="ones")

    def hybrid_forward(self, F, o, lambda_q1, lambda_k1, lambda_q2,
                       lambda_k2, subln):
        return F.diff_attn_combine(o, lambda_q1, lambda_k1, lambda_q2,
                                   lambda_k2, subln,
                                   lambda_init=self._lambda_init,
                                   eps=self._eps)


def _attend(F, cfg, kind, q, k, v, segment_ids, maps):
    """The attention op of a ``"full"``, ``"window"`` or ``"cross"`` layer on
    heads-first q, k and v, and the heads side by side again ``(B, L, heads x
    head size)``.  Differential: one call over the pairs' first maps and
    their second (``_heads_first``'s order), a pair's values side by side as
    one ``v`` of twice the head size under both, then ``maps``."""
    import jax

    if maps is not None:
        v = F.concat(v, v, dim=1)
    sm_scale = 1.0 / math.sqrt(cfg.head_dim)
    if kind == "window":
        o = F.flash_attention(q, k, v, segment_ids, mask="window",
                              window=cfg.attention_window, sm_scale=sm_scale)
    else:
        o = F.flash_attention(q, k, v, segment_ids, causal=True,
                              sm_scale=sm_scale)
    if maps is not None:
        with jax.named_scope(SCOPE_MIXER_GATE):
            return maps(o)
    b, heads, l, hd = o.shape
    return o.transpose((0, 2, 1, 3)).reshape((b, l, heads * hd))


class LlamaAttention(HybridBlock):
    def __init__(self, cfg, kind="full", index=0, hands_on=False, **kwargs):
        super().__init__(**kwargs)
        d, hd = cfg.hidden_size, cfg.head_dim
        self._cfg = cfg
        self._kind = kind
        self._hands_on = hands_on    # returns its K and V beside its output
        biased = cfg.attention_bias
        # child names matter: parallel.tensor_parallel's Megatron rules key
        # on the q/k/v/o_proj suffixes to pick column- vs row-parallel specs
        with self.name_scope():
            self.q_proj = nn.Dense(cfg.num_heads * hd, use_bias=biased,
                                   flatten=False, in_units=d,
                                   prefix="q_proj_")
            self.k_proj = nn.Dense(cfg.num_kv_heads * hd, use_bias=biased,
                                   flatten=False, in_units=d,
                                   prefix="k_proj_")
            self.v_proj = nn.Dense(cfg.num_kv_heads * hd, use_bias=biased,
                                   flatten=False, in_units=d,
                                   prefix="v_proj_")
            self.o_proj = nn.Dense(d, use_bias=biased, flatten=False,
                                   in_units=cfg.num_heads * hd,
                                   prefix="o_proj_")
            if cfg.differential:
                self.maps = _DifferentialMaps(cfg, index, prefix="maps_")
            if cfg.qk_norm:
                self.q_norm = RMSNorm(hd, cfg.rms_eps, prefix="q_norm_")
                self.k_norm = RMSNorm(hd, cfg.rms_eps, prefix="k_norm_")
            if cfg.attention_gate:
                self.gate_proj = nn.Dense(cfg.num_heads * hd, use_bias=False,
                                          flatten=False, in_units=d,
                                          prefix="gate_proj_")

    def hybrid_forward(self, F, x, segment_ids=None, positions=None):
        """``segment_ids`` (batch, L) with the ``positions`` that start
        again at each document (``LlamaModel`` derives them): RoPE turns by
        those, and a query sees the keys of its own document alone."""
        import jax

        cfg = self._cfg
        b, l = x.shape[0], x.shape[1]
        hd = cfg.head_dim
        # the parts are named here, one after the other (profiler.py): the
        # attention op between them names its own kernels and backward
        if cfg.differential:
            # no positions, no q/k norm: the heads go first by the pairs'
            # members, a pair's values side by side as one head of 2 hd
            with jax.named_scope(SCOPE_ATTENTION_PROJ):
                q = _heads_first(self.q_proj(x), cfg.num_heads, True)
                k = _heads_first(self.k_proj(x), cfg.num_kv_heads, True)
                v = _heads_first(self.v_proj(x), cfg.num_kv_heads // 2,
                                 False)
            o = _attend(F, cfg, self._kind, q, k, v, segment_ids, self.maps)
            with jax.named_scope(SCOPE_ATTENTION_PROJ):
                o = self.o_proj(o)
            return (o, k, v) if self._hands_on else o
        with jax.named_scope(SCOPE_ATTENTION_PROJ):
            q, k = self.q_proj(x), self.k_proj(x)
            v = self.v_proj(x).reshape(
                (b, l, cfg.num_kv_heads, hd)).transpose((0, 2, 1, 3))
        turn = None     # what F.rope takes here, or no turn at all
        if cfg.block_diffusion:
            turn = {"base": cfg.rope_base}
            with jax.named_scope(SCOPE_ROPE):
                # [xt ; x0]: both halves of the row carry positions 0..L-1
                half = F.arange(0, l // 2, dtype="int32")
                positions = F.concat(half, half, dim=0)
        elif self._kind in cfg.rope_attention_types:
            turn = cfg.rope_kwargs(self._kind)
        # q/k norm and RoPE in one op, which also puts the heads before the
        # rows (F.qk_norm_rope); the norms stay their weights' owners
        def operand(proj, norm, heads):
            given = [proj]
            if norm is not None:
                given.append(norm.scale())
            if turn is not None and positions is not None:
                given.append(positions)
            return F.qk_norm_rope(*given, heads=heads, norm=norm is not None,
                                  eps=cfg.rms_eps, turn=turn is not None,
                                  **(turn or {}))

        if turn is not None:
            part = SCOPE_ROPE
        else:
            part = SCOPE_NORM if cfg.qk_norm else SCOPE_ATTENTION_PROJ
        with jax.named_scope(part):
            q = operand(q, getattr(self, "q_norm", None), cfg.num_heads)
            k = operand(k, getattr(self, "k_norm", None), cfg.num_kv_heads)
        sm_scale = 1.0 / math.sqrt(hd)
        if cfg.block_diffusion:
            o = F.flash_attention(q, k, v, segment_ids,
                                  mask="block_diffusion",
                                  mask_block=cfg.block_diffusion,
                                  sm_scale=sm_scale)
        elif self._kind == "window":
            o = F.flash_attention(q, k, v, segment_ids, mask="window",
                                  window=cfg.attention_window,
                                  sm_scale=sm_scale)
        else:
            o = F.flash_attention(q, k, v, segment_ids, causal=True,
                                  sm_scale=sm_scale)
        with jax.named_scope(SCOPE_ATTENTION_PROJ):
            o = o.transpose((0, 2, 1, 3)).reshape(
                (b, l, cfg.num_heads * hd))
            if cfg.attention_gate:
                o = o * F.sigmoid(self.gate_proj(x))
            o = self.o_proj(o)
        return (o, k, v) if self._hands_on else o


class LlamaCrossAttention(HybridBlock):
    """Attention over another layer's K and V (``cfg.kv_layer``'s, of that
    layer's normed input): ``Wq`` and ``Wo`` alone, causal, differential
    where the net is (its own four vectors, norm and ``lambda_init``)."""

    def __init__(self, cfg, index=0, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        d, width = cfg.hidden_size, cfg.num_heads * cfg.head_dim
        with self.name_scope():
            self.q_proj = nn.Dense(width, use_bias=cfg.attention_bias,
                                   flatten=False, in_units=d,
                                   prefix="q_proj_")
            self.o_proj = nn.Dense(d, use_bias=cfg.attention_bias,
                                   flatten=False, in_units=width,
                                   prefix="o_proj_")
            self.maps = _DifferentialMaps(cfg, index, prefix="maps_") \
                if cfg.differential else None

    def hybrid_forward(self, F, x, k, v):
        import jax

        cfg = self._cfg
        with jax.named_scope(SCOPE_ATTENTION_PROJ):
            q = _heads_first(self.q_proj(x), cfg.num_heads, cfg.differential)
        o = _attend(F, cfg, "cross", q, k, v, None, self.maps)
        with jax.named_scope(SCOPE_ATTENTION_PROJ):
            return self.o_proj(o)


# rows of a chunk of the selective scan (``ops/selective_scan.py``)
SSM_CHUNK = 64


class LlamaStateSpace(HybridBlock):
    """A state-space block (Mamba-1, arXiv:2312.00752): ``[xs, z] = x Win``;
    ``xc = SiLU(conv(xs))``, a causal depthwise convolution with a bias;
    ``[r, B, C] = xc Wx``; ``delta = softplus(r Wdt + b)``; ``A = -exp(A_log)``;
    the selective scan over a state of ``ssm_state_size`` a channel
    (``F.selective_scan``, its skip ``D``); ``(y * SiLU(z)) Wout``.  No
    positions.  ``hands_on``: returns the scan's output ``y``, before the
    gate, beside its own (the memory of the ``"gmu"`` layers)."""

    def __init__(self, cfg, hands_on=False, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        self._hands_on = hands_on
        d, inner, n = cfg.hidden_size, cfg.ssm_inner_size, cfg.ssm_state_size
        plain = dict(use_bias=False, flatten=False)
        with self.name_scope():
            self.in_proj = nn.Dense(2 * inner, in_units=d, prefix="in_proj_",
                                    **plain)
            self.conv = self.params.get(
                "conv_weight", shape=(cfg.ssm_conv_size, inner))
            self.conv_bias = self.params.get("conv_bias", shape=(inner,),
                                             init="zeros")
            self.x_proj = nn.Dense(cfg.ssm_dt_rank + 2 * n, in_units=inner,
                                   prefix="x_proj_", **plain)
            self.dt_proj = nn.Dense(inner, use_bias=True, flatten=False,
                                    in_units=cfg.ssm_dt_rank,
                                    prefix="dt_proj_")
            self.a_log = self.params.get("a_log", shape=(inner, n),
                                         init="zeros")
            self.d_skip = self.params.get("d_skip", shape=(inner,),
                                          init="ones")
            self.out_proj = nn.Dense(d, in_units=inner, prefix="out_proj_",
                                     **plain)

    def hybrid_forward(self, F, x, segment_ids=None, positions=None, *,
                       conv, conv_bias, a_log, d_skip):
        import jax

        cfg = self._cfg
        if segment_ids is not None:
            raise MXNetError(
                "an 'ssm' layer does not take segment_ids yet: a state and "
                "a convolution that start again at a document's boundary "
                "are not written")
        inner, n, rank = (cfg.ssm_inner_size, cfg.ssm_state_size,
                          cfg.ssm_dt_rank)

        def part(t, begin, end):
            return F.slice_axis(t, axis=2, begin=begin, end=end)

        with jax.named_scope(SCOPE_ATTENTION_PROJ):
            xz = self.in_proj(x)
        with jax.named_scope(SCOPE_MIXER_GATE):
            xc = F.short_conv(part(xz, 0, inner), conv, conv_bias)
        with jax.named_scope(SCOPE_ATTENTION_PROJ):
            rbc = self.x_proj(xc)
            dt = self.dt_proj(part(rbc, 0, rank))
        with jax.named_scope(SCOPE_MIXER_GATE):
            delta, rate = F.ssm_delta(dt), F.ssm_rate(a_log)
        with jax.named_scope(SCOPE_SSM_SCAN):
            y = F.selective_scan(xc, delta, rate, part(rbc, rank, rank + n),
                                 part(rbc, rank + n, rank + 2 * n), d_skip,
                                 chunk=SSM_CHUNK)
        with jax.named_scope(SCOPE_MIXER_GATE):
            gated = F.swiglu(part(xz, inner, 2 * inner), y)
        with jax.named_scope(SCOPE_ATTENTION_PROJ):
            out = self.out_proj(gated)
        return (out, y) if self._hands_on else out


class LlamaGatedMemory(HybridBlock):
    """A gated memory unit (SambaY, arXiv:2507.06607): ``(SiLU(x W1) * M)
    W2`` with ``M`` the scan output that ``cfg.memory_layer`` hands on."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        d, inner = cfg.hidden_size, cfg.ssm_inner_size
        plain = dict(use_bias=False, flatten=False)
        with self.name_scope():
            self.in_proj = nn.Dense(inner, in_units=d, prefix="in_proj_",
                                    **plain)
            self.out_proj = nn.Dense(d, in_units=inner, prefix="out_proj_",
                                     **plain)

    def hybrid_forward(self, F, x, memory):
        import jax

        with jax.named_scope(SCOPE_ATTENTION_PROJ):
            gate = self.in_proj(x)
        with jax.named_scope(SCOPE_MIXER_GATE):
            gated = F.swiglu(gate, memory)
        with jax.named_scope(SCOPE_ATTENTION_PROJ):
            return self.out_proj(gated)


class LlamaLatentAttention(HybridBlock):
    """Latent attention (MLA, DeepSeek-V2's form without a q latent), in its
    training form: ``q_h = x Wq_h`` of ``qk_nope_head_dim +
    qk_rope_head_dim``; ``[c ; kr] = x Wkva`` (``kv_lora_rank +
    qk_rope_head_dim``), ``c`` normed; ``[k_h ; v_h] = c Wkvb_h``; RoPE on
    ``q_h``'s last ``qk_rope_head_dim`` and on ``kr``, which every head
    shares; causal softmax attention over ``[q_nope ; q_rope] . [k_h ;
    kr]`` through ``F.flash_attention`` at its two widths; a gate a head
    (``attention_gate="head_wise"``); the heads held alone
    (``attention_heads_held``)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        d, held = cfg.hidden_size, cfg.attention_heads_held[1]
        nope, turned = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        with self.name_scope():
            self.q_proj = nn.Dense(held * (nope + turned), use_bias=False,
                                   flatten=False, in_units=d,
                                   prefix="q_proj_")
            self.kv_a_proj = nn.Dense(cfg.kv_lora_rank + turned,
                                      use_bias=False, flatten=False,
                                      in_units=d, prefix="kv_a_proj_")
            self.kv_a_norm = RMSNorm(cfg.kv_lora_rank, cfg.rms_eps,
                                     prefix="kv_a_norm_")
            self.kv_b_proj = nn.Dense(held * (nope + cfg.v_head_dim),
                                      use_bias=False, flatten=False,
                                      in_units=cfg.kv_lora_rank,
                                      prefix="kv_b_proj_")
            self.o_proj = nn.Dense(d, use_bias=False, flatten=False,
                                   in_units=held * cfg.v_head_dim,
                                   prefix="o_proj_")
            if cfg.attention_gate:
                self.gate_proj = nn.Dense(
                    held * (1 if cfg.attention_gate == "head_wise"
                            else cfg.v_head_dim),
                    use_bias=False, flatten=False, in_units=d,
                    prefix="gate_proj_")

    def hybrid_forward(self, F, x, segment_ids=None, positions=None):
        import jax

        cfg = self._cfg
        if segment_ids is not None:
            raise MXNetError(
                "an 'mla' layer does not take segment_ids yet: packed "
                "documents under latent attention are not written")
        b, l = x.shape[0], x.shape[1]
        held = cfg.attention_heads_held[1]
        nope, turned, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                            cfg.v_head_dim)

        def heads(t, width):
            return t.reshape((b, l, held, width)).transpose((0, 2, 1, 3))

        with jax.named_scope(SCOPE_ATTENTION_PROJ):
            q = heads(self.q_proj(x), nope + turned)
            latent = self.kv_a_proj(x)
        with jax.named_scope(SCOPE_NORM):
            c = self.kv_a_norm(F.slice_axis(latent, axis=2, begin=0,
                                            end=cfg.kv_lora_rank))
        with jax.named_scope(SCOPE_ATTENTION_PROJ):
            kv = heads(self.kv_b_proj(c), nope + dv)
            k_nope = F.slice_axis(kv, axis=3, begin=0, end=nope)
            v = F.slice_axis(kv, axis=3, begin=nope, end=nope + dv)
        with jax.named_scope(SCOPE_ROPE):
            turn = dict(base=cfg.rope_base, interleave=cfg.rope_interleave)
            q_rope = F.rope(F.slice_axis(q, axis=3, begin=nope,
                                         end=nope + turned), **turn)
            kr = F.rope(F.slice_axis(latent, axis=2, begin=cfg.kv_lora_rank,
                                     end=cfg.kv_lora_rank + turned)
                        .reshape((b, 1, l, turned)), **turn)
            q = F.concat(F.slice_axis(q, axis=3, begin=0, end=nope), q_rope,
                         dim=3)
            k = F.concat(k_nope, F.broadcast_to(
                kr, shape=(b, held, l, turned)), dim=3)
        o = F.flash_attention(q, k, v, causal=True,
                              sm_scale=1.0 / math.sqrt(nope + turned))
        if cfg.attention_gate:
            with jax.named_scope(SCOPE_MIXER_GATE):
                gate = F.sigmoid(self.gate_proj(x))
                o = o * heads(gate, gate.shape[2] // held)
        with jax.named_scope(SCOPE_ATTENTION_PROJ):
            return self.o_proj(o.transpose((0, 2, 1, 3)).reshape(
                (b, l, held * dv)))


# rows of a chunk of the delta rule (``ops/kda.py``): one value until two
# workloads need two
KDA_CHUNK = 64


class LlamaDeltaAttention(HybridBlock):
    """Kimi delta attention (Kimi Linear, arXiv:2510.26692): q, k and v
    through a causal depthwise convolution and SiLU, q and k l2-normed a
    head, a bounded log-decay a channel and a beta a head from the same
    input, the gated delta rule over a state a head (``F.kda``), then an
    RMSNorm over the head (one learned vector) times a full-width sigmoid
    gate, and the output projection.  No positions.  The heads held alone
    (``attention_heads_held``)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        d, hd, held = (cfg.hidden_size, cfg.head_dim,
                       cfg.attention_heads_held[1])
        wide = dict(use_bias=False, flatten=False, in_units=d)
        with self.name_scope():
            self.q_proj = nn.Dense(held * hd, prefix="q_proj_", **wide)
            self.k_proj = nn.Dense(held * hd, prefix="k_proj_", **wide)
            self.v_proj = nn.Dense(held * hd, prefix="v_proj_", **wide)
            self.q_conv = self.params.get(
                "q_conv_weight", shape=(cfg.kda_conv_size, held * hd))
            self.k_conv = self.params.get(
                "k_conv_weight", shape=(cfg.kda_conv_size, held * hd))
            self.v_conv = self.params.get(
                "v_conv_weight", shape=(cfg.kda_conv_size, held * hd))
            self.f_proj = nn.Dense(held * hd, prefix="f_proj_", **wide)
            self.a_log = self.params.get("a_log", shape=(held,),
                                         init="zeros")
            self.dt_bias = self.params.get("dt_bias", shape=(held * hd,),
                                           init="zeros")
            self.b_proj = nn.Dense(held, prefix="b_proj_", **wide)
            self.gate_proj = nn.Dense(held * hd, prefix="gate_proj_", **wide)
            self.o_norm = RMSNorm(hd, cfg.rms_eps, prefix="o_norm_")
            self.o_proj = nn.Dense(d, use_bias=False, flatten=False,
                                   in_units=held * hd, prefix="o_proj_")

    def hybrid_forward(self, F, x, segment_ids=None, positions=None, *,
                       q_conv, k_conv, v_conv, a_log, dt_bias):
        import jax

        cfg = self._cfg
        if segment_ids is not None:
            raise MXNetError(
                "a 'kda' layer does not take segment_ids yet: a state and "
                "a convolution that start again at a document's boundary "
                "are not written")
        b, l = x.shape[0], x.shape[1]
        hd, held = cfg.head_dim, cfg.attention_heads_held[1]
        with jax.named_scope(SCOPE_ATTENTION_PROJ):
            q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
            f, beta, z = self.f_proj(x), self.b_proj(x), self.gate_proj(x)
        with jax.named_scope(SCOPE_MIXER_GATE):
            q = F.l2_norm_heads(F.short_conv(q, q_conv), heads=held,
                                scale=1.0 / math.sqrt(hd))
            k = F.l2_norm_heads(F.short_conv(k, k_conv), heads=held)
            v = F.short_conv(v, v_conv).reshape(
                (b, l, held, hd)).transpose((0, 2, 1, 3))
            g = F.kda_decay(f, a_log, dt_bias, heads=held,
                            lower_bound=cfg.kda_lower_bound)
            beta = F.sigmoid(beta).transpose((0, 2, 1))
        with jax.named_scope(SCOPE_KDA):
            o = F.kda(q, k, v, g, beta, chunk=KDA_CHUNK)
        with jax.named_scope(SCOPE_MIXER_GATE):
            o = self.o_norm(o.transpose((0, 2, 1, 3))).reshape(
                (b, l, held * hd)) * F.sigmoid(z)
        with jax.named_scope(SCOPE_ATTENTION_PROJ):
            return self.o_proj(o)


MIXERS = {"kda": LlamaDeltaAttention, "mla": LlamaLatentAttention}
# the kinds that read what an earlier layer hands on, by its name, and the
# values handed on under each name
READS = {"gmu": "memory", "cross": "kv"}
HANDED_VALUES = {"memory": 1, "kv": 2}


class LlamaMLP(HybridBlock):
    def __init__(self, cfg, width=None, **kwargs):
        super().__init__(**kwargs)
        width = width or cfg.intermediate_size
        with self.name_scope():
            self.gate_proj = nn.Dense(width, use_bias=False,
                                      flatten=False, in_units=cfg.hidden_size,
                                      prefix="gate_proj_")
            self.up_proj = nn.Dense(width, use_bias=False,
                                    flatten=False, in_units=cfg.hidden_size,
                                    prefix="up_proj_")
            self.down_proj = nn.Dense(cfg.hidden_size, use_bias=False,
                                      flatten=False, in_units=width,
                                      prefix="down_proj_")

    def hybrid_forward(self, F, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaMoEMLP(HybridBlock):
    """MoE SwiGLU FFN (net-new vs the reference): switch top-1 with a
    capacity, or dropless top-k over the experts held, with a shared expert
    beside them where the config gives one a width (``LlamaConfig``).

    Expert weights are stacked with a leading expert axis, one entry an
    expert held, so parallel.expert_parallel's dispatch/combine einsums
    (and the ep sharding) or its grouped products apply directly; the
    router keeps the width ``num_experts``."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        E, H, I = cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size
        N = cfg.moe_experts_held[1]
        if cfg.moe_capacity_factor is not None and (
                N != E or cfg.moe_top_k != 1 or cfg.moe_score != "softmax"
                or cfg.moe_select_bias or cfg.moe_route_scale != 1.0
                or cfg.moe_groups):
            raise MXNetError(
                "a moe_capacity_factor is the switch top-1 layer over every "
                "expert, softmax gates; moe_top_k > 1, moe_experts_held, "
                "moe_score, moe_select_bias, moe_route_scale and moe_groups "
                "route dropless (moe_capacity_factor=None)")
        with self.name_scope():
            self.router = self.params.get("router_weight", shape=(H, E))
            self.gate_proj = self.params.get("gate_proj_weight",
                                             shape=(N, H, I))
            self.up_proj = self.params.get("up_proj_weight", shape=(N, H, I))
            self.down_proj = self.params.get("down_proj_weight",
                                             shape=(N, I, H))
            if cfg.moe_select_bias:
                self.select_bias = self.params.get(
                    "select_bias", shape=(E,), init="zeros", grad_req="null")
            if cfg.moe_shared_intermediate_size:
                self.shared = LlamaMLP(
                    cfg, width=cfg.moe_shared_intermediate_size,
                    prefix="shared_")

    def hybrid_forward(self, F, x, router, gate_proj, up_proj, down_proj,
                       select_bias=None):
        # a registered op (not a raw apply_fn), so the block traces to
        # Symbol and exports/imports like the rest of the zoo
        cfg = self._cfg
        if cfg.moe_capacity_factor is not None:
            return F.moe_swiglu(x, router, gate_proj, up_proj, down_proj,
                                capacity_factor=cfg.moe_capacity_factor,
                                aux_loss_weight=cfg.moe_aux_loss_weight)
        n_group, topk_group = cfg.moe_groups or (1, 1)
        out = F.moe_swiglu(x, router, gate_proj, up_proj, down_proj,
                           select_bias, capacity_factor=0.0,
                           top_k=cfg.moe_top_k,
                           renormalize=cfg.moe_renormalize,
                           experts_first=cfg.moe_experts_held[0],
                           score=cfg.moe_score,
                           route_scale=cfg.moe_route_scale,
                           renorm_eps=cfg.moe_renorm_eps, n_group=n_group,
                           topk_group=topk_group)
        if cfg.moe_shared_intermediate_size:
            import jax

            with jax.named_scope(SCOPE_MOE_SHARED):
                out = out + self.shared(x)
        return out


def _several(out):
    """A block's result as a tuple, whether it returned one value or more."""
    return tuple(out) if isinstance(out, (tuple, list)) else (out,)


class LlamaDecoderLayer(HybridBlock):
    """Layer ``index`` of the decoder: its attention of the kind
    ``cfg.attention_types`` names, its FFN dense or the expert layer
    (``cfg.sparse_layer``)."""

    def __init__(self, cfg, index=0, **kwargs):
        super().__init__(**kwargs)
        self._remat = cfg.remat
        self._post_norms = cfg.post_norms
        kind = cfg.attention_types[index]
        # what the layer hands on beside the residual stream ("memory": its
        # scan's output, "kv": its K and V; None: nothing), and which of
        # those its mixer reads, after the packed row's ids
        self.hands_on = {cfg.memory_layer: "memory",
                         cfg.kv_layer: "kv"}.get(index)
        self.reads = READS.get(kind)
        with self.name_scope():
            self.input_layernorm = _block_norm(cfg, "input_layernorm_")
            if kind in MIXERS:
                self.self_attn = MIXERS[kind](cfg, prefix="self_attn_")
            elif kind == "ssm":
                self.self_attn = LlamaStateSpace(
                    cfg, hands_on=bool(self.hands_on), prefix="self_attn_")
            elif kind == "gmu":
                self.self_attn = LlamaGatedMemory(cfg, prefix="self_attn_")
            elif kind == "cross":
                self.self_attn = LlamaCrossAttention(cfg, index,
                                                     prefix="self_attn_")
            else:
                self.self_attn = LlamaAttention(
                    cfg, kind=kind, index=index,
                    hands_on=bool(self.hands_on), prefix="self_attn_")
            self.post_attention_layernorm = _block_norm(
                cfg, "post_attention_layernorm_")
            if cfg.sparse_layer(index):
                self.mlp = LlamaMoEMLP(cfg, prefix="mlp_")
            else:
                self.mlp = LlamaMLP(cfg, prefix="mlp_")
            if cfg.post_norms:
                self.attn_out_layernorm = RMSNorm(
                    cfg.hidden_size, cfg.rms_eps,
                    prefix="attn_out_layernorm_")
                self.mlp_out_layernorm = RMSNorm(
                    cfg.hidden_size, cfg.rms_eps, prefix="mlp_out_layernorm_")

    def _body(self, x, *rest):
        """``rest``: the packed row's ids and positions (or nothing), then
        what the mixer reads of earlier layers.  Returns the residual stream,
        or a tuple of it and what the layer hands on."""
        import jax

        with jax.named_scope(SCOPE_NORM):
            h = self.input_layernorm(x)
        if self.reads:      # a reader takes no ids: its source refused them
            rest = rest[len(rest) - HANDED_VALUES[self.reads]:]
        a, *handed = _several(self.self_attn(h, *rest))
        if self._post_norms:
            with jax.named_scope(SCOPE_NORM):
                a = self.attn_out_layernorm(a)
        x = x + a
        with jax.named_scope(SCOPE_NORM):
            h = self.post_attention_layernorm(x)
        if isinstance(self.mlp, LlamaMLP):
            with jax.named_scope(SCOPE_FFN):
                m = self.mlp(h)
        else:   # the expert layer names its own parts
            m = self.mlp(h)
        if self._post_norms:
            with jax.named_scope(SCOPE_NORM):
                m = self.mlp_out_layernorm(m)
        return (x + m, *handed) if handed else x + m

    def hybrid_forward(self, F, x, *packed):
        """``packed``: nothing, or the row's segment ids and positions,
        which go on to the attention; then what the layer's mixer reads of
        earlier layers (``reads``)."""
        if self._remat:
            import jax

            from ....ndarray.ndarray import NDArray

            xv = x._get() if isinstance(x, NDArray) else x
            if isinstance(xv, jax.core.Tracer):
                # under a jax trace (TrainStep's fused step, hybridize()'s
                # cached op, any jax.jit/grad over the net): checkpoint the
                # whole layer — closed-over parameter tracers differentiate
                # normally, activations are recomputed in backward, all but
                # the attention op's output and row statistics: with those
                # kept, the recomputation holds no attention forward
                # what the layer gives to telemetry.step_scalar leaves the
                # checkpoint as an output and is given again outside
                from .... import telemetry as _telemetry
                from ....ops import flash_attention as _fa, kda as _kda

                from ....ops import selective_scan as _ssm
                from ....parallel import expert_parallel as _ep

                ctx = getattr(x, "context", None)

                def body_pure(*values):
                    with _telemetry.collect_step_scalars() as scalars:
                        out = _several(self._body(*(
                            NDArray._from_jax(v, ctx) for v in values)))
                    return tuple(o._get() for o in out), scalars.stacked()

                # what the layer hands on leaves the checkpoint as an output
                # beside the residual stream, and what it reads enters as an
                # input: their cotangents come back from every reader
                with _fa.checkpoint_keeps():
                    out, scalars = jax.checkpoint(
                        body_pure,
                        policy=jax.checkpoint_policies.save_only_these_names(
                            _fa.KEPT_O, _fa.KEPT_LSE, _kda.KEPT_O,
                            _kda.KEPT_STATES, _ssm.KEPT_Y,
                            _ssm.KEPT_STATES, *_ep.KEPT))(
                                xv, *(p._get() for p in packed))
                for name, values in scalars.items():
                    _telemetry.step_scalar(name, values)
                out = tuple(NDArray._from_jax(o, ctx) for o in out)
                return out if len(out) > 1 else out[0]
            # the eager tape (autograd.record) and export()'s symbolic
            # trace have no remat node: warn rather than silently skipping
            # the memory saving the user asked for
            from .... import autograd as _ag

            if type(x).__name__ == "SymbolTracer" or _ag.is_recording():
                import warnings

                warnings.warn(
                    "LlamaConfig(remat=True) has no effect under the eager "
                    "autograd tape or export(); hybridize() the net or use "
                    "parallel.data_parallel.TrainStep (any jax trace of the "
                    "net) for rematerialized training",
                    stacklevel=2)
        return self._body(x, *packed)


def _count_handed_on(name, values):
    """Counts what a layer hands on, by its shapes, once a trace."""
    from .... import telemetry

    telemetry.LAYER_HANDED_ON_BYTES.labels(name=name).inc(sum(
        int(_np.prod(v.shape)) * _np.dtype(v.dtype).itemsize
        for v in values))


class LlamaModel(HybridBlock):
    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        with self.name_scope():
            self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                             prefix="embed_tokens_")
            self.layers = nn.HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for i in range(cfg.num_layers):
                    self.layers.add(LlamaDecoderLayer(cfg, i, prefix=f"{i}_"))
            self.norm = _block_norm(cfg, "norm_")

    def hybrid_forward(self, F, input_ids, segment_ids=None):
        """``segment_ids`` (batch, L) integers: the row is documents packed
        end to end, a document a run of equal ids.  Positions then start
        again at each document and every layer's attention is confined to
        the query's own (``F.flash_attention``'s ``segment_ids``)."""
        import jax

        with jax.named_scope(SCOPE_EMBED):
            if self._cfg.tie_embeddings:
                # the table comes back beside the rows for the head to read
                # (``F.shared_embedding``): one gradient reaches the table
                h, table = F.shared_embedding(
                    input_ids, self.embed_tokens._resolve_params()["weight"])
            else:
                h = self.embed_tokens(input_ids)
            if self._cfg.embed_scale != 1.0:
                h = h * self._cfg.embed_scale
        # what a packed row brings to every layer: nothing, or its ids and
        # the positions that start again at each document
        packed = ()
        if segment_ids is not None:
            with jax.named_scope(SCOPE_ROPE):
                packed = (segment_ids, F.segment_positions(segment_ids))
        # what layers hand on to later ones: {"memory": (M,), "kv": (K, V)}
        handed = {}
        for layer in self.layers:
            h, *more = _several(layer(h, *packed,
                                      *handed.get(layer.reads, ())))
            if layer.hands_on:
                handed[layer.hands_on] = more
                _count_handed_on(layer.hands_on, more)
        with jax.named_scope(SCOPE_NORM):
            h = self.norm(h)
        return (h, table) if self._cfg.tie_embeddings else h


class LlamaForCausalLM(HybridBlock):
    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        with self.name_scope():
            self.model = LlamaModel(cfg, prefix="model_")
            # tie_embeddings: the logits are h E^T with the embedding E,
            # and the net has no head of its own
            self.lm_head = None if cfg.tie_embeddings else nn.Dense(
                cfg.vocab_size, use_bias=False, flatten=False,
                in_units=cfg.hidden_size, prefix="lm_head_")

    def hybrid_forward(self, F, input_ids, segment_ids=None):
        import jax

        h, *table = _several(self.model(input_ids, segment_ids))
        with jax.named_scope(SCOPE_HEAD):
            if self._cfg.block_diffusion:
                # rows are [xt ; x0]: logits over the noised half only
                h = F.slice_axis(h, axis=1, begin=0, end=h.shape[1] // 2)
            if self.lm_head is None:
                return F.FullyConnected(
                    h, table[0], no_bias=True,
                    num_hidden=self._cfg.vocab_size, flatten=False)
            return self.lm_head(h)

    @property
    def config(self):
        return self._cfg

    # -- incremental (KV-cached) decode -----------------------------------
    def init_decode_cache(self, batch, max_len=None):
        """Dense per-layer KV cache for :meth:`decode_step`.

        Returns ``{"k", "v"}`` of shape (num_layers, batch, num_kv_heads,
        max_len, head_dim) in the parameter dtype, plus ``"len"`` (tokens
        cached so far; uniform across the batch for this dense API — the
        serving engine's paged pool tracks per-row positions instead)."""
        import jax.numpy as jnp

        cfg = self._cfg
        max_len = max_len or cfg.max_seq_len
        dt = self.model.embed_tokens.weight.data().dtype
        shape = (cfg.num_layers, batch, cfg.num_kv_heads, max_len,
                 cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype=dt),
                "v": jnp.zeros(shape, dtype=dt), "len": 0}

    def prefill(self, ids, cache):
        """Run the prompt through the full-context forward, seed ``cache``
        with every layer's roped k/v, and return the logits (B, L, V) —
        the same values ``self(ids)`` produces."""
        from ....ndarray.ndarray import NDArray

        ids_v = ids._get() if isinstance(ids, NDArray) else \
            _np.asarray(ids)
        logits, ks, vs = prefill_apply(serving_params(self), self._cfg,
                                       ids_v)
        L = ids_v.shape[1]
        cache["k"] = cache["k"].at[:, :, :, :L, :].set(
            ks.astype(cache["k"].dtype))
        cache["v"] = cache["v"].at[:, :, :, :L, :].set(
            vs.astype(cache["v"].dtype))
        cache["len"] = L
        from ....context import current_context

        return NDArray._from_jax(logits, current_context())

    def decode_step(self, ids, cache, positions=None):
        """Single-token forward against the cache: feeds ``ids`` (B,) at
        ``positions`` (default: ``cache["len"]`` for every row), writes
        the new k/v in, advances ``cache["len"]``, and returns logits
        (B, V) that bit-match ``self(full_ids)`` at the same position."""
        import jax.numpy as jnp

        from ....context import current_context
        from ....ndarray.ndarray import NDArray

        ids_v = ids._get() if isinstance(ids, NDArray) else \
            jnp.asarray(_np.asarray(ids))
        b = ids_v.shape[0]
        if positions is None:
            pos = jnp.full((b,), cache["len"], dtype=jnp.int32)
            advance = True
        else:
            pos = jnp.asarray(positions).astype(jnp.int32)
            advance = False

        def join(i, k_new, v_new):
            bi = jnp.arange(b)
            cache["k"] = cache["k"].at[i, bi, :, pos, :].set(
                k_new[:, :, 0, :].astype(cache["k"].dtype))
            cache["v"] = cache["v"].at[i, bi, :, pos, :].set(
                v_new[:, :, 0, :].astype(cache["v"].dtype))
            return cache["k"][i], cache["v"][i], pos + 1

        logits = decode_apply(serving_params(self), self._cfg, ids_v, pos,
                              join)
        if advance:
            cache["len"] += 1
        return NDArray._from_jax(logits, current_context())

    def pipeline_decompose(self, n_stages, train_mode=True):
        """Split the net for pipeline parallelism: embed (pre) ->
        ``n_stages`` homogeneous trunk stages of ``num_layers/n_stages``
        decoder layers each -> final norm + lm_head (post).

        The heterogeneous ends run OUTSIDE the pp loop (replicated /
        dp-sharded), the uniform trunk streams through
        ``parallel.pipeline_parallel.pipeline_apply`` — consumed by
        ``TrainStep(pipeline=...)``.

        Returns a dict: ``pre_names``/``post_names`` (parameter-name
        groups), ``layer_names`` (per layer, {layer0-name: this-layer
        name}), and pure ``pre_fn(params_sub, rng, ids)``,
        ``layer_fn(layer_params_keyed_like_layer0, rng, h)``,
        ``post_fn(params_sub, rng, h)``.
        """
        from ....parallel.functional import functionalize

        cfg = self._cfg
        L = cfg.num_layers
        if not cfg.layers_alike():
            raise MXNetError(
                "pipeline_decompose streams equal stages of like layers; "
                "this net's layers are of several kinds (attention_types: "
                f"{sorted(set(cfg.attention_types))}, num_dense_layers "
                f"{cfg.num_dense_layers})")
        if L % n_stages:
            raise MXNetError(
                f"num_layers {L} not divisible by pipeline stages "
                f"{n_stages}")
        model = self.model
        embed_apply, embed_p = functionalize(model.embed_tokens,
                                             train_mode=train_mode)
        lay0 = model.layers[0]
        lay_apply, lay0_p = functionalize(lay0, train_mode=train_mode)
        norm_apply, norm_p = functionalize(model.norm,
                                           train_mode=train_mode)
        head_apply, head_p = functionalize(self.lm_head,
                                           train_mode=train_mode)
        # construction-order mapping: identical blocks declare parameters
        # in the same order; positional zip is stable even when child
        # blocks carry auto-generated (globally counted) name prefixes
        lay0_order = list(lay0.collect_params())
        layer_names = []
        for i in range(L):
            blk_order = list(model.layers[i].collect_params())
            layer_names.append(dict(zip(lay0_order, blk_order,
                                        strict=True)))

        def pre_fn(psub, rng, ids):
            return embed_apply(psub, rng, ids)

        def layer_fn(pl, rng, h):
            return lay_apply(pl, rng, h)

        def post_fn(psub, rng, h):
            h = norm_apply({k: psub[k] for k in norm_p}, rng, h)
            return head_apply({k: psub[k] for k in head_p}, rng, h)

        return {
            "pre_names": list(embed_p),
            "post_names": list(norm_p) + list(head_p),
            "layer_names": layer_names,
            "layer0_names": list(lay0_p),
            "pre_fn": pre_fn,
            "layer_fn": layer_fn,
            "post_fn": post_fn,
        }


# ==========================================================================
# Incremental (KV-cached) decode — the serving-path forward (ISSUE 8).
#
# ``prefill_apply``/``decode_apply`` are *pure* functions over a
# structural-name parameter tree, written to mirror ``hybrid_forward``
# op-for-op (same registry-op bodies, same reshape/transpose order, same
# fp32 softmax with the flash-attention NEG_INF mask convention) so the
# single-token decode logits bit-match the full-context forward at every
# position.  ``mxnet_tpu.serving`` jit-compiles them against bucketed
# signatures (paged KV cache); the gluon-level ``LlamaForCausalLM.prefill``
# / ``decode_step`` run them eagerly against a dense cache for tests and
# small-scale use.
# ==========================================================================
def serving_params(net):
    """Structural-name parameter tree for the pure serving forwards.

    Keys are ``_collect_params_with_prefix`` block-path names
    (``model.layers.0.self_attn.q_proj.weight``) — stable across global
    auto-name prefixes, so an exported manifest binds to any instance of
    the same architecture.  Values are the live jax arrays (no copy)."""
    from collections import OrderedDict

    return OrderedDict(
        (name, p.data()._get())
        for name, p in sorted(net._collect_params_with_prefix().items()))


def _jnp():
    import jax.numpy as jnp

    return jnp


def _dense_nb(x, weight):
    """``F.FullyConnected(flatten=False, no_bias=True)`` body (ops/nn.py):
    weight layout (units, in_units)."""
    return _jnp().matmul(x, weight.T)


def _decode_attention(q, k, v, n_valid, sm_scale):
    """Single-query attention over a (padded) key context.

    Mirrors ``ops.flash_attention._mha_with_lse`` bit-for-bit for one
    query row: GQA repeat, fp32 scores, NEG_INF mask (``exp`` of it is
    exactly 0.0, so padded keys add exact zeros to the same softmax sum
    the full-context forward computes), max-shift softmax, value matmul
    in the value dtype.  ``n_valid`` (B,) counts valid keys per row —
    key j is visible iff ``j < n_valid`` ≡ the causal row of the
    full-context mask at position ``n_valid - 1``."""
    jnp = _jnp()
    from ....ops.flash_attention import NEG_INF

    hq, hkv = q.shape[1], k.shape[1]
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    # matmul, NOT einsum: at query length 1 XLA:CPU lowers the einsum
    # contraction through a different kernel whose d-axis accumulation
    # order diverges from the full-context einsum's rows (~1e-6); the
    # batched matmul reproduces the full-context rows bit-for-bit
    scores = jnp.matmul(q.astype(jnp.float32),
                        jnp.swapaxes(k.astype(jnp.float32), -1, -2)) \
        * sm_scale
    mask = jnp.arange(k.shape[2])[None, :] < n_valid[:, None]      # (B, S)
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    m = scores.max(axis=-1, keepdims=True)
    e = jnp.exp(scores - m)
    denom = e.sum(axis=-1, keepdims=True)
    p = e / denom
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _proj_qkv(params, cfg, pre, h, pos2):
    """q/k/v projections + rope for one attention block (shared by the
    prefill and decode paths so the cached k/v and the decode-step q are
    computed by literally the same code)."""
    from ....ops.attention_ops import rope as _rope

    jnp = _jnp()
    b, l = h.shape[0], h.shape[1]
    hd = cfg.head_dim
    q = _dense_nb(h, params[pre + "self_attn.q_proj.weight"]) \
        .reshape(b, l, cfg.num_heads, hd).transpose(0, 2, 1, 3)
    k = _dense_nb(h, params[pre + "self_attn.k_proj.weight"]) \
        .reshape(b, l, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
    v = _dense_nb(h, params[pre + "self_attn.v_proj.weight"]) \
        .reshape(b, l, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
    q = _rope(q, positions=pos2, base=cfg.rope_base)
    k = _rope(k, positions=pos2, base=cfg.rope_base)
    return q, k, v


def _mlp_block(params, cfg, pre, h):
    from jax import nn as _jnn

    g = _dense_nb(h, params[pre + "mlp.gate_proj.weight"])
    u = _dense_nb(h, params[pre + "mlp.up_proj.weight"])
    return _dense_nb(_jnn.silu(g) * u, params[pre + "mlp.down_proj.weight"])


def _embed(params, cfg, ids):
    """``F.Embedding`` body (ops/tensor.py): clip + take."""
    jnp = _jnp()
    idx = jnp.clip(ids.astype(jnp.int32), 0, cfg.vocab_size - 1)
    return jnp.take(params["model.embed_tokens.weight"], idx, axis=0)


def _refuse_unserved(cfg):
    """The serving forwards know the plain decoder alone: every layer full
    causal attention with RoPE and a dense FFN."""
    if set(cfg.attention_types) & set(MIXERS):
        raise MXNetError(
            "incremental decode does not support 'kda' or 'mla' layers yet: "
            "a recurrent state with a convolution's tail, and a latent "
            "cache, are not written")
    if set(cfg.attention_types) & {"ssm", "gmu", "cross"}:
        raise MXNetError(
            "incremental decode does not support 'ssm', 'gmu' or 'cross' "
            "layers yet: a scan state with a convolution's tail beside one "
            "layer's cache that every cross layer reads is not written")
    if cfg.differential or cfg.norm != "rms" or cfg.attention_bias \
            or cfg.tie_embeddings:
        raise MXNetError(
            "incremental decode does not support differential attention, "
            "LayerNorm, projection biases or tied embeddings yet")
    if cfg.num_experts > 0:
        raise MXNetError("incremental decode does not support MoE FFNs yet")
    if "window" in cfg.attention_types \
            or "full" not in cfg.rope_attention_types \
            or cfg.attention_gate or cfg.post_norms or cfg.embed_scale != 1.0:
        raise MXNetError(
            "incremental decode does not support window layers, layers "
            "without RoPE, the attention gate, post norms or an embedding "
            "scale yet")
    if cfg.rope_parameters:
        raise MXNetError(
            "incremental decode does not support rope_parameters by "
            "attention kind or YaRN yet: it turns every layer by rope_base")


def prefill_apply(params, cfg, ids):
    """Full-context forward that also returns every layer's roped k/v.

    ``ids`` (B, L) int32.  Returns ``(logits (B, L, V), k (num_layers, B,
    num_kv_heads, L, head_dim), v (same))`` — the logits are the same
    computation as ``LlamaForCausalLM.__call__`` (so right-padding a
    prompt never changes the logits at real positions: causal attention
    means position i only sees j <= i), and the k/v stacks seed a decode
    cache."""
    _refuse_unserved(cfg)
    jnp = _jnp()
    from ....ops.attention_ops import rms_norm as _rms
    from ....ops.flash_attention import flash_attention as _fa

    x = _embed(params, cfg, ids)
    b, l = x.shape[0], x.shape[1]
    hd = cfg.head_dim
    ks, vs = [], []
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        h = _rms(x, params[pre + "input_layernorm.weight"], eps=cfg.rms_eps)
        q, k, v = _proj_qkv(params, cfg, pre, h, None)
        ks.append(k)
        vs.append(v)
        o = _fa(q, k, v, causal=True, sm_scale=1.0 / math.sqrt(hd))
        o = o.transpose(0, 2, 1, 3).reshape(b, l, cfg.num_heads * hd)
        x = x + _dense_nb(o, params[pre + "self_attn.o_proj.weight"])
        h2 = _rms(x, params[pre + "post_attention_layernorm.weight"],
                  eps=cfg.rms_eps)
        x = x + _mlp_block(params, cfg, pre, h2)
    x = _rms(x, params["model.norm.weight"], eps=cfg.rms_eps)
    logits = _dense_nb(x, params["lm_head.weight"])
    return logits, jnp.stack(ks), jnp.stack(vs)


def decode_apply(params, cfg, ids, positions, kv_join):
    """One single-token decode step, pure.

    ``ids`` (B,) int32 — the tokens to feed; ``positions`` (B,) int32 —
    each row's sequence position.  ``kv_join(layer, k_new, v_new) ->
    (K, V, n_valid)`` owns the cache: it must merge the new roped
    k/v (B, num_kv_heads, 1, head_dim) into layer ``layer``'s context and
    return the full (padded) key/value arrays plus the per-row valid-key
    count (``positions + 1``).  Dense caches (``decode_step``) and the
    serving paged pool both plug in here, so there is exactly one copy of
    the decode math.  Returns logits (B, vocab)."""
    _refuse_unserved(cfg)
    jnp = _jnp()
    from ....ops.attention_ops import rms_norm as _rms

    hd = cfg.head_dim
    ids = jnp.asarray(ids)
    x = _embed(params, cfg, ids)[:, None, :]                      # (B, 1, d)
    b = x.shape[0]
    pos = jnp.asarray(positions).astype(jnp.int32)                # (B,)
    pos2 = pos[:, None]                                           # rope (B,1)
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        h = _rms(x, params[pre + "input_layernorm.weight"], eps=cfg.rms_eps)
        q, k, v = _proj_qkv(params, cfg, pre, h, pos2)
        K, V, n_valid = kv_join(i, k, v)
        o = _decode_attention(q, K, V, n_valid, 1.0 / math.sqrt(hd))
        o = o.transpose(0, 2, 1, 3).reshape(b, 1, cfg.num_heads * hd)
        x = x + _dense_nb(o, params[pre + "self_attn.o_proj.weight"])
        h2 = _rms(x, params[pre + "post_attention_layernorm.weight"],
                  eps=cfg.rms_eps)
        x = x + _mlp_block(params, cfg, pre, h2)
    x = _rms(x, params["model.norm.weight"], eps=cfg.rms_eps)
    return _dense_nb(x, params["lm_head.weight"])[:, 0, :]        # (B, V)


def llama3_8b(**overrides):
    """The BASELINE config-#5 architecture (Llama-3-8B dims)."""
    return LlamaForCausalLM(LlamaConfig(**overrides))


def llama_tiny(**overrides):
    """Test/bench-scale Llama (same architecture, small dims)."""
    kw = dict(vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
              num_kv_heads=2, intermediate_size=256, max_seq_len=256)
    kw.update(overrides)
    return LlamaForCausalLM(LlamaConfig(**kw))
