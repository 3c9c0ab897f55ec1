"""The five decoder cells' configurations at a small size, through
``TrainStep`` against the configuration's plain reference: the run-and-compare
sequence that ``test_block_diffusion_moe.py``, ``test_window_shared_moe.py``,
``test_packed_yarn_decoder.py``, ``test_kda_mla_decoder.py`` and
``test_ssm_diff_decoder.py`` share, and a
table, a row a configuration.  A new decoder's whole-step parity test is one
more row here and a call of ``matches`` and ``left_out`` in its file
(ROADMAP.md, D8): the reference of an unchanged configuration is followed once
a process, and a left-out case builds the row's ``few`` layers, not the
pattern."""
import functools
import json

import numpy as np

from mxnet_tpu import telemetry

SEED = 5

# the packed cell's RoPE by kind, small: YaRN at base 100 over an original
# context of 64, so that its ramp (dimensions 2..6) lies inside the head's 8
# rotated pairs; the window layers at another base
YARN = {"rope_type": "yarn", "rope_theta": 100, "factor": 4,
        "original_max_position_embeddings": 64, "beta_fast": 2,
        "beta_slow": 0.5, "attention_factor": 1.1386294361119891}

# ``small``: what is laid over the benchmark's ``config.json`` (``changed``)
# for a net of the same shape of layer at a small size.  ``spec``: the cell's
# batch.  ``few``: laid over that again, the fewest layers that hold every
# part a left-out case of the configuration leaves out.
ROWS = {
    # top-2 of 8 experts with 4 held (the second share), block length 4,
    # L = 32, GQA 4 over 2, q/k norm, two layers
    "sdar_30b_a3b": {
        "small": dict(
            vocab_size=96, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            moe_intermediate_size=32, num_experts=4, router_width=8,
            num_experts_per_tok=2, experts_first=4,
            assumed={"mask_token_id": 95}),
        "spec": {"batch": 2, "seq": 32}},
    # five layers (window, window, window, full, window; the first dense), a
    # window of 8 over L = 32, GQA 4 over 2, top-2 of 8 routed experts with 2
    # held (the first of 4 shares, of which the bias favours 2), a shared
    # expert, the attention gate and the norms after
    "trinity_mini": {
        "small": dict(
            vocab_size=96, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=96,
            moe_intermediate_size=32, num_experts=2, router_width=8,
            num_experts_per_tok=2, experts_first=0, sliding_window=8,
            assumed={"expert_bias": {"value": 1.0, "shares": [0, 1]}}),
        "spec": {"batch": 2, "seq": 32},
        # one window layer, sparse
        "few": dict(num_hidden_layers=1, num_dense_layers=0)},
    # four layers (window, window, window, full), a window of 8 over L = 32,
    # GQA 4 over 2, top-4 of 8 softmax-routed experts with 2 held (the first
    # of 4 shares), RoPE by kind
    "mellum2_12b_a2p5b": {
        "small": dict(
            vocab_size=96, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=96,
            moe_intermediate_size=32, num_experts=2, router_width=8,
            num_experts_per_tok=4, experts_first=0, sliding_window=8,
            rope_parameters={
                "full_attention": YARN,
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": 10000}}),
        "spec": {"batch": 2, "seq": 32, "documents": [13, 9, 5, 3, 2]},
        # a window layer and a full one
        "few": dict(num_hidden_layers=2,
                    layer_types=["sliding_attention", "full_attention"])},
    # seven layers (KDA x5, MLA at index 5, KDA; the first dense), 2 of 4
    # heads held, top-4 of 32 routed experts in 4 groups of which 2 stay, 4
    # held (the first of 8 shares; independent columns and no bias, as the
    # cell's), a shared expert.  The tests that build it cut the delta rule's
    # chunks to 16 rows (``chunks_of_16``)
    "ling3_flash_vl": {
        "small": dict(
            vocab_size=96, hidden_size=64, num_attention_heads=2,
            heads_first=0, head_dim=16, intermediate_size=96,
            moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
            num_experts=4, router_width=32, num_experts_per_tok=4, n_group=4,
            topk_group=2, experts_first=0, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            published={"num_attention_heads": 4}),
        "spec": {"batch": 2, "seq": 64},
        # a delta layer and a latent one, both sparse
        "few": dict(num_hidden_layers=2, layer_group_size=2,
                    first_k_dense_replace=0)},
    # the five published layers 15-19 (window, state-space, full, gated
    # memory, cross), a window of 8 over L = 32, differential attention of 4
    # query heads over 2 key-value heads of 16 (two query pairs on one
    # key-value pair), 128 channels of 4 states through a rank of 4.  The
    # tests that build it cut the scan's chunks to 16 rows
    "phi4_mini_flash": {
        "small": dict(
            vocab_size=96, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=96, sliding_window=8,
            assumed={"state_space": {"d_state": 4, "d_conv": 4, "expand": 2,
                                     "dt_rank": 4}}),
        "spec": {"batch": 2, "seq": 32},
        # every kind once is the whole of it: each reads another
        "few": {}},
}


def changed(cfg, *changes):
    """``cfg`` with each of ``changes`` laid over it; a dict laid over a dict
    joins it (``assumed``, ``published``, ``rope_parameters``)."""
    out = dict(cfg)
    for change in changes:
        for key, value in change.items():
            both = isinstance(value, dict) and isinstance(out.get(key), dict)
            out[key] = dict(out[key], **value) if both else value
    return out


def small(name, few=False, **changes):
    """``(cfg, build, reference, driver)``: the benchmark's configuration
    ``name`` at its row's small size (of its ``few`` layers), ``changes``
    over that, with the configuration's own files and the cells' driver."""
    from chipbench.harness.cell import ROOT, _module

    row = ROWS[name]
    cfg = changed(published(name)[0], row["small"],
                  row["few"] if few else {}, changes)
    return (cfg, _module(ROOT, "configs", name, "build"),
            _module(ROOT, "configs", name, "reference"),
            _module(ROOT, "drivers", "fused_step"))


def spec(name, amp=None):
    return dict(ROWS[name]["spec"], optimizer="adam", amp_dtype=amp,
                optimizer_params={"learning_rate": 1e-6})


@functools.lru_cache(maxsize=None)
def pool(name):
    """The host batches every case of ``name`` steps through (they depend on
    the vocabulary and the spec alone)."""
    from chipbench.harness import loop

    cfg, build, _, _ = small(name)
    return loop.make_pool(build, cfg, spec(name), SEED)


def program(name, cfg, weights, steps, amp=None, step=None):
    """``(got, runner)``: the fused step under ``cfg`` from ``weights``
    through its first ``steps`` steps over the pool, as the benchmark's set-up
    drives it (``step(runner, batch, span)`` in the place of the driver's own
    where given); ``got`` is what ``check.compare`` takes.  The counters start
    from nothing (``telemetry.reset`` zeroes a label and keeps it, so the
    attention forward's calls by mask would list earlier tests' masks: that
    family goes and the next call registers it anew)."""
    from chipbench.harness import loop

    _, build, _, driver = small(name)
    telemetry._FAMILIES.pop("mxnet_flash_attention_fwd_calls_total", None)
    telemetry.reset()
    runner = type("Runner", (driver.Runner,), {"step": step}) if step \
        else driver.Runner
    made = runner(spec(name, amp), cfg, build, weights)
    feed = loop.open_feed(pool(name))
    try:
        return loop.first_steps(made, feed, steps), made
    finally:
        feed.close()


_FOLLOWED = {}


def followed(name, cfg, weights=None):
    """The float32 reference's first two steps under ``cfg``.  From the
    seed's weights (``weights`` None) it is worked out once a process and
    configuration, whichever case asks first; a case of one step reads the
    first loss and the first gradient of it."""
    from chipbench.harness import check

    _, _, reference, _ = small(name)

    def follow(weights):
        return check.follow(reference, cfg, "float32", weights,
                            pool(name)[:2], spec(name))

    if weights is not None:
        return follow(weights)
    key = name, json.dumps(cfg, sort_keys=True)
    if key not in _FOLLOWED:
        _FOLLOWED[key] = follow(reference.init_params(cfg, SEED))
    return _FOLLOWED[key]


def matches(name, amp, tolerance):
    """The parity case: the small net's two first steps against the
    reference's, every statistic of ``check.compare`` within ``tolerance``,
    a gradient for every leaf of the reference and none of them zero.
    ``(runner, metrics)`` for what the configuration's own test counts."""
    from chipbench.harness import check

    cfg, _, reference, _ = small(name)
    got, runner = program(name, cfg, reference.init_params(cfg, SEED), 2, amp)
    metrics = telemetry.snapshot()["metrics"]
    assert set(got["first_gradient"]) == set(reference.param_shapes(cfg))
    for stat, (value, where) in check.compare(got, followed(name, cfg)).items():
        assert value <= tolerance[stat], (stat, value, where)
    for leaf, g in got["first_gradient"].items():
        assert np.abs(g).max() > 0, leaf
    return runner, metrics


def zeroed(suffix):
    """Weights with every leaf named ``*suffix`` at zero."""
    return lambda weights: {k: 0.0 * v if k.endswith(suffix) else v
                            for k, v in weights.items()}


def left_out(name, cfg=(), broken=(), mistaken=None, weights=None,
             step=None):
    """``check.compare``'s statistics of a program with one part left out
    against the sound reference, float32, one step, at the row's ``few``
    layers.  ``cfg`` changes the configuration of both sides, ``broken`` the
    program's alone; ``weights`` maps the seed's weights for both sides,
    ``mistaken`` the program's alone; ``step`` is ``program``'s."""
    from chipbench.harness import check

    sound, _, reference, _ = small(name, few=True, **dict(cfg))
    start = reference.init_params(sound, SEED)
    if weights:
        start = weights(start)
    got, _ = program(name, changed(sound, dict(broken)),
                     mistaken(start) if mistaken else start, 1, step=step)
    return check.compare(got, followed(name, sound, weights and start))


def published(name):
    """``(cfg, counts)``: the benchmark's own ``config.json`` of ``name`` as
    it stands, and the configuration's ``counts.py``."""
    from chipbench.harness.cell import ROOT, _json, _module

    return (_json(ROOT, "chipbench", "configs", name, "config.json"),
            _module(ROOT, "configs", name, "counts"))


def shares_add_up(name, experts, held, top_k, atol, **layer):
    """The model-configs guide's test of the cut: ``experts`` routed experts
    in shares of ``held``, ``top_k`` a token, random routers (and a random
    selection bias and a shared expert where ``layer`` asks for them).  The
    routed part of each share's ``LlamaMoEMLP`` (its output less the shared
    expert, which every share computes alike) summed over the shares, plus
    the shared expert once, is the uncut reference's expert block before any
    norm after it, to ``atol`` (float32 sums of a few terms of size 0.1 in
    another order).  ``layer``: the ``LlamaConfig`` keywords of the
    configuration's router."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.language import llama

    cfg, _, reference, _ = small(
        name, num_experts=experts, router_width=experts,
        num_experts_per_tok=top_k,
        **dict(zip(("n_group", "topk_group"), layer.get("moe_groups", ()))))
    shared = "moe_shared_intermediate_size" in layer
    rs = np.random.RandomState(2)
    shapes = {"router_weight": (64, experts),
              "gate_proj_weight": (experts, 64, 32),
              "up_proj_weight": (experts, 64, 32),
              "down_proj_weight": (experts, 32, 64)}
    if shared:
        shapes.update(shared_gate_proj_weight=(32, 64),
                      shared_up_proj_weight=(32, 64),
                      shared_down_proj_weight=(64, 32))
    mine = {k: 0.3 * rs.randn(*s).astype("f") for k, s in shapes.items()}
    if layer.get("moe_select_bias"):
        mine["select_bias"] = (rs.rand(experts)
                               * (rs.rand(experts) < 0.5)).astype("f")
    h = rs.randn(2, 24, 64).astype("f")
    # the reference's names: ``moe.router``, ``moe.gate``, ``shared.down``
    p = {("" if k.startswith("shared") else "moe.")
         + k.replace("_proj", "").replace("_weight", "").replace("_", "."):
         jnp.asarray(v) for k, v in mine.items()}
    bias = [p.pop("moe.select.bias")] if "select_bias" in mine else []
    with jax.default_matmul_precision("highest"):
        whole = reference.routed_experts(cfg, lambda x: x, h.reshape(-1, 64),
                                         p, *bias, 0, experts)
        if shared:
            whole += reference.shared_expert(lambda x: x, h.reshape(-1, 64), p)
    total, once = 0.0, 0.0
    for first in range(0, experts, held):
        block = llama.LlamaMoEMLP(llama.LlamaConfig(
            hidden_size=64, num_heads=4, num_kv_heads=2, num_experts=experts,
            moe_capacity_factor=None, moe_top_k=top_k, moe_renormalize=True,
            moe_experts_held=(first, held), moe_intermediate_size=32,
            **layer))
        block.initialize()
        for key, param in block.collect_params().items():
            value = mine[key[len(block.prefix):]]
            param.set_data(nd.array(
                value[first:first + held] if value.ndim == 3 else value))
        if shared:
            once = block.shared(nd.array(h))._get()
        total = total + block(nd.array(h))._get() - once
    np.testing.assert_allclose((total + once).reshape(-1, 64), whole,
                               atol=atol)
