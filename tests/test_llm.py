"""LLM path tests: flash attention, RoPE/RMSNorm ops, Llama/BERT models,
ring attention (SURVEY.md §8 phase 9 / BASELINE configs #2 and #5)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.ops.flash_attention import flash_attention, _mha_reference
from mxnet_tpu.gluon.model_zoo.language import (llama_tiny, bert_tiny,
                                                BertForPretraining, BertConfig)


def _qkv(b=2, h=4, l=64, d=16, hkv=None, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, h, l, d).astype("f"))
    k = jnp.asarray(rng.randn(b, hkv or h, l, d).astype("f"))
    v = jnp.asarray(rng.randn(b, hkv or h, l, d).astype("f"))
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    q, k, v = _qkv()
    o1 = flash_attention(q, k, v, causal=causal)
    o2 = _mha_reference(q, k, v, causal, 1 / np.sqrt(16))
    assert float(jnp.abs(o1 - o2).max()) < 1e-4


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads(causal):
    q, k, v = _qkv(l=32)
    g1 = jax.grad(lambda q, k, v: flash_attention(q, k, v, causal=causal).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: _mha_reference(q, k, v, causal,
                                                 1 / np.sqrt(16)).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        # both are f32 implementations of the same math; see flash_attention
        # tests in-tree history: f32 softmax conditioning bounds agreement
        assert float(jnp.abs(a - b).max()) < 2e-2


def test_flash_attention_gqa():
    q, k, v = _qkv(h=4, hkv=2)
    o = flash_attention(q, k, v, causal=True)
    assert o.shape == q.shape
    dk = jax.grad(lambda k: flash_attention(q, k, v, causal=True).sum())(k)
    assert dk.shape == k.shape


def test_rope_rotation_properties():
    x = nd.array(np.random.RandomState(0).randn(1, 2, 8, 16).astype("f"))
    y = nd.rope(x)
    # norm-preserving per pair
    xn = np.linalg.norm(x.asnumpy(), axis=-1)
    yn = np.linalg.norm(y.asnumpy(), axis=-1)
    assert np.allclose(xn, yn, atol=1e-4)
    # position 0 is identity
    assert np.allclose(y.asnumpy()[:, :, 0], x.asnumpy()[:, :, 0], atol=1e-5)


def test_rms_norm():
    x = nd.array(np.random.RandomState(0).randn(4, 8).astype("f") * 3)
    g = nd.ones((8,))
    y = nd.rms_norm(x, g).asnumpy()
    expected = x.asnumpy() / np.sqrt(
        (x.asnumpy() ** 2).mean(-1, keepdims=True) + 1e-6)
    assert np.allclose(y, expected, atol=1e-5)


def test_interleaved_matmul_selfatt():
    L, B, H, d = 6, 2, 2, 4
    rng = np.random.RandomState(0)
    qkv = nd.array(rng.randn(L, B, 3 * H * d).astype("f"))
    att = nd.interleaved_matmul_selfatt_qk(qkv, heads=H)
    assert att.shape == (B * H, L, L)
    probs = nd.softmax(att, axis=-1)
    out = nd.interleaved_matmul_selfatt_valatt(qkv, probs, heads=H)
    assert out.shape == (L, B, H * d)


def test_llama_tiny_trains():
    net = llama_tiny()
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(0)
    ids = mx.nd.array(rng.randint(0, 512, (2, 32)).astype("i"))
    labels = mx.nd.array(rng.randint(0, 512, (2, 32)).astype("f"))
    losses = []
    for _ in range(5):
        with autograd.record():
            out = net(ids)
            loss = loss_fn(out.reshape((-1, 512)), labels.reshape((-1,)))
        loss.backward()
        trainer.step(2)
        losses.append(float(loss.mean().asscalar()))
    assert losses[-1] < losses[0], losses


def test_llama_hybridize_matches_eager():
    net = llama_tiny()
    net.initialize()
    ids = mx.nd.array(np.random.RandomState(1).randint(0, 512, (2, 16)).astype("i"))
    y0 = net(ids)
    net.hybridize()
    y1 = net(ids)
    assert np.allclose(y0.asnumpy(), y1.asnumpy(), atol=1e-4)


def test_bert_forward_and_pretrain_heads():
    net = bert_tiny()
    net.initialize()
    ids = mx.nd.array(np.random.RandomState(0).randint(0, 256, (2, 24)).astype("i"))
    seq, pooled = net(ids)
    assert seq.shape == (2, 24, 64)
    assert pooled.shape == (2, 64)
    cfg = BertConfig(vocab_size=256, hidden_size=64, num_layers=2,
                     num_heads=2, intermediate_size=128, max_position=64)
    bp = BertForPretraining(cfg)
    bp.initialize()
    mlm, nsp = bp(ids)
    assert mlm.shape == (2, 24, 256)
    assert nsp.shape == (2, 2)


def test_bert_trains():
    net = bert_tiny()
    net.initialize()
    head = gluon.nn.Dense(2, flatten=False)
    head.initialize()
    params = dict(net.collect_params())
    params.update(head.collect_params())
    trainer = gluon.Trainer(params, "adam", {"learning_rate": 1e-3})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(0)
    ids = mx.nd.array(rng.randint(0, 256, (4, 16)).astype("i"))
    labels = mx.nd.array(rng.randint(0, 2, (4,)).astype("f"))
    losses = []
    for _ in range(5):
        with autograd.record():
            _, pooled = net(ids)
            loss = loss_fn(head(pooled), labels)
        loss.backward()
        trainer.step(4)
        losses.append(float(loss.mean().asscalar()))
    assert losses[-1] < losses[0], losses


# -- ring attention / context parallelism ----------------------------------
def _sp_mesh():
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return Mesh(np.array(devs[:8]), ("sp",))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    from mxnet_tpu.parallel import context_parallel_attention

    mesh = _sp_mesh()
    q, k, v = _qkv(l=64)
    o1 = context_parallel_attention(q, k, v, mesh, causal=causal)
    o2 = _mha_reference(q, k, v, causal, 1 / np.sqrt(16))
    assert float(jnp.abs(o1 - o2).max()) < 1e-5


def test_ring_attention_grad():
    from mxnet_tpu.parallel import context_parallel_attention

    mesh = _sp_mesh()
    q, k, v = _qkv(l=32)
    # (jitted: an eager gradient of the shard_map runs the ring op by op)
    g1 = jax.jit(jax.grad(lambda q: context_parallel_attention(
        q, k, v, mesh, causal=True).sum()))(q)
    g2 = jax.grad(lambda q: _mha_reference(q, k, v, True,
                                           1 / np.sqrt(16)).sum())(q)
    assert float(jnp.abs(g1 - g2).max()) < 1e-5


def test_flash_attention_cross_length_causal_grad():
    # regression: the causal diagonal offset (lk != lq, decode-style) must
    # match between forward and backward
    q, _, _ = _qkv(l=4)
    _, k, v = _qkv(l=8, seed=1)
    o1 = flash_attention(q, k, v, causal=True)
    o2 = _mha_reference(q, k, v, True, 1 / np.sqrt(16))
    assert float(jnp.abs(o1 - o2).max()) < 1e-5
    g1 = jax.grad(lambda q: flash_attention(q, k, v, causal=True).sum())(q)
    g2 = jax.grad(lambda q: _mha_reference(q, k, v, True,
                                           1 / np.sqrt(16)).sum())(q)
    assert float(jnp.abs(g1 - g2).max()) < 1e-4


def test_rope_batched_positions():
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(2, 4, 8, 16).astype("f"))
    pos = nd.array(np.tile(np.arange(8), (2, 1)).astype("f"))
    y = nd.rope(x, pos)
    assert np.allclose(y.asnumpy(), nd.rope(x).asnumpy(), atol=1e-5)


def test_llama_remat_matches_no_remat():
    """remat=True recomputes activations but must be numerically identical
    (same outputs AND gradients) under the fused train step."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel.data_parallel import TrainStep

    cfg = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
               num_kv_heads=2, intermediate_size=64, max_seq_len=16)
    ids = np.random.RandomState(0).randint(0, 64, (2, 8)).astype("int32")
    labels = np.random.RandomState(1).randint(0, 64, (2, 8)).astype("int32")

    def loss_fn(logits, y):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, y[..., None], axis=-1)

    from mxnet_tpu.gluon.model_zoo.language import llama

    results = {}
    for remat in (False, True):
        net = llama.LlamaForCausalLM(llama.LlamaConfig(remat=remat, **cfg))
        net.initialize()
        net(mx.nd.zeros((1, 8), dtype="int32"))
        if remat:
            # same weights as the no-remat run (block prefixes use a
            # global counter, so match by suffix past the first segment)
            src = {k.split("_", 1)[1]: v
                   for k, v in results[False]["params"].items()}
            for name, p in net.collect_params().items():
                p.set_data(mx.nd.array(src[name.split("_", 1)[1]]))
        step = TrainStep(net, loss_fn, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1},
                         train_mode=True)
        if not remat:
            results[False] = {"params": {
                k: p.data().asnumpy().copy()
                for k, p in net.collect_params().items()}}
        loss = float(np.asarray(step(ids, labels)))
        results[remat] = dict(results.get(remat, {}), loss=loss,
                              after={k.split("_", 1)[1]: np.asarray(v)
                                     for k, v in step.train_params.items()})
    assert np.allclose(results[False]["loss"], results[True]["loss"],
                       rtol=1e-5), (results[False]["loss"],
                                    results[True]["loss"])
    for k in results[False]["after"]:
        assert np.allclose(results[False]["after"][k],
                           results[True]["after"][k], atol=1e-5), k


def test_llama_moe_single_expert_matches_dense():
    """num_experts=1: the switch router's softmax gate is exactly 1, so
    the MoE FFN equals the dense SwiGLU MLP with the same weights."""
    cfg = dict(vocab_size=32, hidden_size=16, num_layers=1, num_heads=2,
               num_kv_heads=2, intermediate_size=24, max_seq_len=8)
    from mxnet_tpu.gluon.model_zoo.language import llama

    dense = llama.LlamaForCausalLM(llama.LlamaConfig(**cfg))
    moe = llama.LlamaForCausalLM(llama.LlamaConfig(num_experts=1,
                                                   moe_capacity_factor=64.0,
                                                   **cfg))
    dense.initialize()
    moe.initialize()
    ids = mx.nd.array(np.random.RandomState(0).randint(
        0, 32, (2, 8)).astype("int32"))
    dense(ids)
    moe(ids)
    dp = {k.split("_", 1)[1]: v.data().asnumpy()
          for k, v in dense.collect_params().items()}
    for name, p in moe.collect_params().items():
        suffix = name.split("_", 1)[1]
        if "router" in suffix:
            continue
        if "mlp" in suffix:
            # dense mlp weight (out, in) -> moe expert weight (1, in, out)
            base = suffix.replace("_weight", "")
            dname = [k for k in dp if base in k][0]
            p.set_data(mx.nd.array(dp[dname].T[None]))
        elif suffix in dp:
            p.set_data(mx.nd.array(dp[suffix]))
    y_dense = dense(ids).asnumpy()
    y_moe = moe(ids).asnumpy()
    assert np.allclose(y_dense, y_moe, atol=1e-4), \
        np.abs(y_dense - y_moe).max()


def test_llama_moe_trains_under_trainstep():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.gluon.model_zoo.language import llama
    from mxnet_tpu.parallel.data_parallel import TrainStep

    net = llama.LlamaForCausalLM(llama.LlamaConfig(
        vocab_size=48, hidden_size=16, num_layers=2, num_heads=2,
        num_kv_heads=2, intermediate_size=24, max_seq_len=8,
        num_experts=4, moe_capacity_factor=2.0))
    net.initialize()
    net(mx.nd.zeros((1, 8), dtype="int32"))

    def loss_fn(logits, y):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, y[..., None], axis=-1)

    step = TrainStep(net, loss_fn, optimizer="adam",
                     optimizer_params={"learning_rate": 3e-3},
                     train_mode=True)
    rs = np.random.RandomState(1)
    ids = rs.randint(0, 48, (4, 8)).astype("int32")
    lab = rs.randint(0, 48, (4, 8)).astype("int32")
    losses = [float(np.asarray(step(ids, lab))) for _ in range(40)]
    assert losses[-1] < 0.8 * losses[0], (losses[0], losses[-1])
    assert all(np.isfinite(l) for l in losses)


def test_llama_moe_aux_loss_reaches_router():
    """The injected balance loss changes the router gradient (review
    finding: aux was silently dropped)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.gluon.model_zoo.language import llama
    from mxnet_tpu.parallel.data_parallel import TrainStep

    cfg = dict(vocab_size=32, hidden_size=16, num_layers=1, num_heads=2,
               num_kv_heads=2, intermediate_size=24, max_seq_len=8,
               num_experts=4, moe_capacity_factor=4.0)
    ids = np.random.RandomState(0).randint(0, 32, (2, 8)).astype("int32")
    lab = np.random.RandomState(1).randint(0, 32, (2, 8)).astype("int32")

    def loss_fn(logits, y):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, y[..., None], axis=-1)

    routers = {}
    base_params = None
    for w in (0.0, 0.5):
        net = llama.LlamaForCausalLM(llama.LlamaConfig(
            moe_aux_loss_weight=w, **cfg))
        net.initialize()
        net(mx.nd.zeros((1, 8), dtype="int32"))
        if base_params is None:
            base_params = {k.split("_", 1)[1]: p.data().asnumpy().copy()
                           for k, p in net.collect_params().items()}
        else:
            for k, p in net.collect_params().items():
                p.set_data(mx.nd.array(base_params[k.split("_", 1)[1]]))
        step = TrainStep(net, loss_fn, optimizer="sgd",
                         optimizer_params={"learning_rate": 1.0},
                         train_mode=True)
        step(ids, lab)
        rname = [k for k in step.train_params if "router" in k][0]
        routers[w] = np.asarray(step.train_params[rname])
    assert not np.allclose(routers[0.0], routers[0.5], atol=1e-7)
    assert np.isfinite(routers[0.5]).all()


def test_llama_moe_exports_through_symbol_path(tmp_path):
    """MoE models trace to Symbol, export, and reload via SymbolBlock
    with identical outputs (closes the r4 caveat: moe_swiglu is now a
    registered op instead of a raw apply_fn seam)."""
    import numpy as np

    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.language import llama

    cfg = llama.LlamaConfig(vocab_size=64, hidden_size=16, num_layers=2,
                            num_heads=2, num_kv_heads=2,
                            intermediate_size=24, max_seq_len=16,
                            num_experts=4)
    net = llama.LlamaForCausalLM(cfg)
    net.initialize()
    net.hybridize()
    ids = mx.nd.array(np.random.RandomState(0).randint(0, 64, (2, 8)),
                      dtype="int32")
    y0 = net(ids).asnumpy()

    path = str(tmp_path / "llama_moe")
    net.export(path, 0, ids)
    re = gluon.SymbolBlock.imports(path + "-symbol.json", ["data"],
                                   path + "-0000.params")
    y1 = re(ids).asnumpy()
    np.testing.assert_allclose(y1, y0, rtol=1e-4, atol=1e-5)


# -- incremental (KV-cached) decode — the serving forward (ISSUE 8) ---------
def _tiny_decode_net(**overrides):
    net = llama_tiny(**overrides)
    net.initialize()
    net(nd.zeros((1, 8), dtype="int32"))  # settle deferred shapes
    return net


def test_llama_incremental_decode_bit_matches_full_context():
    """The KV-cached single-token forward reproduces the full-context
    forward's logits BIT-FOR-BIT at every position (the serving-path
    correctness contract).  Pinned against the canonical eager op math;
    the PR 1 per-op jit cache path computes within 5e-6 of it (per-op
    fusion reassociates a few f32 ops) and is covered separately below."""
    net = _tiny_decode_net()
    ids = np.random.RandomState(0).randint(0, 512, (2, 12)).astype("int32")
    prev = mx.nd.set_eager_jit(False)
    try:
        full = net(nd.array(ids, dtype="int32")).asnumpy()
        cache = net.init_decode_cache(2, max_len=32)
        pre = net.prefill(nd.array(ids[:, :5], dtype="int32"), cache)
        assert np.array_equal(pre.asnumpy(), full[:, :5, :])
        assert cache["len"] == 5
        for t in range(5, 12):
            step = net.decode_step(ids[:, t], cache).asnumpy()
            assert np.array_equal(step, full[:, t]), f"position {t}"
        assert cache["len"] == 12
    finally:
        mx.nd.set_eager_jit(prev)


def test_llama_incremental_decode_close_under_dispatch_jit():
    """With the eager jit cache ON the full-context reference itself
    shifts by ~1e-6 (per-op fusion); the decode path stays within the
    pinned envelope."""
    net = _tiny_decode_net()
    ids = np.random.RandomState(1).randint(0, 512, (1, 10)).astype("int32")
    full = net(nd.array(ids, dtype="int32")).asnumpy()
    cache = net.init_decode_cache(1, max_len=16)
    net.prefill(nd.array(ids[:, :4], dtype="int32"), cache)
    for t in range(4, 10):
        step = net.decode_step(ids[:, t], cache).asnumpy()
        np.testing.assert_allclose(step, full[:, t], rtol=0, atol=5e-6)


def test_llama_incremental_decode_amp_bf16_tolerance():
    """Under AMP (bf16 activations on the full-context path) the decode
    logits stay within the pinned bf16 envelope: both paths round their
    matmul inputs to bf16, but through differently-shaped kernels, so
    agreement is bounded by bf16 resolution (~2^-8 relative), not bits."""
    from mxnet_tpu.contrib import amp

    net = _tiny_decode_net()
    net.cast("bfloat16")
    ids = np.random.RandomState(2).randint(0, 512, (2, 10)).astype("int32")
    amp.init("bfloat16")
    try:
        full = net(nd.array(ids, dtype="int32")).asnumpy().astype("f")
        cache = net.init_decode_cache(2, max_len=16)
        net.prefill(nd.array(ids[:, :4], dtype="int32"), cache)
        scale = np.abs(full).max()
        for t in range(4, 10):
            step = net.decode_step(ids[:, t], cache).asnumpy().astype("f")
            assert np.abs(step - full[:, t]).max() <= 0.05 * scale, \
                f"position {t}"
    finally:
        amp.disable()


def test_llama_decode_per_row_positions_and_gqa():
    """Rows at DIFFERENT positions decode correctly in one batch (the
    continuous-batching case: requests join/leave mid-stream), including
    grouped-query attention head repetition."""
    net = _tiny_decode_net()
    r = np.random.RandomState(3)
    ids_a = r.randint(0, 512, (1, 9)).astype("int32")
    ids_b = r.randint(0, 512, (1, 7)).astype("int32")
    prev = mx.nd.set_eager_jit(False)
    try:
        full_a = net(nd.array(ids_a, dtype="int32")).asnumpy()
        full_b = net(nd.array(ids_b, dtype="int32")).asnumpy()
        # one shared cache, rows at staggered positions
        cache = net.init_decode_cache(2, max_len=16)
        ca = net.init_decode_cache(1, max_len=16)
        cb = net.init_decode_cache(1, max_len=16)
        net.prefill(nd.array(ids_a[:, :6], dtype="int32"), ca)
        net.prefill(nd.array(ids_b[:, :4], dtype="int32"), cb)
        cache["k"] = cache["k"].at[:, 0, :, :, :].set(ca["k"][:, 0])
        cache["k"] = cache["k"].at[:, 1, :, :, :].set(cb["k"][:, 0])
        cache["v"] = cache["v"].at[:, 0, :, :, :].set(ca["v"][:, 0])
        cache["v"] = cache["v"].at[:, 1, :, :, :].set(cb["v"][:, 0])
        import jax.numpy as jnp

        toks = np.array([ids_a[0, 6], ids_b[0, 4]], dtype="int32")
        pos = np.array([6, 4], dtype="int32")
        step = net.decode_step(toks, cache, positions=jnp.asarray(pos))
        step = step.asnumpy()
        assert np.array_equal(step[0], full_a[0, 6])
        assert np.array_equal(step[1], full_b[0, 4])
    finally:
        mx.nd.set_eager_jit(prev)
