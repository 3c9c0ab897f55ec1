"""``LlamaAttention`` on ``F.qk_norm_rope`` (ISSUE 36): off a TPU its outputs
and gradients are those of the chain it replaced, bit for bit (the chain is
stored here as it stood), and the block still traces to Symbol and comes
back from ``export()``."""
import math

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon.model_zoo.language import llama


class ChainAttention(llama.LlamaAttention):
    """``LlamaAttention.hybrid_forward`` as it stood before ``F.qk_norm_rope``
    (the scopes, which are names alone, left out): reshape and transpose of
    each projection, the two norms, ``F.rope`` twice."""

    def hybrid_forward(self, F, x, segment_ids=None, positions=None):
        cfg = self._cfg
        b, l = x.shape[0], x.shape[1]
        hd = cfg.head_dim
        q = self.q_proj(x).reshape(
            (b, l, cfg.num_heads, hd)).transpose((0, 2, 1, 3))
        k = self.k_proj(x).reshape(
            (b, l, cfg.num_kv_heads, hd)).transpose((0, 2, 1, 3))
        v = self.v_proj(x).reshape(
            (b, l, cfg.num_kv_heads, hd)).transpose((0, 2, 1, 3))
        if cfg.qk_norm:
            q, k = self.q_norm(q), self.k_norm(k)
        if cfg.block_diffusion:
            half = F.arange(0, l // 2, dtype="int32")
            pos = F.concat(half, half, dim=0)
            q = F.rope(q, pos, base=cfg.rope_base)
            k = F.rope(k, pos, base=cfg.rope_base)
            o = F.flash_attention(q, k, v, segment_ids,
                                  mask="block_diffusion",
                                  mask_block=cfg.block_diffusion,
                                  sm_scale=1.0 / math.sqrt(hd))
        else:
            if self._kind in cfg.rope_attention_types:
                turn = cfg.rope_kwargs(self._kind)
                q = F.rope(q, positions, **turn)
                k = F.rope(k, positions, **turn)
            if self._kind == "window":
                o = F.flash_attention(q, k, v, segment_ids, mask="window",
                                      window=cfg.attention_window,
                                      sm_scale=1.0 / math.sqrt(hd))
            else:
                o = F.flash_attention(q, k, v, segment_ids, causal=True,
                                      sm_scale=1.0 / math.sqrt(hd))
        o = o.transpose((0, 2, 1, 3)).reshape((b, l, cfg.num_heads * hd))
        if cfg.attention_gate:
            o = o * F.sigmoid(self.gate_proj(x))
        return self.o_proj(o)


YARN = {"full": {"rope_type": "yarn", "factor": 16.0, "rope_theta": 1e4,
                 "original_max_position_embeddings": 64,
                 "attention_factor": 1.25}}
# what a layer is: (configuration, kind, packed into documents?)
LAYERS = {
    "full, q/k norm and RoPE": (dict(qk_norm=True), "full", False),
    "packed: positions a sample, YaRN": (
        dict(qk_norm=True, rope_parameters=YARN), "full", True),
    "window outside rope_attention_types: the norm alone": (
        dict(qk_norm=True, attention_types=("window", "full"),
             attention_window=4, rope_attention_types=("full",),
             attention_gate=True), "window", False),
    "block diffusion": (dict(qk_norm=True, block_diffusion=4), "full", False),
    "no q/k norm: the turn alone": (dict(), "full", True),
    "neither": (dict(rope_attention_types=()), "full", False),
}


def _pair(options, kind):
    cfg = llama.LlamaConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=4, num_kv_heads=2, head_dim=16,
                            intermediate_size=32, max_seq_len=64,
                            rms_eps=1e-6, **options)
    blocks = []
    for cls in (llama.LlamaAttention, ChainAttention):
        mx.random.seed(3)
        block = cls(cfg, kind=kind)
        block.initialize(mx.init.Normal(0.3))
        blocks.append(block)
    new, old = blocks
    for a, b in zip(new.collect_params().values(),
                    old.collect_params().values()):
        assert a.shape == b.shape
        b.set_data(a.data())
    return new, old


def _run(block, x, operands):
    x = nd.array(x)
    x.attach_grad()
    with autograd.record():
        out = block(x, *operands)
        loss = (out * out).sum()
    loss.backward()
    grads = {name.split("_", 1)[1]: p.grad().asnumpy()
             for name, p in block.collect_params().items()}
    return out.asnumpy(), x.grad.asnumpy(), grads


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("hybridize", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("layer", list(LAYERS))
def test_attention_block_is_the_chains_bit_for_bit(layer, hybridize, dtype):
    options, kind, packed = LAYERS[layer]
    new, old = _pair(options, kind)
    rs = np.random.RandomState(0)
    x = rs.randn(2, 16, 32).astype("f")
    operands = ()
    if packed:
        ids = np.sort(rs.randint(0, 3, (2, 16)), axis=1).astype("int32")
        operands = (nd.array(ids, dtype="int32"),
                    nd.segment_positions(nd.array(ids, dtype="int32")))
    if dtype != "float32":
        new.cast(dtype), old.cast(dtype)
        x = x.astype(dtype)
    if hybridize:
        new.hybridize(), old.hybridize()
    got, want = _run(new, x, operands), _run(old, x, operands)
    assert got[2].keys() == want[2].keys() and len(got[2]) >= 4
    pairs = [(got[0], want[0]), (got[1], want[1])] + [
        (got[2][name], want[2][name]) for name in got[2]]
    # on the eager tape the op is one recorded program where the chain was
    # four; with bf16 inputs XLA's CPU backend may keep float32 between two
    # roundings it now fuses (excess precision), so there the gradients
    # agree to a bf16 place.  Under any trace the jaxpr is the chain's own
    exact = hybridize or dtype == "float32"
    for a, b in pairs:
        if exact:
            assert np.array_equal(a, b)
        else:
            np.testing.assert_allclose(a.astype("f"), b.astype("f"),
                                       rtol=2 ** -7, atol=2 ** -7)
    assert np.array_equal(got[0], want[0])      # the output, everywhere


@pytest.mark.parametrize("layer", ["packed: positions a sample, YaRN",
                                   "block diffusion"])
def test_the_model_traces_to_symbol_and_round_trips_export(tmp_path, layer):
    """The new op is a node of the exported graph with its static
    attributes (YaRN's tuple of inverse frequencies among them), and the
    imported graph gives the net's outputs."""
    options, _, packed = LAYERS[layer]
    cfg = llama.LlamaConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=4, num_kv_heads=2, head_dim=16,
                            intermediate_size=32, max_seq_len=64, **options)
    net = llama.LlamaForCausalLM(cfg)
    net.initialize()
    net.hybridize()
    rs = np.random.RandomState(0)
    inputs = [nd.array(rs.randint(0, 64, (2, 16)), dtype="int32")]
    if packed:
        inputs.append(nd.array(np.sort(rs.randint(0, 3, (2, 16)), axis=1),
                               dtype="int32"))
    want = net(*inputs).asnumpy()
    path = str(tmp_path / "decoder")
    net.export(path, 0, *inputs)
    with open(path + "-symbol.json") as f:
        graph = f.read()
    assert graph.count('"_contrib_qk_norm_rope"') == 2 * cfg.num_layers
    assert '"rope"' not in graph and '"rms_norm"' in graph  # hidden norms
    back = gluon.SymbolBlock.imports(
        path + "-symbol.json", ["data0", "data1"] if packed else ["data"],
        path + "-0000.params")
    np.testing.assert_allclose(back(*inputs).asnumpy(), want, rtol=1e-5,
                               atol=1e-6)
