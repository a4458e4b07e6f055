"""Quantized execution: quantize_for_serving + QuantizedLinear.

The deploy-chain contract: a trained (or PTQ'd) model converts to REAL
int8 weights (values + per-output-channel scales, stored as buffers),
serves through every engine surface, round-trips through state_dict
and jit.save, and NEVER re-rounds on a second conversion pass.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.quantization import (
    AbsmaxObserver,
    PTQ,
    PerChannelAbsmaxObserver,
    QuantConfig,
    QuantizedLinear,
    quantize_for_serving,
)


@pytest.fixture(scope="module")
def net():
    paddle.seed(5)
    cfg = LlamaConfig.tiny(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
    )
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _buffers(m):
    return {k: np.asarray(v.value) for k, v in m.named_buffers()}


def test_quantize_for_serving_structure_and_closeness(net):
    qm = quantize_for_serving(net)
    # every llama projection became a QuantizedLinear with int8 buffers
    qlayers = [m for _, m in qm.named_sublayers()
               if isinstance(m, QuantizedLinear)]
    # 2 layers x (q,k,v,o + gate_up + down) + lm_head
    assert len(qlayers) == 2 * 6 + 1
    for ql in qlayers:
        assert ql.weight_q.value.dtype == jnp.int8
        assert ql.weight_scale.value.dtype == jnp.float32
        assert ql.weight_scale.shape[0] == ql.out_features
    # no dense float projection weights remain as parameters
    assert not any("proj" in k for k, _ in qm.named_parameters())
    # logits stay close to the float model (weight-only 8-bit)
    x = Tensor(jnp.asarray(np.random.RandomState(0).randint(
        0, 64, (1, 8)), jnp.int32))
    lf = np.asarray(net(x).numpy(), np.float32)
    lq = np.asarray(qm(x).numpy(), np.float32)
    assert float(np.abs(lf - lq).max()) < 0.05
    # and the original model is untouched (not inplace)
    assert net.lm_head is not None
    assert not isinstance(net.lm_head, QuantizedLinear)


def test_quantize_for_serving_is_idempotent(net):
    """The satellite pin: double-quantize must be a structural no-op —
    a second rounding pass would silently degrade int8 weights."""
    qm = quantize_for_serving(net)
    qm2 = quantize_for_serving(qm)
    b1, b2 = _buffers(qm), _buffers(qm2)
    assert b1.keys() == b2.keys()
    for k in b1:
        np.testing.assert_array_equal(b1[k], b2[k], err_msg=k)
    # in-place double application too
    qm3 = quantize_for_serving(qm, inplace=True)
    assert qm3 is qm
    for k, v in _buffers(qm3).items():
        np.testing.assert_array_equal(v, b1[k], err_msg=k)


def test_quantize_for_serving_from_ptq_uses_calibrated_scales(net):
    """PTQ -> convert -> quantize_for_serving: the ObservedLayer's
    frozen per-channel weight scales are what lands in the
    QuantizedLinear (the calibrated deploy chain)."""
    from paddle_tpu import nn

    cfg = QuantConfig()
    cfg.add_type_config(
        nn.Linear, activation=AbsmaxObserver(),
        weight=PerChannelAbsmaxObserver(channel_axis=-1),
    )
    ptq = PTQ(cfg)
    observing = ptq.quantize(net, inplace=False)
    rng = np.random.RandomState(1)
    for _ in range(2):
        observing(Tensor(jnp.asarray(
            rng.randint(0, 64, (1, 8)), jnp.int32)))
    converted = ptq.convert(observing, inplace=False)
    # grab one observed layer's frozen scale before conversion
    obs_head = converted.lm_head
    frozen = np.asarray(obs_head.weight_scale)
    qm = quantize_for_serving(converted)
    got = np.asarray(qm.lm_head.weight_scale.value)
    np.testing.assert_allclose(got, np.maximum(frozen, 1e-8),
                               rtol=1e-6)
    # stream sanity: quantized model still decodes
    p = rng.randint(0, 64, (1, 6))
    out = qm.generate(Tensor(jnp.asarray(p)), max_new_tokens=4)
    assert out.shape[1] == 10


def test_quantized_state_dict_roundtrip(net):
    """int8 buffers survive state_dict -> fresh model -> set_state_dict
    (the checkpoint/reload path for quantized serving weights)."""
    qm = quantize_for_serving(net)
    state = qm.state_dict()
    fresh = quantize_for_serving(net)  # same structure, same values
    # perturb: zero one int8 buffer, then restore from state
    fresh.lm_head.weight_q.value = jnp.zeros_like(
        fresh.lm_head.weight_q.value
    )
    fresh.set_state_dict(state)
    np.testing.assert_array_equal(
        np.asarray(fresh.lm_head.weight_q.value),
        np.asarray(qm.lm_head.weight_q.value),
    )
    p = np.random.RandomState(2).randint(0, 64, (1, 5))
    a = np.asarray(qm.generate(Tensor(jnp.asarray(p)), 4).numpy())
    b = np.asarray(fresh.generate(Tensor(jnp.asarray(p)), 4).numpy())
    np.testing.assert_array_equal(a, b)


def test_quantized_linear_validates_inputs():
    rng = np.random.RandomState(0)
    with pytest.raises(ValueError, match="int8"):
        QuantizedLinear(jnp.zeros((4, 8), jnp.float32),
                        jnp.ones((8,), jnp.float32))
    with pytest.raises(ValueError, match="per-out-channel"):
        QuantizedLinear(jnp.zeros((4, 8), jnp.int8),
                        jnp.ones((4,), jnp.float32))
    # well-formed: composed forward matches manual dequant matmul
    from paddle_tpu.quantization.serving import quantize_weight

    w = jnp.asarray(rng.randn(8, 16), jnp.float32)
    wq, sc = quantize_weight(w)
    lin = QuantizedLinear(wq, sc)
    x = Tensor(jnp.asarray(rng.randn(3, 8), jnp.float32))
    got = np.asarray(lin(x).numpy())
    want = np.asarray(x.value) @ (
        np.asarray(wq, np.float32) * np.asarray(sc)[None, :]
    )
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
