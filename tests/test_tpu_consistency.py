"""check_consistency(cpu ↔ tpu) on the real chip — SURVEY §4.2 calls
this "the single most important harness to replicate" (the reference's
``tests/python/gpu/test_operator_gpu.py``† reran the CPU suite on GPU
and cross-compared).

Runs only when the session's default backend is a TPU
(``MXTPU_TEST_PLATFORM=tpu``); on the CPU-mesh CI config every test
skips (the cpu↔cpu comparison would be vacuous).
"""
import numpy as np
import pytest

import mxtpu as mx
from mxtpu.test_utils import check_consistency


@pytest.fixture(autouse=True)
def _needs_accelerator():
    """Skip from inside the test, not at import: the backend is asked
    for only once a test of this file runs, so every xdist worker
    collects the same tests."""
    import jax
    if jax.default_backend() == "cpu":
        pytest.skip("needs a real accelerator backend "
                    "(MXTPU_TEST_PLATFORM=tpu)")


def _ctxs(extra_bf16=False):
    ctxs = [{"ctx": mx.cpu(), "type_dict": {}},
            {"ctx": mx.tpu(), "type_dict": {}}]
    if extra_bf16:
        ctxs.append({"ctx": mx.tpu(),
                     "type_dict": {"data": "bfloat16"}})
    return ctxs


def _params(sym, seed=0, **shapes):
    rng = np.random.RandomState(seed)
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    return {n: rng.randn(*s).astype(np.float32) * 0.5
            for n, s in zip(sym.list_arguments(), arg_shapes)}


def test_dense_relu_consistency():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc")
    net = mx.sym.Activation(net, act_type="relu")
    check_consistency(net, _ctxs(),
                      arg_params=_params(net, data=(4, 8)))


def test_conv_bn_pool_consistency():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=8,
                             pad=(1, 1), name="conv")
    net = mx.sym.BatchNorm(net, fix_gamma=False, name="bn")[0]
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    params = _params(net, data=(2, 3, 8, 8))
    aux = {"bn_moving_mean": mx.nd.zeros((8,)),
           "bn_moving_var": mx.nd.ones((8,))}
    check_consistency(net, _ctxs(), arg_params=params,
                      aux_states=aux)


def test_layernorm_softmax_consistency():
    data = mx.sym.Variable("data")
    net = mx.sym.LayerNorm(data, name="ln")
    net = mx.sym.softmax(net, axis=-1)
    check_consistency(net, _ctxs(),
                      arg_params=_params(net, data=(4, 32)))


def test_embedding_take_consistency():
    data = mx.sym.Variable("data")
    net = mx.sym.Embedding(data, input_dim=20, output_dim=8,
                           name="emb")
    params = _params(net, data=(4, 5))
    params["data"] = np.random.RandomState(1).randint(
        0, 20, (4, 5)).astype(np.float32)
    check_consistency(net, _ctxs(), grad_req="null",
                      arg_params=params)


def test_reductions_consistency():
    data = mx.sym.Variable("data")
    net = mx.sym.Group([mx.sym.sum(data, axis=1),
                        mx.sym.max(data, axis=0),
                        mx.sym.norm(data)])
    check_consistency(net, _ctxs(),
                      arg_params=_params(net, data=(6, 7)))


def test_softmax_output_training_consistency():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    params = _params(net, data=(6, 10), softmax_label=(6,))
    params["softmax_label"] = np.random.RandomState(2).randint(
        0, 4, (6,)).astype(np.float32)
    check_consistency(net, _ctxs(), arg_params=params)


def test_bf16_variant_consistency():
    """The bf16-on-TPU run agrees with f32 within bf16 tolerance —
    the reference's fp16 check_consistency tier (SURVEY §7
    hard-part 9)."""
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc")
    net = mx.sym.Activation(net, act_type="tanh")
    check_consistency(net, _ctxs(extra_bf16=True),
                      arg_params=_params(net, data=(4, 16)))


def test_residual_block_training_consistency():
    """Composite graph the per-op sweep can't cover: a full ResNet
    bottleneck motif (conv-BN-relu x2 + residual add) fwd+bwd — the
    cross-op autodiff interplay of the BN custom-VJP with convs and
    the skip connection, on real hardware."""
    data = mx.sym.Variable("data")
    b1 = mx.sym.Convolution(data, kernel=(3, 3), num_filter=6,
                            pad=(1, 1), no_bias=True, name="c1")
    b1 = mx.sym.BatchNorm(b1, fix_gamma=False, name="bn1")[0]
    b1 = mx.sym.Activation(b1, act_type="relu")
    b1 = mx.sym.Convolution(b1, kernel=(3, 3), num_filter=6,
                            pad=(1, 1), no_bias=True, name="c2")
    b1 = mx.sym.BatchNorm(b1, fix_gamma=False, name="bn2")[0]
    sc = mx.sym.Convolution(data, kernel=(1, 1), num_filter=6,
                            no_bias=True, name="sc")
    out = mx.sym.Activation(b1 + sc, act_type="relu")
    out = mx.sym.Pooling(out, global_pool=True, pool_type="avg",
                         kernel=(1, 1))
    params = _params(out, data=(2, 3, 9, 7))
    aux = {f"bn{i}_moving_mean": mx.nd.zeros((6,)) for i in (1, 2)}
    aux.update({f"bn{i}_moving_var": mx.nd.ones((6,))
                for i in (1, 2)})
    check_consistency(out, _ctxs(), arg_params=params,
                      aux_states=aux)


def test_lstm_chain_training_consistency():
    """Fused RNN fwd+bwd across time steps on hardware (scan-carried
    state is another cross-op structure the one-op sweep misses)."""
    data = mx.sym.Variable("data")
    par = mx.sym.Variable("rnn_params")
    s0 = mx.sym.Variable("state")
    c0 = mx.sym.Variable("state_cell")
    out = mx.sym.RNN(data, par, s0, c0, state_size=5, num_layers=1,
                     mode="lstm", name="rnn")[0]
    out = mx.sym.sum(out, axis=(0, 2))
    n_par = 4 * 5 * (4 + 5 + 2)
    params = {
        "data": np.random.RandomState(0).randn(6, 3, 4)
        .astype(np.float32) * 0.5,
        "rnn_params": np.random.RandomState(1).randn(n_par)
        .astype(np.float32) * 0.2,
        "state": np.zeros((1, 3, 5), np.float32),
        "state_cell": np.zeros((1, 3, 5), np.float32),
    }
    check_consistency(out, _ctxs(), arg_params=params)


def test_attention_block_training_consistency():
    """Self-attention composite (FC qkv + batched softmax(QK)V + FC)
    fwd+bwd — the transformer motif with its log-softmax/matmul
    autodiff chain on hardware."""
    data = mx.sym.Variable("data")       # (B, T, D)
    qkv = mx.sym.FullyConnected(data, num_hidden=24, flatten=False,
                                no_bias=True, name="qkv")
    q = mx.sym.slice_axis(qkv, axis=2, begin=0, end=8)
    k = mx.sym.slice_axis(qkv, axis=2, begin=8, end=16)
    v = mx.sym.slice_axis(qkv, axis=2, begin=16, end=24)
    s = mx.sym.batch_dot(q, k, transpose_b=True) * (1.0 / np.sqrt(8))
    p = mx.sym.softmax(s, axis=-1)
    o = mx.sym.batch_dot(p, v)
    out = mx.sym.FullyConnected(o, num_hidden=8, flatten=False,
                                name="proj")
    out = mx.sym.LayerNorm(out, axis=-1, name="ln")
    params = _params(out, data=(2, 6, 8))
    check_consistency(out, _ctxs(), arg_params=params)
