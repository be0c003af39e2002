"""Registry-wide cpu<->tpu consistency sweep — case synthesis.

VERDICT r3 item 2: the reference reran its whole operator suite on the
accelerator (``tests/python/gpu/test_operator_gpu.py``†); with 400
registered names the repo's 7-symbol tier was the biggest correctness
hole.  This module synthesizes a one-op test case for every registry
rule it can (generic shapes, including non-multiple-of-8 to hit
padding/tiling edges), plus curated cases for families whose
signatures defeat generic synthesis (conv/pool/rnn/detection/linalg/
ordering/quantized).

Design notes (why not 400 Executor binds): a TPU compile costs
seconds, so the sweep jits GROUPS of ~25 op applications into one
program per backend (tests/tpu_sweep_runner.py) — the same lowering
rules the symbol/NDArray layers dispatch to, 16 compiles instead of
800.  The symbol-layer glue itself is covered
by tests/test_tpu_consistency.py.

Every op lands in exactly one bucket: CASES (swept), or LEDGER
(skipped, with a reason) — test_tpu_sweep.py asserts the union is the
whole registry, so a new op cannot silently dodge the sweep.
"""
from __future__ import annotations

import numpy as np

# ---------------------------------------------------------------------------
# explicit skip/xfail ledger: op -> reason.  Grouped by cause.
# ---------------------------------------------------------------------------

_HOST_SIDE = "host-side/python op (no device lowering to compare)"
_STATEFUL = "stateful RNG op — draws differ by backend design"
_NEEDS_DATA = "needs structured real data (covered by its own test)"
_NOT_GENERIC = "no generic one-op case (covered by family test file)"

LEDGER = {
    # pure-python / host-side
    "_npi_load": _HOST_SIDE, "_npi_save": _HOST_SIDE,
    "Custom": _HOST_SIDE, "_CustomFunction": _HOST_SIDE,
    "_cvimread": _HOST_SIDE, "_cvimresize": _HOST_SIDE,
    "_cvcopyMakeBorder": _HOST_SIDE,
}

# RNG ops: cross-backend value equality is not the contract (threefry
# streams are seeded identically, but op-level draws route through
# different key-split orders per backend batch layout); their
# statistical behavior is tested in test_random.py.
_RNG_PREFIXES = ("_random_", "_sample_", "random_")


def ledger_reason(name, op):
    if name in LEDGER:
        return LEDGER[name]
    if name.startswith(_RNG_PREFIXES) or name in (
            "shuffle", "_shuffle", "BernoulliDropout", "Dropout"):
        return _STATEFUL
    return None


# ---------------------------------------------------------------------------
# curated cases: op -> list of (args_arrays, kwargs).  Shapes
# deliberately include non-multiples of 8.
# ---------------------------------------------------------------------------

def _r(*shape, seed=0, scale=0.5, pos=False):
    rng = np.random.RandomState(hash(shape) % 2 ** 31 + seed)
    a = rng.randn(*shape).astype(np.float32) * scale
    return np.abs(a) + 0.2 if pos else a


def _ri(lo, hi, *shape, seed=0):
    rng = np.random.RandomState(hash(shape) % 2 ** 31 + seed + 7)
    return rng.randint(lo, hi, shape).astype(np.int32)


def curated_cases():
    """Hand-built cases for ops whose Param defaults can't make a
    valid call (kernel sizes, paired index inputs, ...)."""
    c = {}
    x4 = _r(2, 3, 9, 7)          # NCHW, non-multiple-of-8 H/W
    x4c8 = _r(2, 8, 9, 7)
    w = _r(4, 3, 3, 3)
    c["Convolution"] = [((x4, w, _r(4)),
                         dict(kernel=(3, 3), num_filter=4, pad=(1, 1),
                              no_bias=False))]
    c["Deconvolution"] = [((x4, _r(3, 4, 3, 3), _r(4)),
                           dict(kernel=(3, 3), num_filter=4,
                                pad=(1, 1), no_bias=False))]
    c["Pooling"] = [((x4,), dict(kernel=(2, 2), stride=(2, 2),
                                 pool_type="max")),
                    ((x4,), dict(kernel=(3, 3), pad=(1, 1),
                                 pool_type="avg"))]
    c["FullyConnected"] = [((_r(5, 11), _r(6, 11), _r(6)),
                            dict(num_hidden=6, no_bias=False))]
    c["BatchNorm"] = [((x4c8, _r(8, pos=True), _r(8),
                        np.zeros(8, np.float32),
                        np.ones(8, np.float32)),
                       dict(fix_gamma=False))]
    c["LayerNorm"] = [((_r(5, 11), _r(11, pos=True), _r(11)), {})]
    # FFT pair: (rows, n) real in -> interleaved re/im (rows, 2n) out,
    # and back; n deliberately not a power of two
    c["_contrib_fft"] = [((_r(3, 12),), {})]
    c["_contrib_ifft"] = [((_r(3, 24),), {})]
    c["InstanceNorm"] = [((x4, _r(3, pos=True), _r(3)), {})]
    c["L2Normalization"] = [((_r(5, 11),), {})]
    c["LRN"] = [((x4c8,), dict(nsize=3))]
    c["Activation"] = [((_r(5, 11),), dict(act_type=t))
                       for t in ("relu", "sigmoid", "tanh", "softrelu")]
    c["LeakyReLU"] = [((_r(5, 11),), dict(act_type="leaky")),
                      ((_r(5, 11), _r(1, pos=True)),
                       dict(act_type="prelu"))]
    c["softmax"] = [((_r(5, 11),), dict(axis=-1))]
    c["log_softmax"] = [((_r(5, 11),), dict(axis=-1))]
    c["SoftmaxActivation"] = [((_r(5, 11),), {})]
    c["SoftmaxOutput"] = [((_r(5, 11), _ri(0, 11, 5).astype(
        np.float32)), {})]
    c["Embedding"] = [((_ri(0, 19, 4, 5).astype(np.float32),
                        _r(19, 7)),
                       dict(input_dim=19, output_dim=7))]
    c["take"] = [((_r(9, 7), _ri(0, 9, 4).astype(np.float32)), {})]
    c["gather_nd"] = [((_r(6, 7), _ri(0, 6, 1, 5)), {})]
    c["one_hot"] = [((_ri(0, 9, 7).astype(np.float32),),
                     dict(depth=9))]
    c["Concat"] = [((_r(3, 5), _r(3, 6)), dict(dim=1))]
    c["stack"] = [((_r(3, 5), _r(3, 5)), dict(axis=0))]
    c["add_n"] = [((_r(3, 5), _r(3, 5), _r(3, 5)), {})]
    c["Reshape"] = [((_r(3, 10),), dict(shape=(5, 6)))]
    c["reshape_like"] = [((_r(3, 10), _r(5, 6)), {})]
    c["transpose"] = [((_r(3, 5, 7),), dict(axes=(2, 0, 1)))]
    c["expand_dims"] = [((_r(3, 5),), dict(axis=1))]
    c["slice"] = [((_r(5, 11),), dict(begin=(1, 2), end=(4, 9)))]
    c["slice_axis"] = [((_r(5, 11),),
                        dict(axis=1, begin=1, end=9))]
    c["slice_like"] = [((_r(5, 11), _r(3, 7)), {})]
    c["tile"] = [((_r(3, 5),), dict(reps=(2, 3)))]
    c["repeat"] = [((_r(3, 5),), dict(repeats=2, axis=1))]
    c["pad"] = [((x4,), dict(mode="constant",
                             pad_width=(0, 0, 0, 0, 1, 2, 1, 1)))]
    c["flip"] = [((_r(3, 5),), dict(axis=1))]
    c["clip"] = [((_r(5, 11),), dict(a_min=-0.3, a_max=0.4))]
    # ordering family (VERDICT named)
    c["topk"] = [((_r(5, 11),),
                  dict(k=3, axis=-1, ret_typ="value"))]
    c["sort"] = [((_r(5, 11),), dict(axis=-1))]
    c["argsort"] = [((_r(5, 11),), dict(axis=-1))]
    c["argmax"] = [((_r(5, 11),), dict(axis=1))]
    c["argmin"] = [((_r(5, 11),), dict(axis=1))]
    # reductions with axes
    for rop in ("sum", "mean", "prod", "max", "min", "nansum",
                "nanprod"):
        c[rop] = [((_r(3, 5, 7),), dict(axis=(0, 2))),
                  ((_r(3, 5, 7),), dict(axis=1, keepdims=True))]
    c["norm"] = [((_r(3, 5, 7),), dict(ord=2, axis=1))]
    # broadcasting binaries at broadcast shapes
    for bop in ("broadcast_add", "broadcast_sub", "broadcast_mul",
                "broadcast_div", "broadcast_maximum",
                "broadcast_minimum", "broadcast_power",
                "broadcast_hypot"):
        c[bop] = [((_r(3, 1, 7, pos=True), _r(1, 5, 7, pos=True)), {})]
    c["broadcast_to"] = [((_r(3, 1, 7),), dict(shape=(3, 5, 7)))]
    c["broadcast_like"] = [((_r(3, 1, 7), _r(3, 5, 7)), {})]
    c["where"] = [(((_r(3, 5) > 0).astype(np.float32), _r(3, 5),
                    _r(3, 5)), {})]
    c["dot"] = [((_r(5, 11), _r(11, 6)), {})]
    c["batch_dot"] = [((_r(3, 5, 11), _r(3, 11, 6)), {})]
    c["linalg_gemm2"] = [((_r(5, 11), _r(11, 6)), {})]
    # linalg family (VERDICT named): SPD inputs for potrf
    spd = (lambda a: (a @ a.T + 3 * np.eye(6)).astype(np.float32))(
        _r(6, 6))
    c["linalg_potrf"] = [((spd,), {})]
    c["linalg_syrk"] = [((_r(4, 6),), dict(transpose=False))]
    c["linalg_trmm"] = [((np.tril(_r(5, 5)) + np.eye(
        5, dtype=np.float32), _r(5, 7)), {})]
    c["linalg_trsm"] = [((np.tril(_r(5, 5)) + 2 * np.eye(
        5, dtype=np.float32), _r(5, 7)), {})]
    c["linalg_sumlogdiag"] = [((spd,), {})]
    c["linalg_extractdiag"] = [((_r(6, 6),), {})]
    c["linalg_makediag"] = [((_r(6),), {})]
    c["linalg_det"] = [((spd,), {})]
    c["linalg_inverse"] = [((spd,), {})]
    # sequence family
    c["SequenceMask"] = [((_r(7, 3, 5),
                           np.asarray([3, 5, 7], np.float32)),
                          dict(use_sequence_length=True))]
    c["SequenceLast"] = [((_r(7, 3, 5),
                           np.asarray([3, 5, 7], np.float32)),
                          dict(use_sequence_length=True))]
    c["SequenceReverse"] = [((_r(7, 3, 5),
                              np.asarray([3, 5, 7], np.float32)),
                             dict(use_sequence_length=True))]
    c["RNN"] = [((_r(7, 3, 5), _r(4 * 6 * (5 + 6 + 2)),
                  _r(1, 3, 6), _r(1, 3, 6)),
                 dict(state_size=6, num_layers=1, mode="lstm"))]
    # detection family (VERDICT named)
    c["_contrib_box_iou"] = [((np.asarray(
        [[0, 0, 2, 2], [1, 1, 3, 3]], np.float32),
        np.asarray([[0, 0, 2, 2]], np.float32)), {})]
    c["_contrib_box_nms"] = [((np.asarray(
        [[[0.9, 0, 0, 2, 2], [0.8, 1, 1, 3, 3],
          [0.7, 0, 0, 2.1, 2.1]]], np.float32),),
        dict(overlap_thresh=0.5))]
    c["_contrib_ROIAlign"] = [((_r(1, 4, 9, 9), np.asarray(
        [[0, 0, 0, 6, 6]], np.float32)),
        dict(pooled_size=(2, 2), spatial_scale=1.0))]
    c["ROIPooling"] = [((_r(1, 4, 9, 9), np.asarray(
        [[0, 0, 0, 6, 6]], np.float32)),
        dict(pooled_size=(2, 2), spatial_scale=1.0))]
    c["SliceChannel"] = [((_r(4, 6),),
                          dict(num_outputs=2, axis=1))]
    c["UpSampling"] = [((x4,), dict(scale=2,
                                    sample_type="nearest"))]
    c["BilinearSampler"] = [((_r(1, 2, 5, 5),
                              np.clip(_r(1, 2, 5, 5), -0.9, 0.9)), {})]
    c["GridGenerator"] = [((_r(1, 6),),
                           dict(transform_type="affine",
                                target_shape=(5, 5)))]
    c["Crop"] = [((_r(1, 3, 9, 9), _r(1, 3, 5, 5)),
                  dict(num_args=2))]
    c["Cast"] = [((_r(5, 11),), dict(dtype="float32"))]
    c["amp_cast"] = [((_r(5, 11),), dict(dtype="float32"))]
    # quantized family (VERDICT named): int8/uint8 data paths
    qd = _ri(0, 255, 2, 3, 9, 7).astype(np.uint8)
    qw = (_ri(0, 254, 4, 3, 3, 3) - 127).astype(np.int8)
    f0 = np.float32(0.0)
    f4 = np.float32(4.0)
    fw = np.float32(0.9)
    c["_contrib_quantized_conv"] = [((qd, qw, f0, f4, -fw, fw),
                                     dict(kernel=(3, 3), num_filter=4,
                                          pad=(1, 1)))]
    c["_contrib_quantized_fully_connected"] = [
        (((_ri(0, 254, 5, 6) - 127).astype(np.int8),
          (_ri(0, 254, 4, 6) - 127).astype(np.int8),
          -f4, f4, -fw, fw), dict(num_hidden=4))]
    c["_contrib_quantized_pooling"] = [((qd, f0, f4),
                                        dict(kernel=(2, 2),
                                             stride=(2, 2),
                                             pool_type="max"))]
    c["_contrib_quantized_act"] = [(((_ri(0, 254, 5, 7) - 127)
                                     .astype(np.int8), -f4, f4),
                                    dict(act_type="relu"))]
    c["_contrib_requantize"] = [((_ri(-9999, 9999, 5, 7), -f4, f4),
                                 dict(min_calib_range=-1.0,
                                      max_calib_range=1.0))]
    c["quantize"] = [((_r(5, 7), np.float32(-2.0), np.float32(2.0)),
                      dict(out_type="int8"))]
    c["quantize_v2"] = [((_r(5, 7),),
                         dict(min_calib_range=-2.0,
                              max_calib_range=2.0,
                              out_type="int8"))]
    c["dequantize"] = [(((_ri(0, 254, 5, 7) - 127).astype(np.int8),
                         np.float32(-2.0), np.float32(2.0)), {})]

    # ---- wave 2: optimizer updates + remaining families -------------
    w_, g_, m_, v_ = (_r(5, 11, seed=s) for s in range(4))
    okw = dict(lr=0.1, wd=0.01)
    c["sgd_update"] = [((w_, g_), dict(okw))]
    c["sgd_mom_update"] = [((w_, g_, m_), dict(okw, momentum=0.9))]
    c["nag_mom_update"] = [((w_, g_, m_), dict(okw, momentum=0.9))]
    c["signsgd_update"] = [((w_, g_), dict(okw))]
    c["signum_update"] = [((w_, g_, m_), dict(okw, momentum=0.9))]
    c["adam_update"] = [((w_, g_, m_, np.abs(v_)), dict(lr=0.01))]
    c["ftrl_update"] = [((w_, g_, m_, np.abs(v_) + 0.1),
                         dict(lr=0.1))]
    c["rmsprop_update"] = [((w_, g_, np.abs(v_) + 0.1),
                            dict(lr=0.01))]
    c["rmspropalex_update"] = [((w_, g_, m_ * 0.1, np.abs(v_) + 0.1,
                                 m_ * 0.0), dict(lr=0.01))]
    c["mp_sgd_update"] = [((w_.astype(np.float32), g_, w_),
                           dict(okw))]
    c["mp_sgd_mom_update"] = [((w_, g_, m_, w_),
                               dict(okw, momentum=0.9))]
    c["mp_nag_mom_update"] = [((w_, g_, m_, w_),
                               dict(okw, momentum=0.9))]
    c["multi_sgd_update"] = [((w_, g_, v_, m_),
                              dict(lrs=(0.1, 0.1), wds=(0.0, 0.0),
                                   num_weights=2))]
    c["multi_sgd_mom_update"] = [((w_, g_, m_, v_, g_, w_),
                                  dict(lrs=(0.1, 0.1),
                                       wds=(0.0, 0.0), momentum=0.9,
                                       num_weights=2))]
    c["multi_mp_sgd_update"] = [((w_, g_, w_, v_, g_, v_),
                                 dict(lrs=(0.1, 0.1), wds=(0.0, 0.0),
                                      num_weights=2))]
    c["multi_mp_sgd_mom_update"] = [((w_, g_, m_, w_, v_, g_, m_, v_),
                                     dict(lrs=(0.1, 0.1),
                                          wds=(0.0, 0.0),
                                          momentum=0.9,
                                          num_weights=2))]
    c["_sparse_adagrad_update"] = [((w_, g_, np.abs(v_) + 0.1),
                                    dict(lr=0.1))]
    # misc families
    c["matmul"] = [((_r(5, 11), _r(11, 6)), {})]
    c["pick"] = [((_r(5, 11), _ri(0, 11, 5).astype(np.float32)),
                  dict(axis=1))]
    c["batch_take"] = [((_r(5, 11), _ri(0, 11, 5)), {})]
    c["softmax_cross_entropy"] = [((_r(5, 11),
                                    _ri(0, 11, 5).astype(np.float32)),
                                   {})]
    c["GroupNorm"] = [((_r(2, 6, 9, 7), _r(6, pos=True), _r(6)),
                       dict(num_groups=2))]
    c["space_to_depth"] = [((_r(2, 3, 6, 8),), dict(block_size=2))]
    c["depth_to_space"] = [((_r(2, 12, 3, 4),), dict(block_size=2))]
    c["im2col"] = [((_r(2, 3, 9, 7),),
                    dict(kernel=(3, 3), pad=(1, 1)))]
    c["col2im"] = [((_r(2, 27, 63),),
                    dict(output_size=(9, 7), kernel=(3, 3),
                         pad=(1, 1)))]
    c["ElementWiseSum"] = [((_r(3, 10), _r(3, 10), _r(3, 10)), {})]
    c["amp_multicast"] = [((_r(3, 10), _r(3, 10).astype(np.float32)),
                           dict(num_outputs=2))]
    c["multi_all_finite"] = [((_r(3, 10), _r(3, 10)),
                              dict(num_arrays=2))]
    c["khatri_rao"] = [((_r(4, 5), _r(3, 5)), {})]
    c["linalg_gemm"] = [((_r(5, 11), _r(11, 6), _r(5, 6)), {})]
    spd2 = (lambda a: (a @ a.T + 3 * np.eye(6)).astype(np.float32))(
        _r(6, 6, seed=9))
    c["linalg_potri"] = [((np.linalg.cholesky(spd2),), {})]
    c["linalg_slogdet"] = [((spd2,), {})]
    c["linalg_syevd"] = [(((spd2 + spd2.T) / 2,), {})]
    c["arccosh"] = [((np.abs(_r(5, 11)) + 1.2,), {})]
    c["_mod_scalar"] = [((_r(5, 11, pos=True),), dict(scalar=0.7))]
    c["_DivScalar"] = [((_r(5, 11),), dict(scalar=0.7))]
    c["_arange"] = [((), dict(start=0.0, stop=12.0, step=0.5))]
    c["_eye"] = [((), dict(N=7, M=9, k=1))]
    c["_linspace"] = [((), dict(start=0.0, stop=3.0, num=13))]
    c["fill_element_0index"] = [((_r(5, 11), _r(5),
                                  _ri(0, 11, 5).astype(np.float32)),
                                 {})]
    c["_contrib_index_copy"] = [((_r(9, 4), _ri(0, 9, 3),
                                  _r(3, 4)), {})]
    c["_contrib_boolean_mask"] = [((_r(6, 4), np.asarray(
        [1, 0, 1, 1, 0, 1], np.float32)), {})]
    c["_scatter_set_nd"] = [((_r(6, 7), _r(5, 7), _ri(0, 6, 1, 5)),
                             dict(shape=(6, 7)))]
    c["scatter_nd"] = [((_r(5), _ri(0, 6, 1, 5)),
                        dict(shape=(6,)))]
    c["_ravel_multi_index"] = [((_ri(0, 5, 2, 4).astype(np.float32),),
                                dict(shape=(5, 5)))]
    c["_unravel_index"] = [((_ri(0, 24, 6).astype(np.float32),),
                            dict(shape=(4, 6)))]
    c["BilinearResize2D"] = [((_r(1, 3, 6, 5),),
                              dict(height=9, width=11))]
    c["_contrib_AdaptiveAvgPooling2D"] = [((_r(1, 3, 9, 7),),
                                           dict(output_size=(3, 3)))]
    c["_contrib_quantized_flatten"] = [
        (((_ri(0, 254, 2, 3, 4) - 127).astype(np.int8),
          np.float32(-2.0), np.float32(2.0)), {})]
    c["_contrib_MoEFFN"] = [((_r(24, 8), _r(8, 4) * 2,
                              _r(4, 8, 16, scale=0.3),
                              _r(4, 16, scale=0.1),
                              _r(4, 16, 8, scale=0.3),
                              _r(4, 8, scale=0.1)),
                             dict(capacity_factor=1.5))]
    c["_contrib_quantized_concat"] = [
        (((_ri(0, 254, 2, 3) - 127).astype(np.int8),
          (_ri(0, 254, 2, 4) - 127).astype(np.int8),
          np.float32(-2.0), np.float32(2.0),
          np.float32(-1.0), np.float32(1.0)),
         dict(num_args=2, dim=1))]
    return c


# ---------------------------------------------------------------------------
# generic synthesis for everything else
# ---------------------------------------------------------------------------

def _candidates(n_in):
    """Ordered generic input sets; first that works on CPU wins.
    (3, 10) and (2, 3, 9, 7) are deliberately non-multiples of 8."""
    outs = []
    base = [_r(3, 10, seed=i) for i in range(max(n_in, 1))]
    outs.append(tuple(base))
    outs.append(tuple(np.abs(b) + 0.2 for b in base))      # pos-only
    outs.append(tuple(_r(2, 3, 9, 7, seed=i)
                      for i in range(max(n_in, 1))))
    outs.append(tuple(np.abs(_r(2, 3, 9, 7, seed=i)) + 0.2
                      for i in range(max(n_in, 1))))
    outs.append(tuple(_ri(0, 5, 3, 10, seed=i).astype(np.float32)
                      for i in range(max(n_in, 1))))       # small ints
    return outs


def bf16_cases():
    """bf16 variants of the heavy families (case idx >= 100 marks the
    looser bf16 tolerance tier in test_tpu_sweep).  The north-star
    benches run bf16, so the consistency tier must cover it too.
    FORWARD-only: numpy's bfloat16 is not np.floating, so the runner's
    float_argnums sees no differentiable inputs — bwd coverage lives
    in the f32 tier."""
    import numpy as np
    base = curated_cases()
    picks = ["Convolution", "FullyConnected", "BatchNorm", "LayerNorm",
             "softmax", "dot", "batch_dot", "Pooling", "Activation",
             "_contrib_MoEFFN"]
    out = []
    for name in picks:
        for i, (args, kw) in enumerate(base.get(name, [])[:1]):
            # all float inputs go bf16 (conv/dot require matching
            # operand dtypes; params cast alongside data like the
            # compute_dtype train path)
            cast = tuple(
                a.astype("bfloat16")
                if isinstance(a, np.ndarray)
                and a.dtype == np.float32 else a
                for a in args)
            out.append((name, 100 + i, cast, kw))
    return out


def build_cases():
    """-> (cases: list[(op_name, case_idx, args, kwargs)],
           skipped: dict[op_name, reason]).

    Discovery runs each candidate eagerly on CPU; an op joins the
    sweep with its first working candidate (plus every curated case).
    """
    import jax
    import jax.numpy as jnp

    from mxtpu.ops.registry import get_op, list_ops

    curated = curated_cases()
    cases = []
    skipped = {}
    seen_fns = {}
    # pre-seed the rule->name map with the curated names so an alias
    # that sorts earlier (e.g. "MoEFFN" < "_contrib_MoEFFN", "_div" <
    # "broadcast_div") can neither claim the rule (stranding the
    # curated case) nor get auto-swept as a duplicate (r4 review: 14
    # rules were swept twice with a lying ledger)
    for cname in curated:
        try:
            seen_fns.setdefault(id(get_op(cname).fn), cname)
        except Exception:
            pass
    for name in sorted(list_ops()):
        op = get_op(name)
        if name in curated:
            for i, (args, kw) in enumerate(curated[name]):
                cases.append((name, i, args, kw))
            continue
        # aliases share the rule fn; sweep each rule once
        if id(op.fn) in seen_fns:
            skipped[name] = f"alias of {seen_fns[id(op.fn)]}"
            continue
        seen_fns[id(op.fn)] = name
        reason = ledger_reason(name, op)
        if reason is not None:
            skipped[name] = reason
            continue
        n_in = op.num_inputs if op.num_inputs >= 0 else 3
        if n_in == 0:
            # nullary init ops: compare with explicit shape
            try:
                out = op(shape=(3, 10))
                cases.append((name, 0, (), {"shape": (3, 10)}))
            except Exception:
                skipped[name] = _NOT_GENERIC
            continue
        placed = False
        for args in _candidates(n_in):
            for kw in ([{"num_args": len(args)}, {}]
                       if op.num_inputs == -1 else [{}]):
                try:
                    out = op(*[jnp.asarray(a) for a in args], **kw)
                    break
                except Exception:
                    out = None
            try:
                if out is None:
                    raise ValueError("no candidate call succeeded")
                leaves = jax.tree_util.tree_leaves(out)
                if not leaves:
                    raise ValueError("no outputs")
                ok = all(bool(jnp.all(jnp.isfinite(
                    l.astype(jnp.float32)))) for l in leaves
                    if hasattr(l, "astype")
                    and jnp.issubdtype(l.dtype, jnp.floating))
                if not ok:
                    continue
                cases.append((name, 0, args, kw))
                placed = True
                break
            except Exception:
                continue
        if not placed:
            skipped[name] = _NOT_GENERIC
    cases.extend(bf16_cases())
    return cases, skipped
