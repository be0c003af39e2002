"""``mx.nd.contrib`` namespace — control flow + experimental ops.

Reference: ``python/mxnet/ndarray/contrib.py``† (foreach / while_loop /
cond arrived around v1.3, ``src/operator/control_flow.cc``†), plus
contrib ops in ``src/operator/contrib/``†.

TPU-native: control flow maps directly onto ``lax.scan`` / ``lax
.while_loop`` / ``lax.cond`` — compiler-friendly structured control flow
is exactly what the reference was reaching for.  Detection-family ops
(box_nms / multibox) live here too with padded static-shape contracts
(SURVEY.md §7 M7).
"""
from __future__ import annotations

from typing import Any, Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..base import MXNetError
from ..ops.registry import Param, register_op
from .ndarray import NDArray


def _unwrap(x):
    return x._data if isinstance(x, NDArray) else x


def _wrap_tree(t):
    return jax.tree_util.tree_map(
        lambda a: NDArray(a, None, _placed=True), t)


def foreach(body: Callable, data, init_states):
    """``mx.nd.contrib.foreach``† — scan body over the leading axis.

    body(data_slice, states) -> (outputs, new_states)
    """
    data_r = jax.tree_util.tree_map(_unwrap, data)
    states_r = jax.tree_util.tree_map(_unwrap, init_states)

    def step(carry, x):
        xs = _wrap_tree(x)
        cs = _wrap_tree(carry)
        out, new_states = body(xs, cs)
        return (jax.tree_util.tree_map(_unwrap, new_states),
                jax.tree_util.tree_map(_unwrap, out))

    final, outs = lax.scan(step, states_r, data_r)
    return _wrap_tree(outs), _wrap_tree(final)


def while_loop(cond: Callable, func: Callable, loop_vars,
               max_iterations: int):
    """``mx.nd.contrib.while_loop``†.  Static max_iterations bound keeps
    shapes XLA-compatible; outputs are padded to max_iterations."""
    vars_r = [_unwrap(v) for v in loop_vars]

    def c(state):
        i, vs = state
        w = [NDArray(v, None, _placed=True) for v in vs]
        keep = cond(*w)
        keep = _unwrap(keep).astype(bool).reshape(())
        return jnp.logical_and(i < max_iterations, keep)

    def b(state):
        i, vs = state
        w = [NDArray(v, None, _placed=True) for v in vs]
        _, new_vars = func(*w)
        return (i + 1, [_unwrap(v) for v in new_vars])

    # note: we drop per-step stacked outputs (rarely used); loop vars
    # carry the result.  Parity gap documented.
    i, out_vars = lax.while_loop(c, b, (jnp.asarray(0), vars_r))
    return ([], [NDArray(v, None, _placed=True) for v in out_vars])


def cond(pred: Callable, then_func: Callable, else_func: Callable):
    """``mx.nd.contrib.cond``†."""
    p = pred() if callable(pred) else pred
    p = _unwrap(p).astype(bool).reshape(())
    t = lambda _: jax.tree_util.tree_map(  # noqa: E731
        _unwrap, then_func())
    f = lambda _: jax.tree_util.tree_map(  # noqa: E731
        _unwrap, else_func())
    out = lax.cond(p, t, f, None)
    return _wrap_tree(out)


# ----------------------------------------------------------------------
# detection ops — padded static-shape NMS family
# ----------------------------------------------------------------------
def _box_iou_raw(a, b, format="corner"):  # noqa: A002
    """Pairwise IoU (reference ``contrib.box_iou``†)."""
    if format == "center":
        a = jnp.concatenate([a[..., :2] - a[..., 2:] / 2,
                             a[..., :2] + a[..., 2:] / 2], -1)
        b = jnp.concatenate([b[..., :2] - b[..., 2:] / 2,
                             b[..., :2] + b[..., 2:] / 2], -1)
    tl = jnp.maximum(a[..., :, None, :2], b[..., None, :, :2])
    br = jnp.minimum(a[..., :, None, 2:], b[..., None, :, 2:])
    wh = jnp.maximum(br - tl, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    area_a = jnp.maximum((a[..., 2] - a[..., 0]) *
                         (a[..., 3] - a[..., 1]), 0.0)
    area_b = jnp.maximum((b[..., 2] - b[..., 0]) *
                         (b[..., 3] - b[..., 1]), 0.0)
    union = area_a[..., :, None] + area_b[..., None, :] - inter
    return inter / jnp.maximum(union, 1e-12)


register_op("_contrib_box_iou", num_inputs=2,
            params=[Param("format", str, "corner",
                          enum=("corner", "center"))])(_box_iou_raw)


def box_iou(lhs, rhs, format="corner"):  # noqa: A002
    """Pairwise IoU (reference ``contrib.box_iou``†)."""
    return NDArray(_box_iou_raw(_unwrap(lhs), _unwrap(rhs),
                                format=format), None, _placed=True)


def _nms_single(scores, boxes, iou_thresh, valid_thresh, topk,
                ids=None):
    """Greedy NMS with static shapes: iterates topk times via fori_loop,
    suppressing overlaps.  ``ids`` (optional per-box class ids) limits
    suppression to same-class pairs (box_nms ``id_index`` semantics
    when ``force_suppress=False``).  Returns keep mask — the
    padded-max-size contract replacing the reference's dynamic-output
    NMS (src/operator/contrib/bounding_box.cc†)."""
    n = scores.shape[0]
    order = jnp.argsort(-scores)
    boxes_s = boxes[order]
    scores_s = scores[order]
    tl = jnp.maximum(boxes_s[:, None, :2], boxes_s[None, :, :2])
    br = jnp.minimum(boxes_s[:, None, 2:], boxes_s[None, :, 2:])
    wh = jnp.maximum(br - tl, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    area = jnp.maximum((boxes_s[:, 2] - boxes_s[:, 0]) *
                       (boxes_s[:, 3] - boxes_s[:, 1]), 0.0)
    iou = inter / jnp.maximum(area[:, None] + area[None, :] - inter, 1e-12)
    if ids is not None:
        ids_s = ids[order]
        iou = jnp.where(ids_s[:, None] == ids_s[None, :], iou, 0.0)

    def body(i, keep):
        # suppress j>i overlapping box i if i kept
        sup = (iou[i] > iou_thresh) & (jnp.arange(n) > i) & keep[i]
        return keep & ~sup

    keep0 = scores_s > valid_thresh
    keep = lax.fori_loop(0, n if topk < 0 else min(topk, n), body, keep0)
    inv = jnp.argsort(order)
    return keep[inv], order


def _box_nms_raw(d, overlap_thresh=0.5, valid_thresh=0.0, topk=-1,
                 coord_start=2, score_index=1, id_index=-1,
                 force_suppress=False, in_format="corner",
                 out_format="corner"):
    """``contrib.box_nms``† with the padded contract: suppressed entries
    are set to -1 instead of removed (static output shape)."""
    batched = d.ndim == 3
    if not batched:
        d = d[None]

    def one(db):
        scores = db[:, score_index]
        boxes = lax.dynamic_slice_in_dim(db, coord_start, 4, axis=1)
        # id_index restricts suppression to same-class pairs unless
        # force_suppress (reference box_nms semantics)
        ids = db[:, id_index] if id_index >= 0 and not force_suppress \
            else None
        keep, order = _nms_single(scores, boxes, overlap_thresh,
                                  valid_thresh, topk, ids=ids)
        out = jnp.where(keep[:, None], db, -jnp.ones_like(db))
        return out

    out = jax.vmap(one)(d)
    if not batched:
        out = out[0]
    return out


register_op("_contrib_box_nms",
            params=[Param("overlap_thresh", float, 0.5),
                    Param("valid_thresh", float, 0.0),
                    Param("topk", int, -1),
                    Param("coord_start", int, 2),
                    Param("score_index", int, 1),
                    Param("id_index", int, -1),
                    Param("force_suppress", bool, False),
                    Param("in_format", str, "corner"),
                    Param("out_format", str, "corner")],
            aliases=("box_nms",), differentiable=False)(_box_nms_raw)


def box_nms(data, **kwargs):
    return NDArray(_box_nms_raw(_unwrap(data), **kwargs), None,
                   _placed=True)


def _boolean_mask_raw(d, m, axis=0):
    m = m.astype(bool)
    idx = jnp.argsort(~m)  # true rows first, stable
    compacted = jnp.take(d, idx, axis=axis)
    mask_sorted = jnp.sort(~m) == False  # noqa: E712
    shape = [1] * d.ndim
    shape[axis] = d.shape[axis]
    return compacted * mask_sorted.reshape(shape).astype(d.dtype)


register_op("_contrib_boolean_mask", num_inputs=2,
            params=[Param("axis", int, 0)])(_boolean_mask_raw)


def boolean_mask(data, index, axis=0):
    """``contrib.boolean_mask``† — dynamic output in the reference; here
    the padded contract: masked-out rows are zeroed and compacted to the
    front, output keeps the input's static length."""
    return NDArray(_boolean_mask_raw(_unwrap(data), _unwrap(index),
                                     axis=axis), None, _placed=True)


def _getnnz_raw(d, axis=None):
    return jnp.asarray(
        jnp.sum(d != 0) if axis is None else jnp.sum(d != 0, axis=axis)
    ).astype(jnp.int64)


register_op("_contrib_getnnz", params=[Param("axis", int, None)],
            differentiable=False)(_getnnz_raw)


def getnnz(data, axis=None):
    return NDArray(_getnnz_raw(_unwrap(data), axis=axis), None,
                   _placed=True)


def _count_sketch_raw(d, hh, ss, out_dim=0):
    """``contrib.count_sketch``† — compact bilinear pooling primitive.
    Input order (data, h, s) matches the reference op signature."""
    hh = hh.astype(jnp.int32)
    out = jnp.zeros(d.shape[:-1] + (int(out_dim),), d.dtype)
    return out.at[..., hh].add(d * ss)


register_op("_contrib_count_sketch", num_inputs=3,
            params=[Param("out_dim", int, 0)],
            aliases=("_contrib_CountSketch",))(_count_sketch_raw)


def count_sketch(data, h, s, out_dim):
    return NDArray(_count_sketch_raw(_unwrap(data), _unwrap(h),
                                     _unwrap(s), out_dim=out_dim),
                   None, _placed=True)


def _fft_raw(d, compute_size=128):
    f = jnp.fft.fft(d, axis=-1)
    return jnp.stack([f.real, f.imag], axis=-1).reshape(
        d.shape[:-1] + (2 * d.shape[-1],)).astype(d.dtype)


register_op("_contrib_fft",
            params=[Param("compute_size", int, 128)])(_fft_raw)


def fft(data, compute_size=128):
    return NDArray(_fft_raw(_unwrap(data), compute_size=compute_size),
                   None, _placed=True)


def _ifft_raw(d, compute_size=128):
    """Real-matmul IDFT: N*ifft(x)_n = sum_k a_k cos(2pi kn/N)
    - b_k sin(2pi kn/N) for x = a + bi.  A (N, N) cos/sin matmul
    rides the MXU and needs no complex arithmetic; the op's contract
    (contrib.ifft†, compute_size~128) keeps N small."""
    c = d.reshape(d.shape[:-1] + (d.shape[-1] // 2, 2))
    a = c[..., 0]
    b = c[..., 1]
    n = a.shape[-1]
    k = np.arange(n)
    ang = 2.0 * np.pi * np.outer(k, k) / n
    cos_t = jnp.asarray(np.cos(ang), jnp.float32)
    sin_t = jnp.asarray(np.sin(ang), jnp.float32)
    prec = lax.Precision.HIGHEST \
        if jnp.dtype(d.dtype) == jnp.float32 else None
    out = jnp.matmul(a, cos_t, precision=prec) - \
        jnp.matmul(b, sin_t, precision=prec)
    return out.astype(d.dtype)


register_op("_contrib_ifft",
            params=[Param("compute_size", int, 128)])(_ifft_raw)


def ifft(data, compute_size=128):
    return NDArray(_ifft_raw(_unwrap(data), compute_size=compute_size),
                   None, _placed=True)


def _quadratic_raw(d, a=0.0, b=0.0, c=0.0):
    """The reference's tutorial op (``src/operator/contrib/quadratic_op``†)."""
    return a * d * d + b * d + c


register_op("_contrib_quadratic",
            params=[Param("a", float, 0.0), Param("b", float, 0.0),
                    Param("c", float, 0.0)])(_quadratic_raw)


def quadratic(data, a=0.0, b=0.0, c=0.0):
    return NDArray(_quadratic_raw(_unwrap(data), a=a, b=b, c=c), None,
                   _placed=True)


def _bipartite_matching_raw(data, is_ascend=False, threshold=0.0,
                            topk=-1):
    """``contrib.bipartite_matching``†: greedy bipartite matching over a
    (R, C) score matrix.  Returns (row_match, col_match) with -1 for
    unmatched; static shapes via a fori_loop of min(R, C) greedy picks.
    """
    batched = data.ndim == 3
    d = data if batched else data[None]

    def one(s):
        R, C = s.shape
        worst = jnp.inf if is_ascend else -jnp.inf

        def body(_, state):
            s_cur, rm, cm = state
            flat = jnp.argmin(s_cur) if is_ascend else jnp.argmax(s_cur)
            r, c = flat // C, flat % C
            v = s_cur[r, c]
            ok = (v < threshold) if is_ascend else (v > threshold)
            rm = jnp.where(ok, rm.at[r].set(c.astype(rm.dtype)), rm)
            cm = jnp.where(ok, cm.at[c].set(r.astype(cm.dtype)), cm)
            s_cur = jnp.where(ok, s_cur.at[r, :].set(worst)
                              .at[:, c].set(worst), s_cur)
            return s_cur, rm, cm

        n = min(R, C) if topk < 0 else min(topk, R, C)
        init = (s.astype(jnp.float32),
                -jnp.ones((R,), jnp.float32),
                -jnp.ones((C,), jnp.float32))
        _, rm, cm = lax.fori_loop(0, n, body, init)
        return rm, cm

    rm, cm = jax.vmap(one)(d)
    if not batched:
        rm, cm = rm[0], cm[0]
    return rm, cm


register_op("_contrib_bipartite_matching", num_outputs=2,
            params=[Param("is_ascend", bool, False),
                    Param("threshold", float, 0.0),
                    Param("topk", int, -1)],
            differentiable=False)(_bipartite_matching_raw)


def bipartite_matching(data, **kwargs):
    rm, cm = _bipartite_matching_raw(_unwrap(data), **kwargs)
    return (NDArray(rm, None, _placed=True),
            NDArray(cm, None, _placed=True))
