"""NN-tier operator tail: im2col/col2im, deformable convolution,
(PS)ROI pooling variants, ROIAlign, adaptive pooling, bilinear resize,
SyncBatchNorm, index_copy, and the INT8 quantized execution tier
(reference ``src/operator/contrib/*``† and
``src/operator/quantization/*``† rebuilt as XLA lowering rules).

TPU notes: everything is static-shaped and vectorised — per-ROI/per-tap
work is ``vmap`` over gathers and masked reductions (no data-dependent
loops), and int8 conv/fc accumulate in int32 on the MXU via
``preferred_element_type``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..base import MXNetError
from ..ops.registry import Param, register_op
from .ops_impl import _tuple

# ---------------------------------------------------------------------------
# im2col / col2im (src/operator/nn/im2col.h† exposed as ops in 1.5;
# also the building block our deformable conv reuses)
# ---------------------------------------------------------------------------


def _im2col(data, kernel=(), stride=None, dilate=None, pad=None):
    """(N, C, H, W) -> (N, C*kh*kw, Ho*Wo) patch matrix."""
    kh, kw = int(kernel[0]), int(kernel[1])
    sh, sw = _tuple(stride, 2)
    dh, dw = _tuple(dilate, 2)
    ph, pw = _tuple(pad, 2) if pad is not None else (0, 0)
    N, C, H, W = data.shape
    x = jnp.pad(data, ((0, 0), (0, 0), (ph, ph), (pw, pw)))
    Ho = (H + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    Wo = (W + 2 * pw - dw * (kw - 1) - 1) // sw + 1
    cols = []
    for i in range(kh):
        for j in range(kw):
            patch = lax.slice(
                x, (0, 0, i * dh, j * dw),
                (N, C, i * dh + (Ho - 1) * sh + 1,
                 j * dw + (Wo - 1) * sw + 1),
                (1, 1, sh, sw))
            cols.append(patch)
    out = jnp.stack(cols, axis=2)        # (N, C, kh*kw, Ho, Wo)
    return out.reshape(N, C * kh * kw, Ho * Wo)


register_op("im2col",
            params=[Param("kernel", tuple, ()),
                    Param("stride", tuple, None),
                    Param("dilate", tuple, None),
                    Param("pad", tuple, None)])(_im2col)


def _col2im(col, output_size=(), kernel=(), stride=None, dilate=None,
            pad=None):
    """Scatter-add the inverse of im2col (gradient-style fold)."""
    kh, kw = int(kernel[0]), int(kernel[1])
    sh, sw = _tuple(stride, 2)
    dh, dw = _tuple(dilate, 2)
    ph, pw = _tuple(pad, 2) if pad is not None else (0, 0)
    H, W = int(output_size[0]), int(output_size[1])
    N = col.shape[0]
    C = col.shape[1] // (kh * kw)
    Ho = (H + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    Wo = (W + 2 * pw - dw * (kw - 1) - 1) // sw + 1
    cols = col.reshape(N, C, kh * kw, Ho, Wo)
    out = jnp.zeros((N, C, H + 2 * ph, W + 2 * pw), col.dtype)
    k = 0
    for i in range(kh):
        for j in range(kw):
            ys = i * dh + sh * jnp.arange(Ho)
            xs = j * dw + sw * jnp.arange(Wo)
            out = out.at[:, :, ys[:, None], xs[None, :]].add(
                cols[:, :, k])
            k += 1
    return out[:, :, ph:ph + H, pw:pw + W]


register_op("col2im",
            params=[Param("output_size", tuple, ()),
                    Param("kernel", tuple, ()),
                    Param("stride", tuple, None),
                    Param("dilate", tuple, None),
                    Param("pad", tuple, None)])(_col2im)

# ---------------------------------------------------------------------------
# bilinear helpers
# ---------------------------------------------------------------------------


def _bilinear_gather(img, y, x):
    """img (C, H, W); y/x arbitrary same-shaped coords; zero outside.
    Returns (C,) + y.shape."""
    H, W = img.shape[-2], img.shape[-1]
    y0 = jnp.floor(y)
    x0 = jnp.floor(x)
    wy1 = y - y0
    wx1 = x - x0
    out = 0.0
    for dy, wy in ((0, 1.0 - wy1), (1, wy1)):
        for dx, wx in ((0, 1.0 - wx1), (1, wx1)):
            yy = y0 + dy
            xx = x0 + dx
            inb = (yy >= 0) & (yy <= H - 1) & (xx >= 0) & (xx <= W - 1)
            yc = jnp.clip(yy, 0, H - 1).astype(jnp.int32)
            xc = jnp.clip(xx, 0, W - 1).astype(jnp.int32)
            val = img[..., yc, xc]          # (C,) + coord shape
            out = out + val * (wy * wx * inb)
    return out


# ---------------------------------------------------------------------------
# Deformable convolution (contrib/deformable_convolution.cc†,
# Dai et al. 2017)
# ---------------------------------------------------------------------------


def _deformable_convolution(data, offset, weight, bias=None, kernel=(),
                            stride=None, dilate=None, pad=None,
                            num_filter=0, num_group=1,
                            num_deformable_group=1, no_bias=False):
    """data (N,C,H,W); offset (N, 2*G*kh*kw, Ho, Wo) with per-tap
    (dy, dx) pairs for each of G deformable groups; weight
    (O, C/num_group, kh, kw).  Bilinear sampling at deformed tap
    positions, then the conv contraction runs as one einsum on the MXU.
    """
    kh, kw = int(kernel[0]), int(kernel[1])
    sh, sw = _tuple(stride, 2)
    dh, dw = _tuple(dilate, 2)
    ph, pw = _tuple(pad, 2) if pad is not None else (0, 0)
    N, C, H, W = data.shape
    G = num_deformable_group
    K = kh * kw
    Ho = (H + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    Wo = (W + 2 * pw - dw * (kw - 1) - 1) // sw + 1

    base_y = (sh * jnp.arange(Ho) - ph)[:, None]        # (Ho, 1)
    base_x = (sw * jnp.arange(Wo) - pw)[None, :]        # (1, Wo)
    off = offset.reshape(N, G, K, 2, Ho, Wo)

    cg = C // G

    def per_image(img, off_i):           # img (C,H,W), off_i (G,K,2,...)
        taps = []
        for k in range(K):
            i, j = divmod(k, kw)
            tap_g = []
            for g in range(G):
                y = base_y + i * dh + off_i[g, k, 0]    # (Ho, Wo)
                x = base_x + j * dw + off_i[g, k, 1]
                tap_g.append(_bilinear_gather(
                    img[g * cg:(g + 1) * cg], y, x))    # (cg, Ho, Wo)
            taps.append(jnp.concatenate(tap_g, axis=0))  # (C, Ho, Wo)
        return jnp.stack(taps, axis=1)   # (C, K, Ho, Wo)

    cols = jax.vmap(per_image)(data, off)               # (N, C, K, Ho, Wo)
    O = weight.shape[0]
    w = weight.reshape(num_group, O // num_group, C // num_group, K)
    colsg = cols.reshape(N, num_group, C // num_group, K, Ho, Wo)
    out = jnp.einsum("ngckhw,gock->ngohw", colsg, w,
                     preferred_element_type=cols.dtype)
    out = out.reshape(N, O, Ho, Wo)
    if bias is not None and not no_bias:
        out = out + bias.reshape(1, -1, 1, 1)
    return out


register_op("_contrib_DeformableConvolution", num_inputs=-1,
            params=[Param("kernel", tuple, ()),
                    Param("stride", tuple, None),
                    Param("dilate", tuple, None),
                    Param("pad", tuple, None),
                    Param("num_filter", int, 0),
                    Param("num_group", int, 1),
                    Param("num_deformable_group", int, 1),
                    Param("no_bias", bool, False)],
            aliases=("DeformableConvolution",))(
    lambda data, offset, weight, *b, **kw: _deformable_convolution(
        data, offset, weight, b[0] if b else None, **kw))

# ---------------------------------------------------------------------------
# PSROIPooling + DeformablePSROIPooling (contrib†, R-FCN heads)
# ---------------------------------------------------------------------------


def _psroi_core(data, rois, spatial_scale, output_dim, pooled_size,
                group_size, trans=None, trans_std=0.0, part_size=0):
    P = int(pooled_size)
    gs = int(group_size) or P
    N, C, H, W = data.shape
    ys = jnp.arange(H, dtype=jnp.float32)
    xs = jnp.arange(W, dtype=jnp.float32)

    def one_roi(roi, tr):
        bidx = roi[0].astype(jnp.int32)
        # reference rounds roi corners then scales
        x1 = jnp.round(roi[1]) * spatial_scale - 0.5
        y1 = jnp.round(roi[2]) * spatial_scale - 0.5
        x2 = (jnp.round(roi[3]) + 1.0) * spatial_scale - 0.5
        y2 = (jnp.round(roi[4]) + 1.0) * spatial_scale - 0.5
        rh = jnp.maximum(y2 - y1, 0.1)
        rw = jnp.maximum(x2 - x1, 0.1)
        bin_h = rh / P
        bin_w = rw / P
        img = data[bidx]

        def one_bin(d, i, j):
            # deformable shift for this bin, scaled by roi size
            if tr is not None:
                dy = tr[0, i * P + j] * trans_std * rh
                dx = tr[1, i * P + j] * trans_std * rw
            else:
                dy = 0.0
                dx = 0.0
            hstart = y1 + i * bin_h + dy
            hend = hstart + bin_h
            wstart = x1 + j * bin_w + dx
            wend = wstart + bin_w
            mask = ((ys[:, None] >= hstart) & (ys[:, None] < hend) &
                    (xs[None, :] >= wstart) & (xs[None, :] < wend))
            gi = jnp.clip(jnp.floor_divide(i * gs, P), 0, gs - 1)
            gj = jnp.clip(jnp.floor_divide(j * gs, P), 0, gs - 1)
            ch = (d * gs + gi) * gs + gj
            cnt = jnp.maximum(mask.sum(), 1)
            return jnp.where(mask, img[ch], 0.0).sum() / cnt

        dd, ii, jj = jnp.meshgrid(jnp.arange(output_dim),
                                  jnp.arange(P), jnp.arange(P),
                                  indexing="ij")
        vals = jax.vmap(one_bin)(dd.ravel(), ii.ravel(), jj.ravel())
        return vals.reshape(output_dim, P, P)

    if trans is None:
        return jax.vmap(lambda r: one_roi(r, None))(rois)
    return jax.vmap(one_roi)(rois, trans)


def _psroipooling(data, rois, spatial_scale=1.0, output_dim=0,
                  pooled_size=0, group_size=0):
    return _psroi_core(data, rois, spatial_scale, int(output_dim),
                       pooled_size, group_size or pooled_size)


register_op("_contrib_PSROIPooling", num_inputs=2,
            params=[Param("spatial_scale", float, 1.0),
                    Param("output_dim", int, 0),
                    Param("pooled_size", int, 0),
                    Param("group_size", int, 0)],
            aliases=("PSROIPooling",))(_psroipooling)


def _deformable_psroipooling(data, rois, trans=None, spatial_scale=1.0,
                             output_dim=0, pooled_size=0, group_size=0,
                             part_size=0, sample_per_part=1,
                             trans_std=0.0, no_trans=False):
    if no_trans or trans is None:
        return _psroi_core(data, rois, spatial_scale, int(output_dim),
                           pooled_size, group_size or pooled_size)
    P = int(pooled_size)
    R = rois.shape[0]
    tr = trans.reshape(R, 2, -1)
    return _psroi_core(data, rois, spatial_scale, int(output_dim),
                       pooled_size, group_size or pooled_size,
                       trans=tr, trans_std=trans_std)


register_op("_contrib_DeformablePSROIPooling", num_inputs=-1,
            params=[Param("spatial_scale", float, 1.0),
                    Param("output_dim", int, 0),
                    Param("pooled_size", int, 0),
                    Param("group_size", int, 0),
                    Param("part_size", int, 0),
                    Param("sample_per_part", int, 1),
                    Param("trans_std", float, 0.0),
                    Param("no_trans", bool, False)],
            aliases=("DeformablePSROIPooling",))(
    lambda data, rois, *t, **kw: _deformable_psroipooling(
        data, rois, t[0] if t else None, **kw))

# ---------------------------------------------------------------------------
# ROIAlign (contrib/roi_align.cc†, Mask R-CNN)
# ---------------------------------------------------------------------------


def _roi_align(data, rois, pooled_size=(7, 7), spatial_scale=1.0,
               sample_ratio=2, position_sensitive=False):
    """DIVERGENCE vs reference (contrib/roi_align.cc†): the reference's
    sample_ratio<=0 means ADAPTIVE sampling (ceil(roi_size/pooled) grid
    points per bin, data-dependent) — impossible under XLA static
    shapes, so it is approximated with a fixed 2x2 grid per bin (the
    value detection configs hard-code anyway).  position_sensitive
    (R-FCN-style channel splitting) is not implemented and raises
    rather than silently ignoring the flag (r3 advisor)."""
    if position_sensitive:
        raise MXNetError(
            "ROIAlign position_sensitive=True is not implemented; use "
            "_contrib_PSROIPooling for position-sensitive pooling")
    ph, pw = int(pooled_size[0]), int(pooled_size[1])
    N, C, H, W = data.shape
    s = int(sample_ratio) if int(sample_ratio) > 0 else 2

    def one_roi(roi):
        bidx = roi[0].astype(jnp.int32)
        x1 = roi[1] * spatial_scale
        y1 = roi[2] * spatial_scale
        x2 = roi[3] * spatial_scale
        y2 = roi[4] * spatial_scale
        rh = jnp.maximum(y2 - y1, 1.0)
        rw = jnp.maximum(x2 - x1, 1.0)
        bin_h = rh / ph
        bin_w = rw / pw
        # s*s sample points per bin, bilinear, averaged
        iy = (jnp.arange(ph)[:, None] +
              (jnp.arange(s)[None, :] + 0.5) / s).reshape(-1)  # (ph*s,)
        ix = (jnp.arange(pw)[:, None] +
              (jnp.arange(s)[None, :] + 0.5) / s).reshape(-1)
        yy = y1 + iy * bin_h                  # (ph*s,)
        xx = x1 + ix * bin_w                  # (pw*s,)
        grid_y = jnp.broadcast_to(yy[:, None], (ph * s, pw * s))
        grid_x = jnp.broadcast_to(xx[None, :], (ph * s, pw * s))
        vals = _bilinear_gather(data[bidx], grid_y, grid_x)
        vals = vals.reshape(C, ph, s, pw, s)
        return vals.mean(axis=(2, 4))

    return jax.vmap(one_roi)(rois)


register_op("_contrib_ROIAlign", num_inputs=2,
            params=[Param("pooled_size", tuple, ()),
                    Param("spatial_scale", float, 1.0),
                    Param("sample_ratio", int, 2),
                    Param("position_sensitive", bool, False)],
            aliases=("ROIAlign",))(_roi_align)

# ---------------------------------------------------------------------------
# AdaptiveAvgPooling2D + BilinearResize2D (contrib†)
# ---------------------------------------------------------------------------


def _adaptive_avg_pool(data, output_size=()):
    if not output_size:
        oh = ow = 1
    elif len(output_size) == 1:
        oh = ow = int(output_size[0])
    else:
        oh, ow = int(output_size[0]), int(output_size[1])
    N, C, H, W = data.shape

    def axis_weights(inp, out):
        # uniform averaging over [floor(i*inp/out), ceil((i+1)*inp/out))
        i = np.arange(out)
        starts = np.floor(i * inp / out).astype(int)
        ends = np.ceil((i + 1) * inp / out).astype(int)
        w = np.zeros((out, inp), np.float32)
        for r in range(out):
            w[r, starts[r]:ends[r]] = 1.0 / (ends[r] - starts[r])
        return jnp.asarray(w)

    wh = axis_weights(H, oh)                 # (oh, H)
    ww = axis_weights(W, ow)                 # (ow, W)
    # two small matmuls — MXU-friendly, no gather; exact averaging
    # wants true-f32 accumulation, not the TPU default's bf16 inputs
    prec = lax.Precision.HIGHEST \
        if jnp.dtype(data.dtype) == jnp.float32 else None
    return jnp.einsum("oh,nchw,pw->ncop", wh, data, ww,
                      precision=prec)


register_op("_contrib_AdaptiveAvgPooling2D",
            params=[Param("output_size", tuple, ())],
            aliases=("AdaptiveAvgPooling2D",))(_adaptive_avg_pool)


def _bilinear_resize(data, height=0, width=0, scale_height=None,
                     scale_width=None):
    N, C, H, W = data.shape
    oh = int(height) if height else int(round(H * scale_height))
    ow = int(width) if width else int(round(W * scale_width))
    # align_corners=True (the reference's convention)
    ys = jnp.linspace(0.0, H - 1.0, oh)
    xs = jnp.linspace(0.0, W - 1.0, ow)
    grid_y = jnp.broadcast_to(ys[:, None], (oh, ow))
    grid_x = jnp.broadcast_to(xs[None, :], (oh, ow))
    return jax.vmap(lambda img: _bilinear_gather(img, grid_y, grid_x))(
        data)


register_op("_contrib_BilinearResize2D",
            params=[Param("height", int, 0),
                    Param("width", int, 0),
                    Param("scale_height", float, None),
                    Param("scale_width", float, None)],
            aliases=("BilinearResize2D",))(_bilinear_resize)

# ---------------------------------------------------------------------------
# SyncBatchNorm (contrib/sync_batch_norm.cc†) — cross-device statistics.
# TPU-native: inside pjit/shard_map the mean/var reduce with
# lax.pmean over the data-parallel axis; outside (axis_name=None /
# unbound) it degrades to plain BatchNorm, which matches the
# reference's single-device behavior.
# ---------------------------------------------------------------------------


def _sync_batch_norm(x, gamma, beta, moving_mean, moving_var, eps=1e-3,
                     momentum=0.9, fix_gamma=True,
                     use_global_stats=False, output_mean_var=False,
                     ndev=1, key="", axis_name=""):
    ax = 1
    axes = tuple(i for i in range(x.ndim) if i != ax)
    x32 = x.astype(jnp.float32)
    if use_global_stats:
        mean = moving_mean.astype(jnp.float32)
        var = moving_var.astype(jnp.float32)
    else:
        mean = jnp.mean(x32, axis=axes)
        msq = jnp.mean(jnp.square(x32), axis=axes)
        if axis_name:
            mean = lax.pmean(mean, axis_name)
            msq = lax.pmean(msq, axis_name)
        var = msq - jnp.square(mean)
    shape = tuple(-1 if i == ax else 1 for i in range(x.ndim))
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    out = (x32 - mean.reshape(shape)) * lax.rsqrt(
        var.reshape(shape) + eps) * g.astype(jnp.float32).reshape(shape) \
        + beta.astype(jnp.float32).reshape(shape)
    return out.astype(x.dtype), mean, var


register_op("_contrib_SyncBatchNorm", num_inputs=5, num_outputs=3,
            params=[Param("eps", float, 1e-3),
                    Param("momentum", float, 0.9),
                    Param("fix_gamma", bool, True),
                    Param("use_global_stats", bool, False),
                    Param("output_mean_var", bool, False),
                    Param("ndev", int, 1),
                    Param("key", str, ""),
                    Param("axis_name", str, "")],
            aliases=("SyncBatchNorm",))(_sync_batch_norm)

# ---------------------------------------------------------------------------
# index_copy (contrib†)
# ---------------------------------------------------------------------------


def _index_copy(old, idx, new):
    return old.at[idx.astype(jnp.int32)].set(new.astype(old.dtype))


register_op("_contrib_index_copy", num_inputs=3)(_index_copy)

# ---------------------------------------------------------------------------
# INT8 quantized execution tier (src/operator/quantization/*†).
# Convention matches quantize/dequantize in detection_impl.py: int8 is
# symmetric [-127, 127] over [min, max]; int32 accumulators carry the
# product of input scales.  TPU: s8 x s8 -> s32 runs on the MXU via
# preferred_element_type.
# ---------------------------------------------------------------------------


def _qrange(dtype):
    if dtype == jnp.uint8:
        return 0.0, 255.0
    if dtype == jnp.int8:
        return -127.0, 127.0
    return -2147483647.0, 2147483647.0  # int32


def _scale_of(lo, hi, dtype):
    qmin, qmax = _qrange(dtype)
    return (qmax - qmin) / jnp.maximum(hi - lo, 1e-12)


def _requantize(data, min_range, max_range, min_calib_range=None,
                max_calib_range=None, out_type="int8"):
    """int32 -> int8/uint8 given the int32's float range
    (requantize†).  uint8 output uses the shifted range [0, hi]
    (zero-point 0, the post-ReLU convention of the uint8 tier)."""
    lo = min_range.reshape(())
    hi = max_range.reshape(())
    f = (data.astype(jnp.float32) /
         _scale_of(lo, hi, jnp.int32))       # back to float
    if min_calib_range is not None:
        lo = jnp.asarray(min_calib_range, jnp.float32)
        hi = jnp.asarray(max_calib_range, jnp.float32)
        if out_type == "uint8":
            lo = jnp.maximum(lo, 0.0)
    elif out_type == "uint8":
        lo = jnp.asarray(0.0, jnp.float32)
        hi = jnp.maximum(f.max(), 1e-12)
    else:
        amax = jnp.maximum(jnp.abs(f).max(), 1e-12)
        lo, hi = -amax, amax
    if out_type == "uint8":
        scale = _scale_of(lo, hi, jnp.uint8)
        q = jnp.clip(jnp.round(f * scale), 0, 255).astype(jnp.uint8)
    else:
        scale = _scale_of(lo, hi, jnp.int8)
        q = jnp.clip(jnp.round(f * scale), -127, 127).astype(jnp.int8)
    return q, jnp.asarray(lo, jnp.float32), jnp.asarray(hi, jnp.float32)


register_op("_contrib_requantize", num_inputs=3, num_outputs=3,
            params=[Param("min_calib_range", float, None),
                    Param("max_calib_range", float, None),
                    Param("out_type", str, "int8",
                          enum=("int8", "uint8"))],
            aliases=("requantize",), differentiable=False)(_requantize)


def _q_out_range(min_d, max_d, min_w, max_w, in_dtype, w_dtype):
    """float value of one int32 accumulator unit = 1/(sd*sw); the int32
    range bound below mirrors the reference's
    GetQuantizedElemwiseOutputRange logic."""
    sd = _scale_of(min_d.reshape(()), max_d.reshape(()), in_dtype)
    sw = _scale_of(min_w.reshape(()), max_w.reshape(()), w_dtype)
    unit = 1.0 / (sd * sw)
    bound = 2147483647.0 * unit
    return unit, -bound, bound


def _quantized_conv(data, weight, *rest, kernel=(), stride=None,
                    dilate=None, pad=None, num_filter=0, num_group=1,
                    no_bias=True, layout=None):
    """int8 conv with int32 accumulation (quantized_conv†).  Inputs:
    data(int8/uint8), weight(int8), [bias(int8)], then min/max scalars
    for each tensor in the same order.  Returns (int32, min, max)."""
    n_tensors = 2 if no_bias else 3
    if len(rest) != (0 if no_bias else 1) + 2 * n_tensors:
        raise MXNetError(
            f"quantized_conv expects {n_tensors} tensors + "
            f"{2 * n_tensors} ranges")
    if no_bias:
        bias = None
        mins_maxes = rest
    else:
        bias = rest[0]
        mins_maxes = rest[1:]
    min_d, max_d, min_w, max_w = mins_maxes[:4]
    nd = len(kernel)
    stride_t = _tuple(stride, nd)
    dilate_t = _tuple(dilate, nd)
    pad_t = _tuple(pad, nd) if pad is not None else (0,) * nd
    from .ops_impl import _CONV_DN
    layout = layout or {1: "NCW", 2: "NCHW", 3: "NCDHW"}[nd]
    if data.dtype == jnp.uint8:
        # uint8 activations use the shifted-range-with-zero-point-0
        # convention (min_data == 0, the post-ReLU default — the
        # reference's MKLDNN u8s8s32 tier ditto), so the accumulator
        # stays scale-only.  conv_general_dilated requires matching
        # operand dtypes; int16 holds u8 and s8 exactly.  A blind
        # .astype(int8) would wrap 128..255 negative (r3 advisor).
        lhs = data.astype(jnp.int16)
        rhs = weight.astype(jnp.int16)
    elif data.dtype == jnp.int8:
        lhs = data
        rhs = weight.astype(jnp.int8)
    else:
        raise MXNetError(
            f"quantized_conv expects int8/uint8 data, got {data.dtype}")
    out = lax.conv_general_dilated(
        lhs, rhs,
        window_strides=stride_t, padding=[(p, p) for p in pad_t],
        rhs_dilation=dilate_t,
        dimension_numbers=_CONV_DN[layout],
        feature_group_count=num_group,
        preferred_element_type=jnp.int32)
    unit, lo, hi = _q_out_range(min_d, max_d, min_w, max_w,
                                data.dtype, jnp.int8)
    if bias is not None:
        min_b, max_b = mins_maxes[4:6]
        sb = _scale_of(min_b.reshape(()), max_b.reshape(()), jnp.int8)
        # rescale int8 bias into int32 accumulator units
        b32 = jnp.round(bias.astype(jnp.float32) / sb / unit)
        out = out + b32.astype(jnp.int32).reshape(1, -1, *([1] * nd))
    return out, jnp.asarray(lo, jnp.float32), jnp.asarray(hi, jnp.float32)


register_op("_contrib_quantized_conv", num_inputs=-1, num_outputs=3,
            params=[Param("kernel", tuple, ()),
                    Param("stride", tuple, None),
                    Param("dilate", tuple, None),
                    Param("pad", tuple, None),
                    Param("num_filter", int, 0),
                    Param("num_group", int, 1),
                    Param("no_bias", bool, True),
                    Param("layout", str, None)],
            aliases=("quantized_conv",),
            differentiable=False)(_quantized_conv)


def _quantized_fully_connected(data, weight, *rest, num_hidden=0,
                               no_bias=True, flatten=True):
    if no_bias:
        bias = None
        mins_maxes = rest
    else:
        bias = rest[0]
        mins_maxes = rest[1:]
    min_d, max_d, min_w, max_w = mins_maxes[:4]
    if data.dtype not in (jnp.int8, jnp.uint8):
        raise MXNetError(
            f"quantized_fully_connected expects int8/uint8 data, got "
            f"{data.dtype}")
    x = data.reshape(data.shape[0], -1) if flatten else data
    # dot_general takes mixed u8 x s8 operands directly (uint8 keeps
    # the zero-point-0 convention — see _quantized_conv); casting
    # uint8 through int8 would wrap 128..255 negative (r3 advisor)
    out = lax.dot_general(
        x, weight.astype(jnp.int8),
        (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    unit, lo, hi = _q_out_range(min_d, max_d, min_w, max_w,
                                data.dtype, jnp.int8)
    if bias is not None:
        min_b, max_b = mins_maxes[4:6]
        sb = _scale_of(min_b.reshape(()), max_b.reshape(()), jnp.int8)
        b32 = jnp.round(bias.astype(jnp.float32) / sb / unit)
        out = out + b32.astype(jnp.int32)
    return out, jnp.asarray(lo, jnp.float32), jnp.asarray(hi, jnp.float32)


register_op("_contrib_quantized_fully_connected", num_inputs=-1,
            num_outputs=3,
            params=[Param("num_hidden", int, 0),
                    Param("no_bias", bool, True),
                    Param("flatten", bool, True)],
            aliases=("quantized_fully_connected",),
            differentiable=False)(_quantized_fully_connected)


def _quantized_pooling(data, min_data, max_data, kernel=(),
                       pool_type="max", global_pool=False, stride=None,
                       pad=None):
    from .ops_impl import _pooling
    # max/avg pooling commute with the affine quantization map, so the
    # int8 domain result equals quantize(pool(dequantize)) with the
    # SAME range — no requantization step needed
    out = _pooling(data.astype(jnp.float32), kernel=kernel,
                   pool_type=pool_type, global_pool=global_pool,
                   stride=stride, pad=pad)
    out = jnp.round(out).astype(data.dtype) if pool_type == "avg" \
        else out.astype(data.dtype)
    return out, min_data.reshape(()), max_data.reshape(())


register_op("_contrib_quantized_pooling", num_inputs=3, num_outputs=3,
            params=[Param("kernel", tuple, ()),
                    Param("pool_type", str, "max"),
                    Param("global_pool", bool, False),
                    Param("stride", tuple, None),
                    Param("pad", tuple, None)],
            aliases=("quantized_pooling",),
            differentiable=False)(_quantized_pooling)


def _quantized_flatten(data, min_data, max_data):
    return (data.reshape(data.shape[0], -1), min_data.reshape(()),
            max_data.reshape(()))


register_op("_contrib_quantized_flatten", num_inputs=3, num_outputs=3,
            aliases=("quantized_flatten",),
            differentiable=False)(_quantized_flatten)


def _quantized_act(data, min_data, max_data, act_type="relu"):
    if act_type != "relu":
        raise MXNetError("quantized_act supports relu only (the "
                         "reference's quantized_activation ditto)")
    # symmetric int8: float 0 is int 0
    out = jnp.maximum(data, 0).astype(data.dtype)
    return out, min_data.reshape(()), max_data.reshape(())


register_op("_contrib_quantized_act", num_inputs=3, num_outputs=3,
            params=[Param("act_type", str, "relu")],
            aliases=("quantized_act",),
            differentiable=False)(_quantized_act)


def _quantized_concat(*args, num_args=0, dim=1):
    n = (len(args)) // 3
    datas = args[:n]
    mins = [m.reshape(()) for m in args[n::2]]
    maxs = [m.reshape(()) for m in args[n + 1::2]]
    out_min = jnp.stack(mins).min()
    out_max = jnp.stack(maxs).max()
    scale_out = _scale_of(out_min, out_max, jnp.int8)
    parts = []
    for d, lo, hi in zip(datas, mins, maxs):
        s = _scale_of(lo, hi, jnp.int8)
        parts.append(jnp.clip(jnp.round(
            d.astype(jnp.float32) * (scale_out / s)), -127, 127)
            .astype(jnp.int8))
    return jnp.concatenate(parts, axis=dim), out_min, out_max


register_op("_contrib_quantized_concat", num_inputs=-1, num_outputs=3,
            params=[Param("num_args", int, 0), Param("dim", int, 1)],
            aliases=("quantized_concat",),
            differentiable=False)(_quantized_concat)


# ---------------------------------------------------------------------------
# Switch-MoE feed-forward (new capability; parallel/moe.py is the
# functional core — expert parallelism engages when the expert-axis
# parameters are sharded P("ep") via param_spec_fn, GSPMD propagates)
# ---------------------------------------------------------------------------


def _contrib_moe_ffn(data, gate_w, w1, b1, w2, b2,
                     capacity_factor=1.25, activation="relu"):
    from ..parallel.moe import moe_ffn  # lazy: avoids an import cycle
    act = {"relu": jax.nn.relu, "gelu": jax.nn.gelu,
           "tanh": jnp.tanh}.get(activation)
    if act is None:
        raise MXNetError(f"MoEFFN activation {activation!r} not in "
                         f"relu/gelu/tanh")
    y, aux = moe_ffn(data, gate_w, w1, b1, w2, b2,
                     capacity_factor=float(capacity_factor),
                     activation=act)
    return y, aux


register_op("_contrib_MoEFFN", num_inputs=6, num_outputs=2,
            params=[Param("capacity_factor", float, 1.25),
                    Param("activation", str, "relu",
                          enum=("relu", "gelu", "tanh"))],
            aliases=("MoEFFN",))(_contrib_moe_ffn)


def _routed_experts_op(data, router, w_in, w_out, length, *extra, top_k=2,
                       norm_topk=True, score="softmax", route_scale=1.0,
                       held_first=0, held_count=0, bias=False, shared=False):
    """Sparse SwiGLU feed-forward of a served decoder: top-k routing
    with no capacity and no dropped token (``parallel.moe
    .routed_experts``).  ``data``: (B, T, D), of which row b's first
    ``length_b`` tokens are valid (the rest are routed nowhere);
    ``router``: (D, E); ``w_in``: (E', D, 2 F) gate over up; ``w_out``:
    (E', F, D).  After the lengths come, where ``bias``, the selection
    bias (E,), and, where ``shared``, a shared expert's ``shared_in``
    (D, 2 F') and ``shared_out`` (F', D).  ``held_count`` > 0 makes the
    layer this device's share of an expert-parallel one: experts
    ``held_first ..`` of the E routed over (E' = ``held_count``).
    Returns ``(y (B, T, D) in data's dtype, counts int32)``: how many
    held experts this call gave a token, (1,), or with a share that and
    the assignments that landed on held experts, (2,)."""
    from ..parallel.moe import routed_experts  # lazy: avoids an import cycle
    valid = jnp.arange(data.shape[1], dtype=jnp.int32)[None, :] \
        < jnp.asarray(length).astype(jnp.int32)[:, None]
    if len(extra) != int(bool(bias)) + 2 * int(bool(shared)):
        raise MXNetError(f"routed_experts: {len(extra)} inputs after the "
                         f"lengths where bias={bias} and shared={shared}")
    y, counts = routed_experts(
        data, router, w_in, w_out, valid, top_k=int(top_k),
        renormalise=bool(norm_topk), score=score,
        bias=extra[0] if bias else None, scale=float(route_scale),
        shared=tuple(extra[-2:]) if shared else None,
        held=(int(held_first), int(held_count)) if held_count else None)
    return y.astype(data.dtype), counts.reshape(-1)


register_op("routed_experts", num_inputs=-1, num_outputs=2,
            differentiable=False,
            params=[Param("top_k", int, 2, lower=1),
                    Param("norm_topk", bool, True),
                    Param("score", str, "softmax",
                          enum=("softmax", "sigmoid")),
                    Param("route_scale", float, 1.0),
                    Param("held_first", int, 0, lower=0),
                    Param("held_count", int, 0, lower=0),
                    Param("bias", bool, False),
                    Param("shared", bool, False)],
            doc=_routed_experts_op.__doc__)(_routed_experts_op)
