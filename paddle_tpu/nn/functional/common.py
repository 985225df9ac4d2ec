"""nn.functional core ops: linear, conv, pooling, dropout, embedding, attention,
interpolate (reference: ``python/paddle/nn/functional/{common,conv,pooling,
input}.py`` — SURVEY.md §2.2). All map to lax/XLA; conv/matmul hit the MXU."""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ...framework.core import Tensor
from ...framework import random as prandom
from ...autograd.tape import apply, defop


# ---------------------------------------------------------------------------
# linear / embedding
# ---------------------------------------------------------------------------

def linear(x, weight, bias=None, name=None):
    """paddle linear: weight is [in, out] (note: transposed vs torch)."""
    if bias is None:
        return apply(lambda a, w: a @ w, x, weight, op_name="linear")
    return apply(lambda a, w, b: a @ w + b, x, weight, bias, op_name="linear")


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    def fn(w, idx):
        out = jnp.take(w, idx, axis=0)
        if padding_idx is not None:
            mask = (idx == padding_idx)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out

    idx = x._data if isinstance(x, Tensor) else jnp.asarray(x)
    return apply(lambda w: fn(w, idx), weight, op_name="embedding")


def one_hot(x, num_classes, name=None):
    from ...ops.manipulation import one_hot as _oh
    return _oh(x, num_classes)


# ---------------------------------------------------------------------------
# dropout
# ---------------------------------------------------------------------------

def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training and p > 0.0:
            return apply(lambda a: a * (1.0 - p), x, op_name="dropout")
        return x if isinstance(x, Tensor) else Tensor(x)
    key = prandom.next_key()

    def fn(a):
        shape = list(a.shape)
        if axis is not None:
            axes = [axis] if isinstance(axis, int) else list(axis)
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1.0 - p), 0.0).astype(a.dtype)
        return jnp.where(keep, a, 0.0).astype(a.dtype)

    return apply(fn, x, op_name="dropout")


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    axis = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p, axis=axis, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    axis = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p, axis=axis, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    if not training or p == 0.0:
        return x
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    key = prandom.next_key()

    def fn(a):
        keep = jax.random.bernoulli(key, 1.0 - p, a.shape)
        q = 1.0 - p
        a_coef = (q + alpha_p ** 2 * q * p) ** -0.5
        b_coef = -a_coef * alpha_p * p
        return a_coef * jnp.where(keep, a, alpha_p) + b_coef

    return apply(fn, x, op_name="alpha_dropout")


# ---------------------------------------------------------------------------
# conv
# ---------------------------------------------------------------------------

def _conv_padding(padding, ndim, strides=None, ksize=None, dilation=None):
    """paddle padding: int, list, 'SAME'/'VALID'."""
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, int):
        return [(padding, padding)] * ndim
    pads = list(padding)
    if len(pads) == ndim and all(isinstance(p, int) for p in pads):
        return [(p, p) for p in pads]
    if len(pads) == 2 * ndim:
        return [(pads[2 * i], pads[2 * i + 1]) for i in range(ndim)]
    return [tuple(p) for p in pads]


def _tuple(v, n):
    return tuple(v) if isinstance(v, (list, tuple)) else (v,) * n


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    nd = 2
    strides = _tuple(stride, nd)
    dil = _tuple(dilation, nd)
    pad = _conv_padding(padding, nd)
    dn = ("NCHW", "OIHW", "NCHW") if data_format == "NCHW" else ("NHWC", "HWIO", "NHWC")

    def fn(a, w, *b):
        if data_format != "NCHW":
            w = jnp.transpose(w, (2, 3, 1, 0))
        out = lax.conv_general_dilated(
            a, w, window_strides=strides, padding=pad,
            rhs_dilation=dil, dimension_numbers=dn, feature_group_count=groups,
            preferred_element_type=None)
        if b:
            bias_shape = [1, -1, 1, 1] if data_format == "NCHW" else [1, 1, 1, -1]
            out = out + b[0].reshape(bias_shape)
        return out

    args = (x, weight) + ((bias,) if bias is not None else ())
    return apply(fn, *args, op_name="conv2d")


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    strides = _tuple(stride, 1)
    dil = _tuple(dilation, 1)
    pad = _conv_padding(padding, 1)
    dn = ("NCH", "OIH", "NCH") if data_format == "NCL" else ("NHC", "HIO", "NHC")

    def fn(a, w, *b):
        if data_format != "NCL":
            # weights come in Paddle [out, in, k] layout; lax expects HIO here
            w = jnp.transpose(w, (2, 1, 0))
        out = lax.conv_general_dilated(a, w, window_strides=strides, padding=pad,
                                       rhs_dilation=dil, dimension_numbers=dn,
                                       feature_group_count=groups)
        if b:
            shape = [1, -1, 1] if data_format == "NCL" else [1, 1, -1]
            out = out + b[0].reshape(shape)
        return out

    args = (x, weight) + ((bias,) if bias is not None else ())
    return apply(fn, *args, op_name="conv1d")


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    strides = _tuple(stride, 3)
    dil = _tuple(dilation, 3)
    pad = _conv_padding(padding, 3)
    dn = ("NCDHW", "OIDHW", "NCDHW")

    def fn(a, w, *b):
        out = lax.conv_general_dilated(a, w, window_strides=strides, padding=pad,
                                       rhs_dilation=dil, dimension_numbers=dn,
                                       feature_group_count=groups)
        if b:
            out = out + b[0].reshape([1, -1, 1, 1, 1])
        return out

    args = (x, weight) + ((bias,) if bias is not None else ())
    return apply(fn, *args, op_name="conv3d")


def _conv_transpose_nd(x, weight, bias, nd, stride, padding, output_padding,
                       groups, dilation, output_size, op_name):
    """Shared N-D transposed convolution (paddle weight layout
    [in_c, out_c/groups, *k]); ``output_size`` resolves the stride
    ambiguity by overriding the per-dim output padding."""
    strides = _tuple(stride, nd)
    dil = _tuple(dilation, nd)
    opad = list(_tuple(output_padding, nd))
    padding_ = padding
    dn_map = {1: ("NCH", "OIH", "NCH"), 2: ("NCHW", "OIHW", "NCHW"),
              3: ("NCDHW", "OIDHW", "NCDHW")}
    if output_size is not None:
        if isinstance(padding_, str):
            raise NotImplementedError(
                "output_size with string padding is unsupported")
        if hasattr(output_size, "tolist"):
            output_size = output_size.tolist()
        out_sp = [int(s) for s in tuple(output_size)[-nd:]]
        p = _conv_padding(padding_, nd)
        kshape = weight.shape[2:]
        in_sp = x.shape[2:2 + nd]
        for i in range(nd):
            base = ((int(in_sp[i]) - 1) * strides[i] - p[i][0] - p[i][1]
                    + dil[i] * (int(kshape[i]) - 1) + 1)
            extra = out_sp[i] - base
            if extra < 0 or extra >= strides[i] + max(0, dil[i] - 1):
                raise ValueError(
                    f"output_size[{i}]={out_sp[i]} unreachable "
                    f"(base {base}, stride {strides[i]})")
            opad[i] = extra

    def fn(a, w, *b):
        kshape = w.shape[2:]
        if isinstance(padding_, str):
            pad = padding_.upper()
        else:
            p = _conv_padding(padding_, nd)
            # transposed conv padding math (gradient-style):
            # pad_t = dil*(k-1) - pad, high side + output_padding
            pad = [(dil[i] * (kshape[i] - 1) - p[i][0],
                    dil[i] * (kshape[i] - 1) - p[i][1] + opad[i])
                   for i in range(nd)]
        w_flip = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
        if groups == 1:
            w_t = jnp.swapaxes(w_flip, 0, 1)   # -> [out_c, in_c, *k]
        else:
            ic, ocg = w.shape[0], w.shape[1]
            w_g = w_flip.reshape(groups, ic // groups, ocg, *kshape)
            w_t = jnp.swapaxes(w_g, 1, 2).reshape(groups * ocg, ic // groups,
                                                  *kshape)
        out = lax.conv_general_dilated(
            a, w_t, window_strides=(1,) * nd, padding=pad,
            lhs_dilation=strides, rhs_dilation=dil,
            dimension_numbers=dn_map[nd], feature_group_count=groups)
        if b:
            out = out + b[0].reshape([1, -1] + [1] * nd)
        return out

    args = (x, weight) + ((bias,) if bias is not None else ())
    return apply(fn, *args, op_name=op_name)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0,
                     groups=1, dilation=1, data_format="NCHW", output_size=None,
                     name=None):
    return _conv_transpose_nd(x, weight, bias, 2, stride, padding,
                              output_padding, groups, dilation, output_size,
                              "conv2d_transpose")


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------

def _pool(x, ksize, strides, padding, reducer, init, data_format="NCHW",
          ceil_mode=False, norm=None, count_include_pad=True):
    nd = len(ksize)

    def fn(a):
        channels_first = data_format in ("NCHW", "NCL", "NCDHW")
        spatial = a.shape[2:2 + nd] if channels_first else a.shape[1:1 + nd]
        if isinstance(padding, str):
            spad = [(0, 0)] * nd if padding.upper() == "VALID" else None
            if spad is None:  # SAME
                spad = []
                for i in range(nd):
                    out_i = -(-spatial[i] // strides[i])
                    tot = max((out_i - 1) * strides[i] + ksize[i] - spatial[i], 0)
                    spad.append((tot // 2, tot - tot // 2))
        else:
            spad = [tuple(p) for p in padding]
        counted_pad = list(spad)  # pad that counts toward avg when include_pad
        if ceil_mode:
            # extend the high side so the last partial window is produced
            spad = list(spad)
            for i in range(nd):
                eff = spatial[i] + spad[i][0] + spad[i][1]
                rem = (eff - ksize[i]) % strides[i]
                if rem:
                    spad[i] = (spad[i][0], spad[i][1] + strides[i] - rem)
        if channels_first:
            window = (1, 1) + tuple(ksize)
            strd = (1, 1) + tuple(strides)
            pad = [(0, 0), (0, 0)] + spad
            cpad = [(0, 0), (0, 0)] + counted_pad
        else:
            window = (1,) + tuple(ksize) + (1,)
            strd = (1,) + tuple(strides) + (1,)
            pad = [(0, 0)] + spad + [(0, 0)]
            cpad = [(0, 0)] + counted_pad + [(0, 0)]
        out = lax.reduce_window(a, init, reducer, window, strd, pad)
        if norm == "avg":
            if count_include_pad and not ceil_mode \
                    and all(p == (0, 0) for p in spad):
                out = out / float(np.prod(ksize))
            else:
                # count only real elements (+ user padding when include_pad):
                # reduce a ones-array padded the same way
                ones = jnp.ones_like(a)
                if count_include_pad:
                    ones = jnp.pad(ones, cpad, constant_values=1.0)
                    extra = [(p[0] - c[0], p[1] - c[1])
                             for p, c in zip(pad, cpad)]
                    counts = lax.reduce_window(ones, 0.0, lax.add, window, strd,
                                               extra)
                else:
                    counts = lax.reduce_window(ones, 0.0, lax.add, window, strd,
                                               pad)
                out = out / counts
        return out

    return apply(fn, x, op_name="pool")


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCHW", name=None):
    ksize = _tuple(kernel_size, 2)
    strides = _tuple(stride, 2) if stride is not None else ksize
    if return_mask:
        from .extras import _max_pool_with_index, _check_index_pool_args
        _check_index_pool_args(padding, ceil_mode, data_format, "NCHW")
        return _max_pool_with_index(x, ksize, strides, _tuple(padding, 2))
    pad = _conv_padding(padding, 2) if not isinstance(padding, str) else padding
    return _pool(x, ksize, strides, pad, lax.max, -jnp.inf, data_format, ceil_mode)


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW", name=None):
    ksize = _tuple(kernel_size, 2)
    strides = _tuple(stride, 2) if stride is not None else ksize
    pad = _conv_padding(padding, 2) if not isinstance(padding, str) else padding
    return _avg_pool_impl(x, ksize, strides, pad, data_format, ceil_mode,
                          exclusive, divisor_override)


def _avg_pool_impl(x, ksize, strides, pad, data_format, ceil_mode,
                   exclusive, divisor_override):
    """Shared avg-pool tail: divisor_override = fixed divisor (window
    sums / divisor), else true mean with the exclusive/include-pad rule."""
    if divisor_override:
        sums = _pool(x, ksize, strides, pad, lax.add, 0.0, data_format,
                     ceil_mode)
        return apply(lambda s: s / float(divisor_override), sums,
                     op_name="avg_pool_divisor")
    return _pool(x, ksize, strides, pad, lax.add, 0.0, data_format,
                 ceil_mode, norm="avg", count_include_pad=not exclusive)


def max_pool1d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, name=None):
    ksize = _tuple(kernel_size, 1)
    strides = _tuple(stride, 1) if stride is not None else ksize
    if return_mask:
        from .extras import _max_pool_with_index, _check_index_pool_args
        _check_index_pool_args(padding, ceil_mode, "NCL", "NCL")
        return _max_pool_with_index(x, ksize, strides, _tuple(padding, 1))
    pad = _conv_padding(padding, 1) if not isinstance(padding, str) else padding
    return _pool(x, ksize, strides, pad, lax.max, -jnp.inf, "NCL", ceil_mode)


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, name=None):
    ksize = _tuple(kernel_size, 1)
    strides = _tuple(stride, 1) if stride is not None else ksize
    pad = _conv_padding(padding, 1) if not isinstance(padding, str) else padding
    return _pool(x, ksize, strides, pad, lax.add, 0.0, "NCL", ceil_mode, norm="avg",
                 count_include_pad=not exclusive)


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    out_hw = _tuple(output_size, 2)

    def fn(a):
        h, w = (a.shape[2], a.shape[3]) if data_format == "NCHW" else (a.shape[1], a.shape[2])
        oh = out_hw[0] or h
        ow = out_hw[1] or w
        if h % oh == 0 and w % ow == 0:
            kh, kw = h // oh, w // ow
            if data_format == "NCHW":
                r = a.reshape(a.shape[0], a.shape[1], oh, kh, ow, kw)
                return r.mean(axis=(3, 5))
            r = a.reshape(a.shape[0], oh, kh, ow, kw, a.shape[3])
            return r.mean(axis=(2, 4))
        # general case: integral-image style via per-output-bin mean
        hi = [int(np.floor(i * h / oh)) for i in range(oh)]
        hie = [int(np.ceil((i + 1) * h / oh)) for i in range(oh)]
        wi = [int(np.floor(j * w / ow)) for j in range(ow)]
        wie = [int(np.ceil((j + 1) * w / ow)) for j in range(ow)]
        rows = []
        for i in range(oh):
            cols = []
            for j in range(ow):
                if data_format == "NCHW":
                    cols.append(a[:, :, hi[i]:hie[i], wi[j]:wie[j]].mean(axis=(2, 3)))
                else:
                    cols.append(a[:, hi[i]:hie[i], wi[j]:wie[j], :].mean(axis=(1, 2)))
            rows.append(jnp.stack(cols, axis=-1))
        out = jnp.stack(rows, axis=-2)
        return out

    return apply(fn, x, op_name="adaptive_avg_pool2d")


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    out_hw = _tuple(output_size, 2)

    def fn(a):
        h, w = a.shape[2], a.shape[3]
        oh, ow = out_hw[0] or h, out_hw[1] or w
        if h % oh == 0 and w % ow == 0:
            kh, kw = h // oh, w // ow
            r = a.reshape(a.shape[0], a.shape[1], oh, kh, ow, kw)
            return r.max(axis=(3, 5))
        raise NotImplementedError("adaptive_max_pool2d with non-divisible sizes")

    return apply(fn, x, op_name="adaptive_max_pool2d")


def adaptive_avg_pool1d(x, output_size, name=None):
    def fn(a):
        l = a.shape[2]
        ol = output_size
        if l % ol == 0:
            return a.reshape(a.shape[0], a.shape[1], ol, l // ol).mean(axis=3)
        raise NotImplementedError

    return apply(fn, x, op_name="adaptive_avg_pool1d")


# ---------------------------------------------------------------------------
# padding / upsample
# ---------------------------------------------------------------------------

def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):
    from ...ops.manipulation import pad as _pad
    return _pad(x, pad, mode, value, data_format)


def _bilinear_align_corners(a, oh, ow):
    """Bilinear resize with align_corners=True grid (src = i*(H-1)/(OH-1));
    jax.image.resize only does the half-pixel convention."""
    h, w = a.shape[2], a.shape[3]
    ys = jnp.linspace(0.0, h - 1.0, oh)
    xs = jnp.linspace(0.0, w - 1.0, ow)
    y0 = jnp.floor(ys).astype(jnp.int32)
    x0 = jnp.floor(xs).astype(jnp.int32)
    y1 = jnp.minimum(y0 + 1, h - 1)
    x1 = jnp.minimum(x0 + 1, w - 1)
    wy = (ys - y0).astype(a.dtype)[:, None]      # [oh, 1]
    wx = (xs - x0).astype(a.dtype)[None, :]      # [1, ow]
    tl = a[:, :, y0][:, :, :, x0]
    tr = a[:, :, y0][:, :, :, x1]
    bl = a[:, :, y1][:, :, :, x0]
    br = a[:, :, y1][:, :, :, x1]
    top = tl * (1 - wx) + tr * wx
    bot = bl * (1 - wx) + br * wx
    return top * (1 - wy) + bot * wy


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW", name=None):
    # 3-D (NCL/NWC) input: treat length as W with a singleton H, then squeeze.
    if x.ndim == 3:
        chan_last = data_format in ("NWC", "NLC")
        xs = x.unsqueeze(2) if not chan_last else x.unsqueeze(1)
        size2 = [1, int(size[0] if isinstance(size, (list, tuple)) else size)] \
            if size is not None else None
        sf = scale_factor
        if sf is not None:
            sf = [1, sf[0] if isinstance(sf, (list, tuple)) else sf]
        mode2 = "bilinear" if mode == "linear" else mode
        out = interpolate(xs, size2, sf, mode2, align_corners, align_mode,
                          "NCHW" if not chan_last else "NHWC")
        return out.squeeze(2) if not chan_last else out.squeeze(1)

    def fn(a):
        n, c, h, w = a.shape if data_format == "NCHW" else \
            (a.shape[0], a.shape[3], a.shape[1], a.shape[2])
        if size is not None:
            oh, ow = int(size[0]), int(size[1])
        else:
            sf = scale_factor if isinstance(scale_factor, (list, tuple)) \
                else [scale_factor, scale_factor]
            oh, ow = int(h * sf[0]), int(w * sf[1])
        if data_format != "NCHW":
            a = jnp.transpose(a, (0, 3, 1, 2))
        if mode == "nearest":
            ridx = (jnp.arange(oh) * h // oh).astype(jnp.int32)
            cidx = (jnp.arange(ow) * w // ow).astype(jnp.int32)
            out = a[:, :, ridx][:, :, :, cidx]
        elif mode in ("bilinear", "linear"):
            if align_corners and oh > 1 and ow > 1:
                out = _bilinear_align_corners(a, oh, ow)
            else:
                out = jax.image.resize(a, (a.shape[0], a.shape[1], oh, ow),
                                       method="linear")
        elif mode == "bicubic":
            out = jax.image.resize(a, (a.shape[0], a.shape[1], oh, ow), method="cubic")
        else:
            raise NotImplementedError(mode)
        if data_format != "NCHW":
            out = jnp.transpose(out, (0, 2, 3, 1))
        return out

    return apply(fn, x, op_name="interpolate")


def upsample(x, size=None, scale_factor=None, mode="nearest", align_corners=False,
             align_mode=0, data_format="NCHW", name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode,
                       data_format)


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    r = upscale_factor

    def fn(a):
        n, c, h, w = a.shape
        a = a.reshape(n, c // (r * r), r, r, h, w)
        a = jnp.transpose(a, (0, 1, 4, 2, 5, 3))
        return a.reshape(n, c // (r * r), h * r, w * r)

    return apply(fn, x, op_name="pixel_shuffle")


def channel_shuffle(x, groups, data_format="NCHW", name=None):
    """reference: ``paddle.nn.functional.channel_shuffle``."""
    def fn(a):
        if data_format == "NHWC":
            n, h, w, c = a.shape
            a = a.reshape(n, h, w, groups, c // groups)
            a = jnp.swapaxes(a, 3, 4)
            return a.reshape(n, h, w, c)
        n, c, h, w = a.shape
        a = a.reshape(n, groups, c // groups, h, w)
        a = jnp.swapaxes(a, 1, 2)
        return a.reshape(n, c, h, w)

    return apply(fn, x, op_name="channel_shuffle")


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    ks = _tuple(kernel_sizes, 2)
    st = _tuple(strides, 2)
    pd = _tuple(paddings, 2)
    dl = _tuple(dilations, 2)

    def fn(a):
        n, c, h, w = a.shape
        a = jnp.pad(a, [(0, 0), (0, 0), (pd[0], pd[0]), (pd[1], pd[1])])
        oh = (a.shape[2] - (dl[0] * (ks[0] - 1) + 1)) // st[0] + 1
        ow = (a.shape[3] - (dl[1] * (ks[1] - 1) + 1)) // st[1] + 1
        patches = []
        for i in range(ks[0]):
            for j in range(ks[1]):
                patches.append(a[:, :, i * dl[0]: i * dl[0] + oh * st[0]: st[0],
                                 j * dl[1]: j * dl[1] + ow * st[1]: st[1]])
        out = jnp.stack(patches, axis=2)  # [n, c, k*k, oh, ow]
        return out.reshape(n, c * ks[0] * ks[1], oh * ow)

    return apply(fn, x, op_name="unfold")


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None):
    """paddle.nn.functional.scaled_dot_product_attention.

    Layout [batch, seq, heads, head_dim] (paddle flash-attn convention —
    reference wires FA2 as a phi kernel, SURVEY.md §2.1). Causal/full attention
    without mask/dropout dispatches to the Pallas flash-attention kernel
    (``paddle_tpu/ops/pallas/flash_attention.py``) on TPU — FA2's phi-kernel
    role; gate with FLAGS_use_flash_attention. Masked/dropout paths use XLA.
    """
    from ...flags import flag as _flag
    use_flash = (_flag("FLAGS_use_flash_attention", True)
                 and attn_mask is None
                 and (dropout_p == 0.0 or not training)
                 and jax.default_backend() == "tpu"
                 and query.shape[1] >= 128 and query.shape[-1] % 64 == 0)
    if use_flash:
        from ...ops.pallas import flash_attention as _fa
        # bottom-right causal alignment when sq != sk (KV-cache decode):
        # local query i sits at global position (sk - sq) + i
        q_off = key.shape[1] - query.shape[1]

        def flash_fn(q, k, v):
            from ...distributed.mesh import shard_attention_kernel
            kernel = functools.partial(_fa, causal=is_causal,
                                       q_offset=q_off, interpret=False)
            return shard_attention_kernel(kernel, q, k, v)

        return apply(flash_fn, query, key, value, op_name="flash_attn")

    dk = prandom.next_key() if (dropout_p > 0.0 and training) else None

    # long-sequence memory safety: with flash unavailable (disabled
    # flag, CPU, short/odd head_dim) a no-mask/no-dropout attention at
    # seq >= 4096 would materialize an S×S fp32 logits tensor — route it
    # through the pure-XLA tier dispatcher instead (flash-like memory:
    # per-chunk remat + causal kv-prefix trim, or the scan tiers per
    # PADDLE_TPU_XFA)
    # trigger on EITHER the seq product (any single [sq, sk] logits plane
    # at 4096^2 is flash territory regardless of b*h) OR total logits
    # bytes (b=8, h=32, s=2048 is ~4.3 GB of fp32 logits with a tiny
    # seq product)
    n_logits = (query.shape[0] * query.shape[2]
                * query.shape[1] * key.shape[1])
    if (attn_mask is None and (dropout_p == 0.0 or not training)
            and query.shape[1] > 1
            and (query.shape[1] * key.shape[1] >= 4096 * 4096
                 or n_logits * 4 >= 1 << 30)):   # >= 1 GiB of fp32 logits
        from ...ops.pallas.flash_attention import xla_attention

        def chunked_fn(q, k, v):
            qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
            q_off = kt.shape[2] - qt.shape[2] if is_causal else 0
            out = xla_attention(qt, kt, vt, causal=is_causal,
                                q_offset=q_off)
            return jnp.swapaxes(out, 1, 2)

        return apply(chunked_fn, query, key, value, op_name="sdpa_chunked")

    def fn(q, k, v, *mask):
        scale = 1.0 / np.sqrt(q.shape[-1])
        # [b, s, h, d] -> [b, h, s, d]
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        if kt.shape[1] != qt.shape[1]:
            # GQA: grouped einsum — no materialized K/V repeats
            rep = qt.shape[1] // kt.shape[1]
            qg = qt.reshape(qt.shape[0], kt.shape[1], rep, *qt.shape[2:])
            logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kt) * scale
            logits = logits.reshape(qt.shape[0], qt.shape[1],
                                    *logits.shape[3:])
        else:
            logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
        if is_causal:
            sq, sk = logits.shape[-2], logits.shape[-1]
            causal = jnp.tril(jnp.ones((sq, sk), bool), sk - sq)
            logits = jnp.where(causal, logits, -jnp.inf)
        if mask:
            m = mask[0]
            if m.dtype == jnp.bool_:
                logits = jnp.where(m, logits, -jnp.inf)
            else:
                logits = logits + m
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
        if dk is not None:
            keep = jax.random.bernoulli(dk, 1.0 - dropout_p, probs.shape)
            probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
        if vt.shape[1] != qt.shape[1]:
            rep = qt.shape[1] // vt.shape[1]
            pg = probs.reshape(probs.shape[0], vt.shape[1], rep,
                               *probs.shape[2:])
            out = jnp.einsum("bhgqk,bhkd->bhgqd", pg, vt)
            out = out.reshape(probs.shape[0], qt.shape[1], *out.shape[3:])
        else:
            out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
        return jnp.swapaxes(out, 1, 2)

    args = (query, key, value) + ((attn_mask,) if attn_mask is not None else ())
    return apply(fn, *args, op_name="sdpa")


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------

def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def fn(l):
        k = l.shape[-1]
        smooth = (1.0 - epsilon) * l + epsilon * (1.0 / k if prior_dist is None else prior_dist)
        return smooth

    return apply(fn, label, op_name="label_smooth")


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def fn(a):
        nrm = jnp.linalg.norm(a, ord=p, axis=axis, keepdims=True)
        return a / jnp.maximum(nrm, epsilon)

    return apply(fn, x, op_name="normalize")


def unfold_channels(x, kernel_sizes, strides=1, paddings=0, dilations=1,
                    name=None):
    """im2col with channel-major patch ordering ([c0·k00, c0·k01, …]) —
    the layout :func:`unfold` already produces; kept as a distinct name
    for callers that spell the reference's channels variant."""
    return unfold(x, kernel_sizes, strides=strides, paddings=paddings,
                  dilations=dilations, name=name)


def bilinear(x1, x2, weight, bias=None, name=None):
    def fn(a, b, w, *bias_):
        out = jnp.einsum("bi,oij,bj->bo", a, w, b)
        if bias_:
            out = out + bias_[0]
        return out

    args = (x1, x2, weight) + ((bias,) if bias is not None else ())
    return apply(fn, *args, op_name="bilinear")


def zeropad2d(x, padding, data_format="NCHW", name=None):
    """reference: ``paddle.nn.functional.zeropad2d`` — [left, right,
    top, bottom] zero padding."""
    return pad(x, padding, mode="constant", value=0.0,
               data_format=data_format)


def feature_alpha_dropout(x, p=0.5, training=True, name=None):
    """Alpha dropout that drops whole channels (reference:
    ``paddle.nn.functional.feature_alpha_dropout``)."""
    if not training or p == 0.0:
        return x
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    key = prandom.next_key()

    def fn(a):
        mshape = a.shape[:2] + (1,) * (a.ndim - 2)
        keep = jax.random.bernoulli(key, 1.0 - p, mshape)
        q = 1.0 - p
        a_coef = (q + alpha_p ** 2 * q * p) ** -0.5
        b_coef = -a_coef * alpha_p * p
        return a_coef * jnp.where(keep, a, alpha_p) + b_coef

    return apply(fn, x, op_name="feature_alpha_dropout")


def class_center_sample(label, num_classes, num_samples, group=None):
    """reference: ``paddle.nn.functional.class_center_sample`` (PLSC) —
    sample the positive class centers plus random negatives; returns
    (remapped_label, sampled_class_indices). Host-side sampling (eager
    data-prep op in the reference too)."""
    import numpy as np_
    yv = np_.asarray(label.numpy() if hasattr(label, "numpy")
                     else label).reshape(-1)
    pos = np_.unique(yv)
    n_extra = max(int(num_samples) - pos.size, 0)
    rest = np_.setdiff1d(np_.arange(num_classes), pos, assume_unique=False)
    if n_extra > 0 and rest.size:
        # negatives drawn through the framework's seeded key tree, not
        # numpy's global RNG — deterministic under paddle.seed() and
        # identical across same-seed data-parallel workers
        seed = int(jax.random.randint(prandom.next_key(), (), 0, 2 ** 31 - 1))
        extra = np_.random.default_rng(seed).permutation(rest)[:n_extra]
        sampled = np_.concatenate([pos, np_.sort(extra)])
    else:
        sampled = pos
    remap = np_.full(num_classes, -1, np_.int64)
    remap[sampled] = np_.arange(sampled.size)
    from ...framework.core import Tensor as _T
    return (_T(jnp.asarray(remap[yv], jnp.int32)),
            _T(jnp.asarray(sampled, jnp.int32)))


def sparse_attention(query, key, value, sparse_csr_offset=None,
                     sparse_csr_columns=None, sparse_mask=None,
                     key_padding_mask=None, attn_mask=None, name=None):
    """reference: ``paddle.nn.functional.sparse_attention`` — attention
    restricted to a per-(batch, head) CSR pattern (offset [B,H,S+1],
    columns [B,H,nnz]), with optional ``key_padding_mask`` [B,S] and
    additive ``attn_mask`` [S,S]. The MXU has no sparse systolic path
    (same rationale as paddle_tpu.sparse's attention tier), so the
    pattern becomes an additive dense mask over one fused
    einsum+softmax chain."""
    def _np(t):
        return np.asarray(t.numpy() if hasattr(t, "numpy") else t)

    b, h, s, _ = query.shape
    if sparse_mask is not None:
        from ...sparse import is_sparse as _is_sp
        dense = _np(sparse_mask.to_dense() if _is_sp(sparse_mask)
                    else sparse_mask).reshape(b, h, s, s)
        allowed = dense != 0
    elif sparse_csr_offset is not None and sparse_csr_columns is not None:
        offs = _np(sparse_csr_offset).reshape(b, h, s + 1).astype(np.int64)
        cols = _np(sparse_csr_columns).reshape(b, h, -1).astype(np.int64)
        # vectorized CSR expansion: nnz entry j of (bi, hi) belongs to the
        # row whose offset range contains j
        allowed = np.zeros((b, h, s, s), bool)
        nnz = cols.shape[-1]
        j = np.arange(nnz)
        # rows[bi, hi, j] = searchsorted(offs[bi, hi], j, side='right') - 1
        rows = (offs[..., None, 1:-1] <= j[:, None]).sum(-1)  # [B,H,nnz]
        valid = j < offs[..., -1:]                            # inside nnz
        bi, hi, ji = np.nonzero(valid)
        allowed[bi, hi, rows[bi, hi, ji], cols[bi, hi, ji]] = True
    else:
        raise ValueError("sparse_attention needs sparse_mask or CSR "
                         "offset+columns")
    if key_padding_mask is not None:
        # [B, S]: zero/False marks padded keys — disallowed for every query
        keep = _np(key_padding_mask).astype(bool)
        allowed = allowed & keep[:, None, None, :]
    add = None
    if attn_mask is not None:
        add = jnp.asarray(_np(attn_mask), jnp.float32)
    allowed_j = jnp.asarray(allowed)

    # a row with NO allowed keys (empty CSR row, or key_padding_mask
    # masking every key) must output zero, not a uniform average over all
    # keys — the -1e30 fill alone would softmax to uniform
    dead_row = jnp.asarray(~allowed.any(-1))          # [B, H, S]

    def fn(q, k, v):
        lg = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / (q.shape[-1] ** 0.5)
        if add is not None:
            lg = lg + add
        lg = jnp.where(allowed_j, lg, -1e30)
        w = jax.nn.softmax(lg, axis=-1)
        w = jnp.where(dead_row[..., None], 0.0, w)
        return jnp.einsum("bhqk,bhkd->bhqd", w,
                          v.astype(jnp.float32)).astype(q.dtype)

    return apply(fn, query, key, value, op_name="sparse_attention")
