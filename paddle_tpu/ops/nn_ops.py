"""NN ops: activations, conv/pool, normalization, embedding, losses, attention.

PHI nn-kernel analog (ref: paddle/phi/kernels/gpu/*, fusion/*, upstream layout,
unverified — mount empty). Convs/matmuls hit the MXU; everything elementwise
around them is left to XLA fusion. Attention has a jnp reference implementation
here; the Pallas flash/splash kernel lives in paddle_tpu/ops/pallas_kernels.py
and is selected automatically when shapes allow.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax


# ----------------------------------------------------------------- activations


def softplus(x, beta=1.0, threshold=20.0):
    bx = beta * x
    return jnp.where(bx > threshold, x, jax.nn.softplus(bx) / beta)


def prelu(x, weight):
    w = weight
    if w.size > 1 and x.ndim >= 2:
        # channel dim is axis 1 (NCHW)
        shape = [1] * x.ndim
        shape[1] = w.size
        w = w.reshape(shape)
    return jnp.where(x > 0, x, w * x)


def rrelu(x, lower=0.125, upper=1.0 / 3.0, training=False):
    slope = (lower + upper) / 2.0
    return jnp.where(x >= 0, x, slope * x)


def maxout(x, groups, axis=1):
    axis = axis % x.ndim
    c = x.shape[axis]
    shape = list(x.shape)
    shape[axis] = c // groups
    shape.insert(axis + 1, groups)
    return jnp.max(x.reshape(shape), axis=axis + 1)


# ------------------------------------------------------------------ conv/pool


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(i) for i in v)
    return (int(v), int(v))


def _conv_padding(padding, k, stride, dilation, n_spatial):
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, int):
        return [(padding, padding)] * n_spatial
    padding = list(padding)
    if len(padding) == n_spatial:
        return [(int(p), int(p)) for p in padding]
    if len(padding) == 2 * n_spatial:
        return [
            (int(padding[2 * i]), int(padding[2 * i + 1]))
            for i in range(n_spatial)
        ]
    raise ValueError(f"bad padding {padding!r}")


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW"):
    stride = _pair(stride)
    dilation = _pair(dilation)
    kh, kw = weight.shape[-2], weight.shape[-1]
    pad = _conv_padding(padding, (kh, kw), stride, dilation, 2)
    dn = lax.conv_dimension_numbers(
        x.shape, weight.shape,
        ("NCHW", "OIHW", "NCHW") if data_format == "NCHW"
        else ("NHWC", "OIHW", "NHWC"),
    )
    out = lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=pad,
        rhs_dilation=dilation, dimension_numbers=dn,
        feature_group_count=groups,
        preferred_element_type=None,
    )
    if bias is not None:
        bshape = (1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1)
        out = out + bias.reshape(bshape)
    return out


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL"):
    stride = (int(stride) if isinstance(stride, int) else int(stride[0]),)
    dilation = (int(dilation) if isinstance(dilation, int) else int(dilation[0]),)
    if isinstance(padding, str):
        pad = padding.upper()
    elif isinstance(padding, int):
        pad = [(padding, padding)]
    else:
        p = list(padding)
        pad = [(p[0], p[-1])]
    dn = lax.conv_dimension_numbers(x.shape, weight.shape, ("NCH", "OIH", "NCH"))
    out = lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=pad,
        rhs_dilation=dilation, dimension_numbers=dn,
        feature_group_count=groups,
    )
    if bias is not None:
        out = out + bias.reshape(1, -1, 1)
    return out


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW"):
    def _triple(v):
        if isinstance(v, (list, tuple)):
            return tuple(int(i) for i in v)
        return (int(v),) * 3

    stride = _triple(stride)
    dilation = _triple(dilation)
    pad = _conv_padding(padding, weight.shape[-3:], stride, dilation, 3)
    dn = lax.conv_dimension_numbers(
        x.shape, weight.shape, ("NCDHW", "OIDHW", "NCDHW")
    )
    out = lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=pad,
        rhs_dilation=dilation, dimension_numbers=dn,
        feature_group_count=groups,
    )
    if bias is not None:
        out = out + bias.reshape(1, -1, 1, 1, 1)
    return out


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCHW"):
    if data_format != "NCHW":
        raise NotImplementedError(
            "conv2d_transpose supports NCHW only; transpose the input")
    return _conv_transpose_nd(x, weight, bias, stride, padding,
                              output_padding, dilation, groups, 2,
                              ("NCHW", "OIHW", "NCHW"))


def _pool(x, kernel, stride, padding, init, op, data_format="NCHW",
          count_include_pad=True, is_avg=False):
    kernel = _pair(kernel)
    stride = _pair(stride) if stride is not None else kernel
    if data_format == "NCHW":
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        sp_axes = (2, 3)
    else:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        sp_axes = (1, 2)
    if isinstance(padding, str):
        pad = padding.upper()
    else:
        p = _conv_padding(padding, kernel, stride, (1, 1), 2)
        pad = [(0, 0), (0, 0), p[0], p[1]] if data_format == "NCHW" else \
              [(0, 0), p[0], p[1], (0, 0)]
    out = lax.reduce_window(x, init, op, window, strides, pad)
    if is_avg:
        if count_include_pad or pad == "VALID" or (
            not isinstance(pad, str) and all(p == (0, 0) for p in pad)
        ):
            out = out / (kernel[0] * kernel[1])
        else:
            ones = jnp.ones_like(x)
            cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, pad)
            out = out / cnt
    return out


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               data_format="NCHW"):
    return _pool(x, kernel_size, stride, padding, -jnp.inf, lax.max,
                 data_format)


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               count_include_pad=True, data_format="NCHW"):
    return _pool(x, kernel_size, stride, padding, 0.0, lax.add, data_format,
                 count_include_pad=count_include_pad, is_avg=True)


def adaptive_avg_pool2d(x, output_size, data_format="NCHW"):
    oh, ow = _pair(output_size)
    if data_format != "NCHW":
        x = jnp.transpose(x, (0, 3, 1, 2))
    n, c, h, w = x.shape
    if h % oh == 0 and w % ow == 0:
        out = x.reshape(n, c, oh, h // oh, ow, w // ow).mean(axis=(3, 5))
    else:
        # general adaptive pooling via per-window means
        def win_mean(hi, wi):
            hs, he = (hi * h) // oh, -(-((hi + 1) * h) // oh)
            ws, we = (wi * w) // ow, -(-((wi + 1) * w) // ow)
            return x[:, :, hs:he, ws:we].mean(axis=(2, 3))

        rows = [jnp.stack([win_mean(i, j) for j in range(ow)], axis=-1)
                for i in range(oh)]
        out = jnp.stack(rows, axis=-2)
    if data_format != "NCHW":
        out = jnp.transpose(out, (0, 2, 3, 1))
    return out


def adaptive_max_pool2d(x, output_size, data_format="NCHW"):
    oh, ow = _pair(output_size)
    n, c, h, w = x.shape
    if h % oh == 0 and w % ow == 0:
        return x.reshape(n, c, oh, h // oh, ow, w // ow).max(axis=(3, 5))
    def win_max(hi, wi):
        hs, he = (hi * h) // oh, -(-((hi + 1) * h) // oh)
        ws, we = (wi * w) // ow, -(-((wi + 1) * w) // ow)
        return x[:, :, hs:he, ws:we].max(axis=(2, 3))

    rows = [jnp.stack([win_max(i, j) for j in range(ow)], axis=-1)
            for i in range(oh)]
    return jnp.stack(rows, axis=-2)


def max_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False):
    k = kernel_size if isinstance(kernel_size, int) else kernel_size[0]
    s = k if stride is None else (stride if isinstance(stride, int) else stride[0])
    p = padding if isinstance(padding, int) else padding[0]
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 1, k), (1, 1, s),
        [(0, 0), (0, 0), (p, p)],
    )


def avg_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False):
    k = kernel_size if isinstance(kernel_size, int) else kernel_size[0]
    s = k if stride is None else (stride if isinstance(stride, int) else stride[0])
    p = padding if isinstance(padding, int) else padding[0]
    out = lax.reduce_window(
        x, 0.0, lax.add, (1, 1, k), (1, 1, s), [(0, 0), (0, 0), (p, p)]
    )
    return out / k


# -------------------------------------------------------------- normalization


def layer_norm(x, weight=None, bias=None, epsilon=1e-5,
               begin_norm_axis=-1):
    if isinstance(begin_norm_axis, int) and begin_norm_axis >= 0:
        axes = tuple(range(begin_norm_axis, x.ndim))
    else:
        axes = (x.ndim - 1,)
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=axes, keepdims=True)
    out = (x32 - mean) * lax.rsqrt(var + epsilon)
    out = out.astype(x.dtype)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


def rms_norm(x, weight=None, epsilon=1e-6):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = (x32 * lax.rsqrt(ms + epsilon)).astype(x.dtype)
    if weight is not None:
        out = out * weight
    return out


def batch_norm_infer(x, running_mean, running_var, weight=None, bias=None,
                     epsilon=1e-5, data_format="NCHW"):
    c_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    shape = [1] * x.ndim
    shape[c_axis] = -1
    inv = lax.rsqrt(running_var.astype(jnp.float32) + epsilon).reshape(shape)
    out = (x.astype(jnp.float32) - running_mean.reshape(shape)) * inv
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out.astype(x.dtype)


def batch_norm_train(x, weight=None, bias=None, epsilon=1e-5,
                     data_format="NCHW"):
    """Returns (out, batch_mean, batch_var). Running-stat update is the
    layer's job (momentum blending outside the op, like PHI's batch_norm)."""
    c_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != c_axis)
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axes)
    var = jnp.var(x32, axis=axes)
    shape = [1] * x.ndim
    shape[c_axis] = -1
    out = (x32 - mean.reshape(shape)) * lax.rsqrt(var.reshape(shape) + epsilon)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out.astype(x.dtype), mean, var


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format="NCHW"):
    if data_format != "NCHW":
        raise NotImplementedError("group_norm supports NCHW")
    n, c = x.shape[0], x.shape[1]
    g = num_groups
    rest = x.shape[2:]
    x32 = x.astype(jnp.float32).reshape((n, g, c // g) + rest)
    axes = tuple(range(2, x32.ndim))
    mean = jnp.mean(x32, axis=axes, keepdims=True)
    var = jnp.var(x32, axis=axes, keepdims=True)
    out = ((x32 - mean) * lax.rsqrt(var + epsilon)).reshape(x.shape)
    shape = [1] * x.ndim
    shape[1] = -1
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out.astype(x.dtype)


def instance_norm(x, weight=None, bias=None, epsilon=1e-5):
    axes = tuple(range(2, x.ndim))
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axes, keepdims=True)
    var = jnp.var(x32, axis=axes, keepdims=True)
    out = (x32 - mean) * lax.rsqrt(var + epsilon)
    shape = [1] * x.ndim
    shape[1] = -1
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out.astype(x.dtype)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0):
    sq = jnp.square(x)
    half = size // 2
    pad_cfg = [(0, 0)] * x.ndim
    pad_cfg[1] = (half, size - 1 - half)
    sq = jnp.pad(sq, pad_cfg)
    window = [1] * x.ndim
    window[1] = size
    s = lax.reduce_window(sq, 0.0, lax.add, tuple(window), (1,) * x.ndim,
                          [(0, 0)] * x.ndim)
    return x / jnp.power(k + alpha * s, beta)


# --------------------------------------------------------- dropout/emb/linear


def dropout(x, key, p=0.5, training=True, mode="upscale_in_train", axis=None):
    if not training or p == 0.0:
        return x
    if p >= 1.0:
        return jnp.zeros_like(x) if mode == "upscale_in_train" else x * 0.0
    shape = x.shape
    if axis is not None:
        axes = (axis,) if isinstance(axis, int) else tuple(axis)
        shape = tuple(s if i in axes else 1 for i, s in enumerate(x.shape))
    keep = jax.random.bernoulli(key, 1.0 - p, shape=shape)
    if mode == "upscale_in_train":
        return jnp.where(keep, x / (1.0 - p), jnp.zeros_like(x))
    return jnp.where(keep, x, jnp.zeros_like(x))


def embedding(ids, weight, padding_idx=None, sparse=False):
    out = jnp.take(weight, ids, axis=0)
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return out


def linear(x, weight, bias=None):
    # paddle weight layout: (in_features, out_features)
    out = jnp.matmul(x, weight)
    if bias is not None:
        out = out + bias
    return out


# --------------------------------------------------------------------- losses


def cross_entropy(logits, label, weight=None, soft_label=False, axis=-1,
                  ignore_index=-100, reduction="mean",
                  label_smoothing=0.0):
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=axis)
    n_classes = logits.shape[axis]
    if soft_label:
        target = label.astype(jnp.float32)
        loss = -jnp.sum(target * logp, axis=axis)
        valid = jnp.ones(loss.shape, dtype=jnp.float32)
    else:
        lbl = label
        if lbl.ndim == logp.ndim and lbl.shape[axis] == 1:
            lbl = jnp.squeeze(lbl, axis=axis)
        lbl = lbl.astype(jnp.int32)
        valid = (lbl != ignore_index).astype(jnp.float32)
        safe = jnp.where(lbl == ignore_index, 0, lbl)
        if label_smoothing > 0.0:
            onehot = jax.nn.one_hot(safe, n_classes, axis=axis)
            target = onehot * (1.0 - label_smoothing) + label_smoothing / n_classes
            loss = -jnp.sum(target * logp, axis=axis)
        else:
            loss = -jnp.take_along_axis(
                logp, jnp.expand_dims(safe, axis), axis=axis
            ).squeeze(axis)
        if weight is not None:
            w = jnp.take(weight, safe)
            loss = loss * w
            valid = valid * w
        loss = loss * (lbl != ignore_index).astype(loss.dtype)
    if reduction == "none":
        return loss
    if reduction == "sum":
        return jnp.sum(loss)
    denom = jnp.maximum(jnp.sum(valid), 1.0)
    return jnp.sum(loss) / denom


def nll_loss(logp, label, weight=None, ignore_index=-100, reduction="mean"):
    lbl = label.astype(jnp.int32)
    valid = (lbl != ignore_index).astype(jnp.float32)
    safe = jnp.where(lbl == ignore_index, 0, lbl)
    loss = -jnp.take_along_axis(logp, safe[:, None], axis=1).squeeze(1)
    if weight is not None:
        w = jnp.take(weight, safe)
        loss = loss * w
        valid = valid * w
    loss = loss * (lbl != ignore_index).astype(loss.dtype)
    if reduction == "none":
        return loss
    if reduction == "sum":
        return jnp.sum(loss)
    return jnp.sum(loss) / jnp.maximum(jnp.sum(valid), 1.0)


def _flce_dims(transpose_y):
    # x (c, H) contracted with w: (V, H) when transpose_y else (H, V)
    return (((1,), (1,)), ((), ())) if transpose_y else (((1,), (0,)), ((), ()))


def _flce_chunks(x2, lbl, ignore_index, chunk):
    n = x2.shape[0]
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        # padded rows carry ignore_index, so they drop out of loss and grads
        lbl = jnp.pad(lbl, (0, pad), constant_values=ignore_index)
    return (x2.reshape(n_chunks, chunk, x2.shape[1]),
            lbl.reshape(n_chunks, chunk))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flce_rows(x2, w, b, lbl, ignore_index, transpose_y, chunk):
    loss, _ = _flce_fwd(x2, w, b, lbl, ignore_index, transpose_y, chunk)
    return loss


def _flce_fwd(x2, w, b, lbl, ignore_index, transpose_y, chunk):
    n = x2.shape[0]
    dims = _flce_dims(transpose_y)
    xs, ls = _flce_chunks(x2, lbl, ignore_index, chunk)
    bf = b.astype(jnp.float32)

    def body(_, xe):
        x_c, l_c = xe
        # the matmul runs in the INPUT dtype (bf16 rides the MXU natively)
        # with f32 accumulation; only the (chunk, V) block is ever resident
        logits = jax.lax.dot_general(
            x_c, w, dims, preferred_element_type=jnp.float32) + bf
        m = jnp.max(logits, axis=1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=1))
        valid = l_c != ignore_index
        safe = jnp.where(valid, l_c, 0).astype(jnp.int32)
        gold = jnp.take_along_axis(logits, safe[:, None], axis=1)[:, 0]
        return 0, (jnp.where(valid, lse - gold, 0.0), lse)

    _, (loss, lse) = jax.lax.scan(body, 0, (xs, ls))
    return loss.reshape(-1)[:n], lse.reshape(-1)[:n]


def _flce_fwd_vjp(x2, w, b, lbl, ignore_index, transpose_y, chunk):
    loss, lse = _flce_fwd(x2, w, b, lbl, ignore_index, transpose_y, chunk)
    return loss, (x2, w, b, lbl, lse)


def _flce_bwd(ignore_index, transpose_y, chunk, res, g):
    x2, w, b, lbl, lse = res
    n, hdim = x2.shape
    vocab = w.shape[0] if transpose_y else w.shape[1]
    dims = _flce_dims(transpose_y)
    # dx chunk: coeff (c, V) x w -> (c, H)
    dx_dims = ((((1,), (0,)), ((), ())) if transpose_y
               else (((1,), (1,)), ((), ())))
    xs, ls = _flce_chunks(x2, lbl, ignore_index, chunk)
    n_chunks = xs.shape[0]
    pad = n_chunks * chunk - n
    # padded rows carry lse=+inf so p = exp(logits - lse) is exactly 0:
    # with a 0 pad, a padded row whose recomputed logits overflow exp()
    # yields p=inf, and inf * (g*valid == 0) = NaN poisoning the dw/db
    # scan accumulators (ragged final chunk, advisor round-5 finding)
    lse_s = jnp.pad(lse, (0, pad),
                    constant_values=jnp.inf).reshape(n_chunks, chunk)
    g_s = jnp.pad(g.astype(jnp.float32), (0, pad)).reshape(n_chunks, chunk)
    bf = b.astype(jnp.float32)

    def body(carry, xe):
        dw_acc, db_acc = carry
        x_c, l_c, lse_c, g_c = xe
        logits = jax.lax.dot_general(
            x_c, w, dims, preferred_element_type=jnp.float32) + bf
        p = jnp.exp(logits - lse_c[:, None])
        valid = l_c != ignore_index
        safe = jnp.where(valid, l_c, 0).astype(jnp.int32)
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (chunk, vocab), 1)
                  == safe[:, None])
        coeff = (p - onehot) * (g_c * valid)[:, None]
        coeff_l = coeff.astype(x_c.dtype)  # bf16 dgrad/wgrad on the MXU
        dx_c = jax.lax.dot_general(
            coeff_l, w, dx_dims, preferred_element_type=jnp.float32)
        # wgrad: (V, H) = coeff^T x_c when transpose_y, else (H, V)
        if transpose_y:
            dw_c = jax.lax.dot_general(
                coeff_l, x_c, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            dw_c = jax.lax.dot_general(
                x_c, coeff_l, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return ((dw_acc + dw_c, db_acc + jnp.sum(coeff, axis=0)),
                dx_c)

    (dw, db), dxs = jax.lax.scan(
        body, (jnp.zeros(w.shape, jnp.float32),
               jnp.zeros((vocab,), jnp.float32)),
        (xs, ls, lse_s, g_s))
    dx = dxs.reshape(-1, hdim)[:n].astype(x2.dtype)
    return dx, dw.astype(w.dtype), db.astype(b.dtype), None


_flce_rows.defvjp(_flce_fwd_vjp, _flce_bwd)


def fused_linear_cross_entropy(x, weight, bias=None, label=None,
                               ignore_index=-100, transpose_y=False,
                               reduction="mean", chunk_size=2048):
    """Linear projection + softmax cross-entropy that never materializes the
    (N, vocab) logits: a scanned chunk loop computes per-row lse/gold in one
    pass, and a custom VJP recomputes each chunk's logits in the backward
    (flash-attention's trick applied to the LM head). Cuts the f32 logits
    buffer (batch*seq x vocab) from the train step's live set and removes
    the layout copies XLA spends on it (1.2 GB in float32 at ERNIE-base,
    batch 32 x seq 512, from the shapes).

    Upstream analog: paddle.incubate's fused CE path (upstream layout,
    unverified — mount empty). Semantics match
    cross_entropy(linear(x, w, b), label) with hard labels.
    """
    hdim = x.shape[-1]
    x2 = x.reshape(-1, hdim)
    lbl = label.reshape(-1).astype(jnp.int32)
    vocab = weight.shape[0] if transpose_y else weight.shape[1]
    b = (jnp.zeros((vocab,), jnp.float32) if bias is None
         else bias)
    chunk = max(1, int(min(chunk_size, x2.shape[0])))
    loss = _flce_rows(x2, weight, b, lbl, int(ignore_index),
                      bool(transpose_y), chunk)
    if reduction == "none":
        return loss.reshape(label.shape)
    if reduction == "sum":
        return jnp.sum(loss)
    valid = (lbl != ignore_index).astype(jnp.float32)
    return jnp.sum(loss) / jnp.maximum(jnp.sum(valid), 1.0)


def mse_loss(input, label, reduction="mean"):
    loss = jnp.square(input - label)
    if reduction == "none":
        return loss
    return jnp.sum(loss) if reduction == "sum" else jnp.mean(loss)


def l1_loss(input, label, reduction="mean"):
    loss = jnp.abs(input - label)
    if reduction == "none":
        return loss
    return jnp.sum(loss) if reduction == "sum" else jnp.mean(loss)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0):
    diff = jnp.abs(input - label)
    loss = jnp.where(diff < delta, 0.5 * diff * diff / delta,
                     diff - 0.5 * delta)
    if reduction == "none":
        return loss
    return jnp.sum(loss) if reduction == "sum" else jnp.mean(loss)


def binary_cross_entropy(input, label, weight=None, reduction="mean"):
    eps = 1e-12
    x = jnp.clip(input.astype(jnp.float32), eps, 1.0 - eps)
    loss = -(label * jnp.log(x) + (1.0 - label) * jnp.log(1.0 - x))
    if weight is not None:
        loss = loss * weight
    if reduction == "none":
        return loss
    return jnp.sum(loss) if reduction == "sum" else jnp.mean(loss)


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None):
    logit = logit.astype(jnp.float32)
    label = label.astype(jnp.float32)
    max_val = jnp.clip(-logit, 0, None)
    if pos_weight is not None:
        log_w = (pos_weight - 1.0) * label + 1.0
        loss = (1.0 - label) * logit + log_w * (
            jnp.log(jnp.exp(-max_val) + jnp.exp(-logit - max_val)) + max_val
        )
    else:
        loss = (1.0 - label) * logit + max_val + jnp.log(
            jnp.exp(-max_val) + jnp.exp(-logit - max_val)
        )
    if weight is not None:
        loss = loss * weight
    if reduction == "none":
        return loss
    return jnp.sum(loss) if reduction == "sum" else jnp.mean(loss)


def kl_div(input, label, reduction="mean", log_target=False):
    if log_target:
        loss = jnp.exp(label) * (label - input)
    else:
        safe = jnp.where(label > 0, label, 1.0)
        loss = jnp.where(label > 0, label * (jnp.log(safe) - input),
                         jnp.zeros_like(label))
    if reduction == "none":
        return loss
    if reduction == "sum":
        return jnp.sum(loss)
    if reduction == "batchmean":
        return jnp.sum(loss) / input.shape[0]
    return jnp.mean(loss)


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean"):
    loss = jnp.where(label == 1.0, input,
                     jnp.clip(margin - input, 0.0, None))
    if reduction == "none":
        return loss
    return jnp.sum(loss) if reduction == "sum" else jnp.mean(loss)


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    dot_ = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.sqrt(jnp.sum(jnp.square(x1), axis=axis))
    n2 = jnp.sqrt(jnp.sum(jnp.square(x2), axis=axis))
    return dot_ / jnp.maximum(n1 * n2, eps)


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean"):
    loss = jnp.clip(-label * (input - other) + margin, 0.0, None)
    if reduction == "none":
        return loss
    return jnp.sum(loss) if reduction == "sum" else jnp.mean(loss)


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum"):
    p = jax.nn.sigmoid(logit)
    logit32 = logit.astype(jnp.float32)
    label32 = label.astype(jnp.float32)
    max_val = jnp.clip(-logit32, 0, None)
    ce = (1.0 - label32) * logit32 + max_val + jnp.log(
        jnp.exp(-max_val) + jnp.exp(-logit32 - max_val))
    p_t = p * label32 + (1 - p) * (1 - label32)
    loss = ce * jnp.power(1 - p_t, gamma)
    alpha_t = alpha * label32 + (1 - alpha) * (1 - label32)
    loss = alpha_t * loss
    if normalizer is not None:
        loss = loss / normalizer
    if reduction == "none":
        return loss
    return jnp.sum(loss) if reduction == "sum" else jnp.mean(loss)


def label_smooth(label, epsilon=0.1, prior_dist=None):
    n = label.shape[-1]
    if prior_dist is not None:
        return (1.0 - epsilon) * label + epsilon * prior_dist
    return (1.0 - epsilon) * label + epsilon / n


# ------------------------------------------------------------------ attention


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 rng_key=None, dropout_p=0.0,
                                 is_causal=False, scale=None):
    """Reference attention. Layout: (batch, seq, heads, head_dim) — paddle's
    flash_attention layout. The Pallas flash kernel substitutes this op on TPU
    for long sequences (see ops/pallas_kernels.py). Attention dropout (on the
    softmax probs, upscale_in_train) applies when rng_key is provided."""
    b, sq, h, d = query.shape
    sk = key.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    q = jnp.einsum("bqhd->bhqd", query)
    k = jnp.einsum("bkhd->bhkd", key)
    v = jnp.einsum("bkhd->bhkd", value)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    if is_causal:
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool))
        logits = jnp.where(mask, logits, -jnp.inf)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, -jnp.inf)
        else:
            logits = logits + attn_mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(query.dtype)
    if dropout_p > 0.0 and rng_key is not None:
        keep = jax.random.bernoulli(rng_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p),
                          jnp.zeros_like(probs))
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return jnp.einsum("bhqd->bqhd", out)


# ---------------------------------------------------------------------- misc


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, data_format="NCHW"):
    n, c, h, w = x.shape
    if size is None:
        sf = scale_factor if isinstance(scale_factor, (list, tuple)) else \
            (scale_factor, scale_factor)
        size = (int(h * sf[0]), int(w * sf[1]))
    oh, ow = int(size[0]), int(size[1])
    method = {"nearest": "nearest", "bilinear": "linear", "bicubic": "cubic",
              "linear": "linear"}[mode]
    out = jax.image.resize(x, (n, c, oh, ow), method=method)
    return out.astype(x.dtype)


def pixel_shuffle(x, upscale_factor, data_format="NCHW"):
    r = upscale_factor
    if data_format == "NHWC":
        # channel dim factors as (oc, r, r), matching the NCHW semantics
        n, h, w, c = x.shape
        oc = c // (r * r)
        out = x.reshape(n, h, w, oc, r, r)
        out = jnp.transpose(out, (0, 1, 4, 2, 5, 3))
        return out.reshape(n, h * r, w * r, oc)
    n, c, h, w = x.shape
    oc = c // (r * r)
    out = x.reshape(n, oc, r, r, h, w)
    out = jnp.transpose(out, (0, 1, 4, 2, 5, 3))
    return out.reshape(n, oc, h * r, w * r)


def channel_shuffle(x, groups, data_format="NCHW"):
    """Interleave channels across `groups` (ShuffleNet block glue; ref:
    paddle.nn.functional.channel_shuffle, upstream phi kernel — mount
    empty). Pure reshape/transpose: XLA lowers it to a free relayout."""
    if data_format == "NHWC":
        n, h, w, c = x.shape
        out = x.reshape(n, h, w, groups, c // groups)
        return jnp.swapaxes(out, 3, 4).reshape(n, h, w, c)
    n, c, h, w = x.shape
    out = x.reshape(n, groups, c // groups, h, w)
    return jnp.swapaxes(out, 1, 2).reshape(n, c, h, w)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    kh, kw = _pair(kernel_sizes)
    sh, sw = _pair(strides)
    ph, pw = _pair(paddings)
    dh, dw = _pair(dilations)
    n, c, h, w = x.shape
    xp = jnp.pad(x, [(0, 0), (0, 0), (ph, ph), (pw, pw)])
    oh = (h + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    ow = (w + 2 * pw - dw * (kw - 1) - 1) // sw + 1
    cols = []
    for i in range(kh):
        for j in range(kw):
            patch = xp[:, :, i * dh:i * dh + sh * oh:sh,
                       j * dw:j * dw + sw * ow:sw]
            cols.append(patch)
    out = jnp.stack(cols, axis=2)  # n, c, kh*kw, oh, ow
    return out.reshape(n, c * kh * kw, oh * ow)


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    sim = jnp.matmul(anchor, positive.T)
    lbl = labels.reshape(-1, 1)
    target = (lbl == lbl.T).astype(jnp.float32)
    target = target / jnp.sum(target, axis=1, keepdims=True)
    logp = jax.nn.log_softmax(sim, axis=-1)
    ce = -jnp.mean(jnp.sum(target * logp, axis=1))
    reg = l2_reg * (jnp.mean(jnp.sum(jnp.square(anchor), axis=1)) +
                    jnp.mean(jnp.sum(jnp.square(positive), axis=1))) * 0.25
    return ce + reg


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW"):
    nt, c, h, w = x.shape
    n = nt // seg_num
    x5 = x.reshape(n, seg_num, c, h, w)
    fold = int(c * shift_ratio)
    left = jnp.concatenate([x5[:, 1:, :fold], jnp.zeros_like(x5[:, :1, :fold])],
                           axis=1)
    right = jnp.concatenate([jnp.zeros_like(x5[:, :1, fold:2 * fold]),
                             x5[:, :-1, fold:2 * fold]], axis=1)
    rest = x5[:, :, 2 * fold:]
    out = jnp.concatenate([left, right, rest], axis=2)
    return out.reshape(nt, c, h, w)


# ----------------------------------------------------------- round-3 losses

def _reduce_loss(loss, reduction):
    if reduction == "none":
        return loss
    return jnp.sum(loss) if reduction == "sum" else jnp.mean(loss)


def huber_loss(input, label, delta=1.0, reduction="mean"):
    diff = jnp.abs(input - label)
    loss = jnp.where(diff <= delta, 0.5 * diff * diff,
                     delta * (diff - 0.5 * delta))
    return _reduce_loss(loss, reduction)


def soft_margin_loss(input, label, reduction="mean"):
    # softplus(-y*x): overflow-stable form of log(1 + exp(-y*x))
    loss = jax.nn.softplus(-label.astype(input.dtype) * input)
    return _reduce_loss(loss, reduction)


def multi_label_soft_margin_loss(input, label, weight=None,
                                 reduction="mean"):
    lab = label.astype(input.dtype)
    loss = -(lab * jax.nn.log_sigmoid(input)
             + (1.0 - lab) * jax.nn.log_sigmoid(-input))
    if weight is not None:
        loss = loss * weight
    loss = jnp.mean(loss, axis=-1)
    return _reduce_loss(loss, reduction)


def poisson_nll_loss(input, label, log_input=True, full=False,
                     epsilon=1e-8, reduction="mean"):
    if log_input:
        loss = jnp.exp(input) - label * input
    else:
        loss = input - label * jnp.log(input + epsilon)
    if full:
        # Stirling approximation for the label! term, applied where y > 1
        stirling = (label * jnp.log(label + epsilon) - label
                    + 0.5 * jnp.log(2.0 * math.pi * (label + epsilon)))
        loss = loss + jnp.where(label > 1, stirling, 0.0)
    return _reduce_loss(loss, reduction)


def gaussian_nll_loss(input, label, variance, full=False, epsilon=1e-6,
                      reduction="mean"):
    var = jnp.maximum(variance, epsilon)
    loss = 0.5 * (jnp.log(var) + jnp.square(input - label) / var)
    if full:
        loss = loss + 0.5 * math.log(2.0 * math.pi)
    return _reduce_loss(loss, reduction)


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False):
    d = x - y + epsilon
    return jnp.sum(jnp.abs(d) ** p, axis=-1, keepdims=keepdim) ** (1.0 / p)


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean"):
    def dist(a, b):
        return pairwise_distance(a, b, p=p, epsilon=epsilon)
    d_pos = dist(input, positive)
    d_neg = dist(input, negative)
    if swap:
        d_neg = jnp.minimum(d_neg, dist(positive, negative))
    loss = jnp.maximum(d_pos - d_neg + margin, 0.0)
    return _reduce_loss(loss, reduction)


def dice_loss(input, label, epsilon=1e-5):
    # input: (N, ..., C) probabilities; label: (N, ..., 1) int class ids
    n_classes = input.shape[-1]
    lab = jax.nn.one_hot(label.squeeze(-1), n_classes, dtype=input.dtype)
    reduce_dims = tuple(range(1, input.ndim))
    inter = jnp.sum(input * lab, axis=reduce_dims)
    union = jnp.sum(input, axis=reduce_dims) + jnp.sum(lab, axis=reduce_dims)
    dice = (2.0 * inter + epsilon) / (union + epsilon)
    return jnp.mean(1.0 - dice)


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, return_softmax=False,
                         reduction="mean"):
    """ArcFace-family margin softmax (cos(m1*θ + m2) - m3), single-rank
    path (the fleet model-parallel variant shards the class dim)."""
    # clip strictly inside (-1, 1): d/dx arccos explodes at the endpoints
    cos = jnp.clip(logits, -1.0 + 1e-6, 1.0 - 1e-6)
    theta = jnp.arccos(cos)
    target_cos = jnp.cos(margin1 * theta + margin2) - margin3
    onehot = jax.nn.one_hot(label, logits.shape[-1], dtype=logits.dtype)
    adjusted = jnp.where(onehot > 0, target_cos, cos) * scale
    logp = jax.nn.log_softmax(adjusted, axis=-1)
    loss = -jnp.sum(onehot * logp, axis=-1)
    loss = _reduce_loss(loss, reduction)
    if return_softmax:
        return loss, jnp.exp(logp)
    return loss


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC forward algorithm in log space via lax.scan over time.

    log_probs: (T, B, C) log-softmaxed activations (paddle's warpctc
    contract); labels: (B, L) int; returns per-sample negative log
    likelihood. Static shapes: the alpha lattice is (B, 2L+1) with masked
    updates — TPU-friendly (one scan, no data-dependent shapes)."""
    T, B, C = log_probs.shape
    L = labels.shape[1]
    S = 2 * L + 1
    neg_inf = jnp.float32(-1e30)

    # extended label sequence: blank y1 blank y2 ... yL blank
    ext = jnp.full((B, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(labels.astype(jnp.int32))
    pos = jnp.arange(S)[None, :]
    valid = pos < (2 * label_lengths[:, None] + 1)
    # transitions: alpha[s] <- alpha[s] + alpha[s-1] (+ alpha[s-2] when the
    # current symbol differs from the one two back, i.e. not blank-blank
    # and not repeated label)
    ext_prev2 = jnp.pad(ext, ((0, 0), (2, 0)), constant_values=-1)[:, :S]
    can_skip = (ext != blank) & (ext != ext_prev2)

    lp0 = log_probs[0]
    alpha0 = jnp.where(pos == 0, lp0[jnp.arange(B)[:, None], ext[:, :1]],
                       jnp.where(pos == 1,
                                 lp0[jnp.arange(B)[:, None], ext[:, 1:2]],
                                 neg_inf))
    alpha0 = jnp.where(valid, alpha0, neg_inf)

    def step(alpha, lp_t):
        a_prev1 = jnp.pad(alpha, ((0, 0), (1, 0)),
                          constant_values=neg_inf)[:, :S]
        a_prev2 = jnp.pad(alpha, ((0, 0), (2, 0)),
                          constant_values=neg_inf)[:, :S]
        a = jnp.logaddexp(alpha, a_prev1)
        a = jnp.where(can_skip, jnp.logaddexp(a, a_prev2), a)
        emit = lp_t[jnp.arange(B)[:, None], ext]
        new_alpha = jnp.where(valid, a + emit, neg_inf)
        return new_alpha, new_alpha

    _, alphas = jax.lax.scan(step, alpha0, log_probs[1:])
    alphas = jnp.concatenate([alpha0[None], alphas], axis=0)  # (T, B, S)

    # read out at each sample's input length: last blank or last label
    t_idx = jnp.clip(input_lengths.astype(jnp.int32) - 1, 0, T - 1)
    alpha_T = alphas[t_idx, jnp.arange(B)]                    # (B, S)
    end = 2 * label_lengths.astype(jnp.int32)
    a_last = alpha_T[jnp.arange(B), end]
    a_prev = alpha_T[jnp.arange(B), jnp.maximum(end - 1, 0)]
    nll = -jnp.logaddexp(a_last, jnp.where(label_lengths > 0, a_prev,
                                           neg_inf))
    if norm_by_times:
        nll = nll / jnp.maximum(input_lengths.astype(nll.dtype), 1.0)
    if reduction == "mean":
        # warpctc/torch contract: per-sample nll over label length, THEN
        # batch mean
        return jnp.mean(nll / jnp.maximum(
            label_lengths.astype(nll.dtype), 1.0))
    return _reduce_loss(nll, reduction)


def rnnt_loss(input, label, input_lengths, label_lengths, blank=0,
              fastemit_lambda=0.0, reduction="mean"):
    """RNN-Transducer loss (Graves 2012) — forward-variable DP.

    input: (B, T, U+1, V) joint-network logits (log_softmax applied here,
    warprnnt contract); label: (B, U) int. The lattice recursion scans t
    with an inner scan over u (the in-row dependency alpha[t,u-1] ->
    alpha[t,u] is inherently sequential); everything is static-shape, so
    the whole loss jits as two nested lax.scans.

    fastemit_lambda: FastEmit scales the EMIT PORTION OF THE GRADIENT
    (the forward NLL value is unchanged in warprnnt); a forward-side
    rescale would un-normalize the per-step distribution, so nonzero
    values are rejected until the gradient-side form is implemented."""
    if fastemit_lambda:
        raise NotImplementedError(
            "fastemit_lambda != 0 is not implemented (warprnnt applies "
            "FastEmit to the gradient only; a forward-side rescale would "
            "change the returned NLL)")
    logp = jax.nn.log_softmax(input.astype(jnp.float32), axis=-1)
    B, T, U1, V = logp.shape
    U = U1 - 1
    lab = label.astype(jnp.int32)
    b_idx = jnp.arange(B)[:, None]
    u_idx = jnp.arange(U)[None, :]
    # emit[b, t, u] = logp[b, t, u, label[b, u]]  (u < U)
    emit = logp[b_idx[:, :, None], jnp.arange(T)[None, :, None],
                u_idx[:, None, :], lab[:, None, :]]    # (B, T, U)
    blank_p = logp[..., blank]                         # (B, T, U+1)
    neg_inf = jnp.float32(-1e30)

    def row_scan(base, emit_row):
        """row[u] = logaddexp(base[u], row[u-1] + emit_row[u-1]) along u."""
        def step(prev, be):
            b_u, e_prev = be
            cur = jnp.logaddexp(b_u, prev + e_prev)
            return cur, cur
        first = base[:, 0]
        _, rest = jax.lax.scan(
            step, first,
            (jnp.swapaxes(base[:, 1:], 0, 1),
             jnp.swapaxes(emit_row, 0, 1)))
        return jnp.concatenate([first[:, None],
                                jnp.swapaxes(rest, 0, 1)], axis=1)

    # t = 0 row: pure emit chain
    alpha0 = row_scan(
        jnp.concatenate([jnp.zeros((B, 1), jnp.float32),
                         jnp.full((B, U), neg_inf)], axis=1),
        emit[:, 0])

    def t_step(alpha_prev, inps):
        blank_prev, emit_t = inps                      # (B, U+1), (B, U)
        base = alpha_prev + blank_prev                 # advance t via blank
        alpha_t = row_scan(base, emit_t)
        return alpha_t, alpha_t

    _, alphas = jax.lax.scan(
        t_step, alpha0,
        (jnp.swapaxes(blank_p[:, :-1], 0, 1),
         jnp.swapaxes(emit[:, 1:], 0, 1)))
    alphas = jnp.concatenate([alpha0[None], alphas], axis=0)  # (T, B, U+1)

    t_last = jnp.clip(input_lengths.astype(jnp.int32) - 1, 0, T - 1)
    u_last = jnp.clip(label_lengths.astype(jnp.int32), 0, U)
    bb = jnp.arange(B)
    ll = alphas[t_last, bb, u_last] + blank_p[bb, t_last, u_last]
    nll = -ll
    return _reduce_loss(nll, reduction)


# ---------------------------------------------------- round-3c vision ops

def _triple_(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(i) for i in v)
    return (int(v),) * 3


def _check_pool3d_args(ceil_mode, data_format):
    if ceil_mode:
        raise NotImplementedError("ceil_mode=True is not implemented for "
                                  "3d/lp pooling; pad the input instead")
    if data_format not in ("NCDHW", "NDHWC"):
        raise ValueError(f"unsupported data_format {data_format!r}")


def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               data_format="NCDHW"):
    _check_pool3d_args(ceil_mode, data_format)
    k = _triple_(kernel_size)
    s = _triple_(stride) if stride is not None else k
    p = _triple_(padding)
    if data_format == "NDHWC":
        window, strides = (1,) + k + (1,), (1,) + s + (1,)
        pad = [(0, 0)] + [(pi, pi) for pi in p] + [(0, 0)]
    else:
        window, strides = (1, 1) + k, (1, 1) + s
        pad = [(0, 0), (0, 0)] + [(pi, pi) for pi in p]
    return lax.reduce_window(x, -jnp.inf, lax.max, window, strides, pad)


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               count_include_pad=True, data_format="NCDHW"):
    _check_pool3d_args(ceil_mode, data_format)
    k = _triple_(kernel_size)
    s = _triple_(stride) if stride is not None else k
    p = _triple_(padding)
    if data_format == "NDHWC":
        window, strides = (1,) + k + (1,), (1,) + s + (1,)
        pad = [(0, 0)] + [(pi, pi) for pi in p] + [(0, 0)]
    else:
        window, strides = (1, 1) + k, (1, 1) + s
        pad = [(0, 0), (0, 0)] + [(pi, pi) for pi in p]
    summed = lax.reduce_window(x, 0.0, lax.add, window, strides, pad)
    if count_include_pad:
        return summed / float(k[0] * k[1] * k[2])
    ones = jnp.ones_like(x)
    counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, pad)
    return summed / counts


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW"):
    out = _triple_(output_size)
    if data_format != "NCDHW":
        x = jnp.transpose(x, (0, 4, 1, 2, 3))
    n, c, d, h, w = x.shape
    od, oh, ow = out
    if d % od == 0 and h % oh == 0 and w % ow == 0:
        res = x.reshape(n, c, od, d // od, oh, h // oh, ow, w // ow).mean(
            axis=(3, 5, 7))
    else:
        # general adaptive pooling via per-window means (2D-op pattern)
        def win_mean(di, hi, wi):
            ds, de = (di * d) // od, -(-((di + 1) * d) // od)
            hs, he = (hi * h) // oh, -(-((hi + 1) * h) // oh)
            ws, we = (wi * w) // ow, -(-((wi + 1) * w) // ow)
            return x[:, :, ds:de, hs:he, ws:we].mean(axis=(2, 3, 4))

        planes = [jnp.stack(
            [jnp.stack([win_mean(i, j, l) for l in range(ow)], axis=-1)
             for j in range(oh)], axis=-2) for i in range(od)]
        res = jnp.stack(planes, axis=-3)
    if data_format != "NCDHW":
        res = jnp.transpose(res, (0, 2, 3, 4, 1))
    return res


def lp_pool1d(x, norm_type, kernel_size, stride=None, padding=0,
              ceil_mode=False, data_format="NCL"):
    if ceil_mode:
        raise NotImplementedError("ceil_mode=True is not implemented for "
                                  "lp pooling")
    if data_format != "NCL":
        raise ValueError("lp_pool1d supports data_format='NCL' only")
    k = int(kernel_size)
    s = int(stride) if stride is not None else k
    p = int(padding)
    # torch/paddle LP pool is sum(x^p)^(1/p) on the SIGNED values (odd
    # norm_type can legitimately produce nan on negative windows)
    xp = x.astype(jnp.float32) ** norm_type
    summed = lax.reduce_window(xp, 0.0, lax.add, (1, 1, k), (1, 1, s),
                               [(0, 0), (0, 0), (p, p)])
    return (summed ** (1.0 / norm_type)).astype(x.dtype)


def lp_pool2d(x, norm_type, kernel_size, stride=None, padding=0,
              ceil_mode=False, data_format="NCHW"):
    if ceil_mode:
        raise NotImplementedError("ceil_mode=True is not implemented for "
                                  "lp pooling")
    if data_format != "NCHW":
        raise ValueError("lp_pool2d supports data_format='NCHW' only")
    k = _pair(kernel_size)
    s = _pair(stride) if stride is not None else k
    p = _pair(padding)
    xp = x.astype(jnp.float32) ** norm_type
    summed = lax.reduce_window(
        xp, 0.0, lax.add, (1, 1) + k, (1, 1) + s,
        [(0, 0), (0, 0), (p[0], p[0]), (p[1], p[1])])
    return (summed ** (1.0 / norm_type)).astype(x.dtype)


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1):
    """col2im — inverse of unfold: x (N, C*kh*kw, L) -> (N, C, H, W) with
    overlapping patches summed (scatter-add via .at[])."""
    kh, kw = _pair(kernel_sizes)
    sh, sw = _pair(strides)
    ph, pw = _pair(paddings)
    dh, dw = _pair(dilations)
    oh, ow = _pair(output_sizes)
    n, ckk, L = x.shape
    c = ckk // (kh * kw)
    hp, wp = oh + 2 * ph, ow + 2 * pw
    n_h = (hp - dh * (kh - 1) - 1) // sh + 1
    n_w = (wp - dw * (kw - 1) - 1) // sw + 1
    if n_h * n_w != L:
        raise ValueError(f"fold: L={L} inconsistent with output_sizes "
                         f"(expected {n_h * n_w} patches)")
    cols = x.reshape(n, c, kh, kw, n_h, n_w)
    # absolute row/col index per (kernel tap, patch)
    rows = (jnp.arange(kh)[:, None] * dh
            + jnp.arange(n_h)[None, :] * sh)          # (kh, n_h)
    colsi = (jnp.arange(kw)[:, None] * dw
             + jnp.arange(n_w)[None, :] * sw)         # (kw, n_w)
    out = jnp.zeros((n, c, hp, wp), x.dtype)
    # scatter-add all taps at once: index grids broadcast to cols' layout
    r = rows[None, None, :, None, :, None]
    cc = colsi[None, None, None, :, None, :]
    out = out.at[
        jnp.arange(n)[:, None, None, None, None, None],
        jnp.arange(c)[None, :, None, None, None, None],
        r, cc].add(cols)
    return out[:, :, ph:ph + oh, pw:pw + ow]


def max_unpool2d(x, indices, kernel_size, stride=None, padding=0,
                 output_size=None, data_format="NCHW"):
    """Scatter pooled values back to their argmax positions (indices are
    flat per (n, c) spatial offsets — the paddle/torch convention)."""
    if data_format != "NCHW":
        raise ValueError("max_unpool2d supports data_format='NCHW' only")
    k = _pair(kernel_size)
    s = _pair(stride) if stride is not None else k
    p = _pair(padding)
    n, c, h, w = x.shape
    if output_size is None:
        oh = (h - 1) * s[0] - 2 * p[0] + k[0]
        ow = (w - 1) * s[1] - 2 * p[1] + k[1]
    else:  # paddle/torch accept a full (N, C, H, W) shape too
        osz = list(output_size)
        oh, ow = int(osz[-2]), int(osz[-1])
    flat = jnp.zeros((n, c, oh * ow), x.dtype)
    idx = indices.astype(jnp.int32).reshape(n, c, h * w)
    flat = flat.at[jnp.arange(n)[:, None, None],
                   jnp.arange(c)[None, :, None], idx].set(
        x.reshape(n, c, h * w))
    return flat.reshape(n, c, oh, ow)


def cosine_embedding_loss(input1, input2, label, margin=0.0,
                          reduction="mean"):
    x1 = input1.astype(jnp.float32)
    x2 = input2.astype(jnp.float32)
    cos = jnp.sum(x1 * x2, -1) / jnp.maximum(
        jnp.linalg.norm(x1, axis=-1) * jnp.linalg.norm(x2, axis=-1), 1e-12)
    lab = label.astype(jnp.float32)
    loss = jnp.where(lab > 0, 1.0 - cos, jnp.maximum(cos - margin, 0.0))
    return _reduce_loss(loss, reduction)


def affine_grid(theta, out_shape, align_corners=True):
    """theta (N, 2, 3) -> sampling grid (N, H, W, 2) in [-1, 1] coords."""
    n, _, h, w = [int(v) for v in out_shape]
    if align_corners:
        xs = jnp.linspace(-1.0, 1.0, w)
        ys = jnp.linspace(-1.0, 1.0, h)
    else:
        xs = (jnp.arange(w) * 2.0 + 1.0) / w - 1.0
        ys = (jnp.arange(h) * 2.0 + 1.0) / h - 1.0
    gx, gy = jnp.meshgrid(xs, ys, indexing="xy")     # (h, w)
    base = jnp.stack([gx, gy, jnp.ones_like(gx)], axis=-1)  # (h, w, 3)
    return jnp.einsum("hwk,njk->nhwj", base,
                      theta.astype(jnp.float32)).astype(theta.dtype)


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True):
    """Sample x (N, C, H, W) at normalized grid (N, Hg, Wg, 2) coords.

    bilinear/nearest; padding zeros/border/reflection. All-gather based —
    XLA lowers the 4 corner gathers the same way deform_conv2d's do."""
    if mode not in ("bilinear", "nearest"):
        raise ValueError(f"grid_sample mode must be 'bilinear' or "
                         f"'nearest', got {mode!r}")
    if padding_mode not in ("zeros", "border", "reflection"):
        raise ValueError(f"grid_sample padding_mode must be zeros/border/"
                         f"reflection, got {padding_mode!r}")
    n, c, h, w = x.shape
    g = grid.astype(jnp.float32)

    def unnorm(v, size):
        if align_corners:
            return (v + 1.0) / 2.0 * (size - 1)
        return ((v + 1.0) * size - 1.0) / 2.0

    gx = unnorm(g[..., 0], w)
    gy = unnorm(g[..., 1], h)

    def reflect(v, size):
        if size <= 1:
            return jnp.zeros_like(v)
        span = 2.0 * (size - 1) if align_corners else 2.0 * size
        off = 0.0 if align_corners else 0.5
        v2 = jnp.mod(v + off, span)
        v2 = jnp.minimum(v2, span - v2)
        return v2 - off

    if padding_mode == "reflection":
        gx = reflect(gx, w)
        gy = reflect(gy, h)

    def sample(ix, iy):
        inside = (ix >= 0) & (ix <= w - 1) & (iy >= 0) & (iy <= h - 1)
        cx = jnp.clip(ix, 0, w - 1).astype(jnp.int32)
        cy = jnp.clip(iy, 0, h - 1).astype(jnp.int32)
        v = x[jnp.arange(n)[:, None, None], :, cy, cx]   # (n, hg, wg, c)
        if padding_mode == "zeros":
            v = v * inside[..., None].astype(x.dtype)
        return v

    if mode == "nearest":
        out = sample(jnp.round(gx), jnp.round(gy))
    else:
        x0, y0 = jnp.floor(gx), jnp.floor(gy)
        wx, wy = gx - x0, gy - y0
        v00 = sample(x0, y0)
        v01 = sample(x0 + 1, y0)
        v10 = sample(x0, y0 + 1)
        v11 = sample(x0 + 1, y0 + 1)
        wx = wx[..., None].astype(x.dtype)
        wy = wy[..., None].astype(x.dtype)
        out = (v00 * (1 - wx) * (1 - wy) + v01 * wx * (1 - wy)
               + v10 * (1 - wx) * wy + v11 * wx * wy)
    return jnp.moveaxis(out, -1, 1)                       # (n, c, hg, wg)


def max_pool2d_with_index(x, kernel_size, stride=None, padding=0,
                          ceil_mode=False, data_format="NCHW"):
    """Max pool returning (values, flat argmax indices over H*W) — the
    paddle return_mask=True contract, feeding max_unpool2d. Candidates
    are gathered per kernel tap (kh*kw stacked slices) and argmax'd; the
    taps are few, so this stays a handful of fused XLA slices."""
    if ceil_mode:
        raise NotImplementedError("ceil_mode with return_mask is not "
                                  "implemented")
    if data_format != "NCHW":
        raise ValueError("return_mask supports data_format='NCHW' only")
    k = _pair(kernel_size)
    st = _pair(stride) if stride is not None else k
    p = _pair(padding)
    n, c, h, w = x.shape
    xp = jnp.pad(x, [(0, 0), (0, 0), (p[0], p[0]), (p[1], p[1])],
                 constant_values=-jnp.inf)
    hp, wp = h + 2 * p[0], w + 2 * p[1]
    oh = (hp - k[0]) // st[0] + 1
    ow = (wp - k[1]) // st[1] + 1
    vals, idxs = [], []
    # absolute (unpadded) flat index per tap and output cell
    oy = jnp.arange(oh)[:, None] * st[0] - p[0]
    ox = jnp.arange(ow)[None, :] * st[1] - p[1]
    for i in range(k[0]):
        for j in range(k[1]):
            sl = jax.lax.slice(
                xp, (0, 0, i, j),
                (n, c, i + (oh - 1) * st[0] + 1, j + (ow - 1) * st[1] + 1),
                (1, 1, st[0], st[1]))
            vals.append(sl)
            idxs.append(((oy + i) * w + (ox + j))[None, None])
    stacked = jnp.stack(vals)                           # (taps, n, c, oh, ow)
    tap = jnp.argmax(stacked, axis=0)
    out = jnp.max(stacked, axis=0)
    flat_idx = jnp.stack([jnp.broadcast_to(ix, (n, c, oh, ow))
                          for ix in idxs])
    indices = jnp.take_along_axis(flat_idx, tap[None], axis=0)[0]
    return out, indices.astype(jnp.int32)


# ------------------------------------------------- round-4 coverage ops
# (tools/api_inventory.py audit — verdict r3 #6)

def adaptive_avg_pool1d(x, output_size):
    o = output_size if isinstance(output_size, int) else output_size[0]
    n, c, l = x.shape
    if l % o == 0:
        return x.reshape(n, c, o, l // o).mean(axis=3)
    cols = [x[:, :, (i * l) // o: -(-((i + 1) * l) // o)].mean(axis=2)
            for i in range(o)]
    return jnp.stack(cols, axis=-1)


def adaptive_max_pool1d(x, output_size):
    o = output_size if isinstance(output_size, int) else output_size[0]
    n, c, l = x.shape
    if l % o == 0:
        return x.reshape(n, c, o, l // o).max(axis=3)
    cols = [x[:, :, (i * l) // o: -(-((i + 1) * l) // o)].max(axis=2)
            for i in range(o)]
    return jnp.stack(cols, axis=-1)


def adaptive_max_pool3d(x, output_size):
    out = _triple_(output_size)
    n, c, d, h, w = x.shape
    od, oh, ow = out
    if d % od == 0 and h % oh == 0 and w % ow == 0:
        return x.reshape(n, c, od, d // od, oh, h // oh, ow, w // ow).max(
            axis=(3, 5, 7))

    def win_max(di, hi, wi):
        ds, de = (di * d) // od, -(-((di + 1) * d) // od)
        hs, he = (hi * h) // oh, -(-((hi + 1) * h) // oh)
        ws, we = (wi * w) // ow, -(-((wi + 1) * w) // ow)
        return x[:, :, ds:de, hs:he, ws:we].max(axis=(2, 3, 4))

    planes = [jnp.stack(
        [jnp.stack([win_max(i, j, l_) for l_ in range(ow)], axis=-1)
         for j in range(oh)], axis=-2) for i in range(od)]
    return jnp.stack(planes, axis=-3)


def _conv_transpose_nd(x, weight, bias, stride, padding, output_padding,
                       dilation, groups, nd, fmt):
    """Shared gradient-of-conv formulation (see conv2d_transpose)."""
    def _nt(v):
        if isinstance(v, (list, tuple)):
            return tuple(int(i) for i in v)
        return (int(v),) * nd

    stride, dilation, output_padding = _nt(stride), _nt(dilation), \
        _nt(output_padding)
    ks = weight.shape[-nd:]
    if isinstance(padding, str):
        raise NotImplementedError("string padding for conv_transpose")
    padp = _conv_padding(padding, ks, stride, dilation, nd)
    pads = []
    for (plo, phi), k, dl, op_ in zip(padp, ks, dilation, output_padding):
        eff_k = (k - 1) * dl + 1
        pads.append((eff_k - 1 - plo, eff_k - 1 - phi + op_))
    if groups == 1:
        w = jnp.swapaxes(weight, 0, 1)
    else:
        cin, cog = weight.shape[0], weight.shape[1]
        w = weight.reshape((groups, cin // groups, cog) + ks)
        w = jnp.swapaxes(w, 1, 2).reshape(
            (groups * cog, cin // groups) + ks)
    w = jnp.flip(w, axis=tuple(range(-nd, 0)))
    dn = lax.conv_dimension_numbers(x.shape, w.shape, fmt)
    out = lax.conv_general_dilated(
        x, w, window_strides=(1,) * nd, padding=pads,
        lhs_dilation=stride, rhs_dilation=dilation, dimension_numbers=dn,
        feature_group_count=groups)
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     data_format="NCL"):
    if data_format != "NCL":
        raise NotImplementedError(
            "conv1d_transpose supports NCL only; transpose the input")
    return _conv_transpose_nd(x, weight, bias, stride, padding,
                              output_padding, dilation, groups, 1,
                              ("NCH", "OIH", "NCH"))


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     data_format="NCDHW"):
    if data_format != "NCDHW":
        raise NotImplementedError(
            "conv3d_transpose supports NCDHW only; transpose the input")
    return _conv_transpose_nd(x, weight, bias, stride, padding,
                              output_padding, dilation, groups, 3,
                              ("NCDHW", "OIDHW", "NCDHW"))
