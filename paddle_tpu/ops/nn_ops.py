"""NN op lowerings: conv/pool/norm/softmax/losses/dropout/embedding/etc.

Reference kernels: paddle/fluid/operators/{conv,pool,batch_norm,layer_norm,
softmax,cross_entropy,dropout,lookup_table,lrn,...}_op.* (+ cuDNN variants).
On TPU the conv/matmul lowerings feed the MXU via lax.conv_general_dilated /
dot_general (MXU accumulates bf16 in f32 in hardware); elementwise ops are left
to XLA fusion, which is what the cuDNN fused kernels hand-coded.
"""
from __future__ import annotations

import weakref

import numpy as np

from .. import observability as obs
from ..registry import register
from .common import mixed_dtypes


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * n


@register("conv2d", "depthwise_conv2d")
def _conv2d(ctx, op):
    import jax
    import jax.numpy as jnp

    x = ctx.get_input(op, "Input")  # NCHW
    w = ctx.get_input(op, "Filter")  # OIHW (I = C/groups)
    x, w = mixed_dtypes(x, w)
    strides = _pair(op.attrs.get("strides", [1, 1]))
    pads = _pair(op.attrs.get("paddings", [0, 0]))
    dil = _pair(op.attrs.get("dilations", [1, 1]))
    groups = op.attrs.get("groups", 1) or 1
    if op.type == "depthwise_conv2d":
        groups = x.shape[1]
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dil,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
    ).astype(x.dtype)
    ctx.set_output(op, "Output", out)


@register("conv3d")
def _conv3d(ctx, op):
    import jax
    import jax.numpy as jnp

    x = ctx.get_input(op, "Input")  # NCDHW
    w = ctx.get_input(op, "Filter")
    x, w = mixed_dtypes(x, w)
    strides = _pair(op.attrs.get("strides", [1, 1, 1]), 3)
    pads = _pair(op.attrs.get("paddings", [0, 0, 0]), 3)
    dil = _pair(op.attrs.get("dilations", [1, 1, 1]), 3)
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=[(p, p) for p in pads],
        rhs_dilation=dil,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=op.attrs.get("groups", 1) or 1,
    ).astype(x.dtype)
    ctx.set_output(op, "Output", out)


@register("conv2d_transpose")
def _conv2d_transpose(ctx, op):
    import jax
    import jax.numpy as jnp

    x = ctx.get_input(op, "Input")  # NCHW
    w = ctx.get_input(op, "Filter")  # [in_c, out_c/groups, kh, kw]
    x, w = mixed_dtypes(x, w)
    strides = _pair(op.attrs.get("strides", [1, 1]))
    pads = _pair(op.attrs.get("paddings", [0, 0]))
    dil = _pair(op.attrs.get("dilations", [1, 1]))
    groups = op.attrs.get("groups", 1) or 1
    kh, kw = w.shape[2], w.shape[3]
    # transposed conv = lhs-dilated conv with flipped, transposed kernel
    w_t = jnp.swapaxes(w, 0, 1)[:, :, ::-1, ::-1]  # [out_c/g, in_c, kh, kw]
    if groups > 1:
        # regroup: incoming w is [in_c, out_c/g, ...] with in_c = g * (in_c/g)
        in_c = x.shape[1]
        w_g = w.reshape(groups, in_c // groups, w.shape[1], kh, kw)
        w_t = jnp.concatenate([jnp.swapaxes(w_g[g], 0, 1)[:, :, ::-1, ::-1] for g in range(groups)], axis=0)
    out = jax.lax.conv_general_dilated(
        x,
        w_t,
        window_strides=(1, 1),
        padding=[
            (dil[0] * (kh - 1) - pads[0], dil[0] * (kh - 1) - pads[0]),
            (dil[1] * (kw - 1) - pads[1], dil[1] * (kw - 1) - pads[1]),
        ],
        lhs_dilation=strides,
        rhs_dilation=dil,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
    ).astype(x.dtype)
    ctx.set_output(op, "Output", out)


@register("conv3d_transpose")
def _conv3d_transpose(ctx, op):
    import jax
    import jax.numpy as jnp

    x = ctx.get_input(op, "Input")
    w = ctx.get_input(op, "Filter")
    strides = _pair(op.attrs.get("strides", [1, 1, 1]), 3)
    pads = _pair(op.attrs.get("paddings", [0, 0, 0]), 3)
    ks = w.shape[2:]
    w_t = jnp.swapaxes(w, 0, 1)[:, :, ::-1, ::-1, ::-1]
    out = jax.lax.conv_general_dilated(
        x,
        w_t,
        window_strides=(1, 1, 1),
        padding=[(k - 1 - p, k - 1 - p) for k, p in zip(ks, pads)],
        lhs_dilation=strides,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
    ).astype(x.dtype)
    ctx.set_output(op, "Output", out)


def _pool(ctx, op, nd):
    import jax
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    ptype = op.attrs.get("pooling_type", "max")
    ksize = _pair(op.attrs.get("ksize"), nd)
    strides = _pair(op.attrs.get("strides", [1] * nd), nd)
    pads = _pair(op.attrs.get("paddings", [0] * nd), nd)
    if op.attrs.get("global_pooling", False):
        ksize = x.shape[2:]
        pads = (0,) * nd
        strides = (1,) * nd
    window = (1, 1) + ksize
    wstrides = (1, 1) + strides
    # ceil_mode: extend high-side padding so the last partial window counts
    pads_hi = list(pads)
    if op.attrs.get("ceil_mode", False):
        for i in range(nd):
            in_sz = x.shape[2 + i]
            out_sz = -(-(in_sz - ksize[i] + 2 * pads[i]) // strides[i]) + 1  # ceil div
            needed = (out_sz - 1) * strides[i] + ksize[i] - in_sz - pads[i]
            pads_hi[i] = max(needed, pads[i])
    padding = ((0, 0), (0, 0)) + tuple((p, ph) for p, ph in zip(pads, pads_hi))
    if ptype == "max":
        init = -jnp.inf if np.issubdtype(np.dtype(str(x.dtype).replace("bfloat16", "float32")), np.floating) else np.iinfo(np.int32).min
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, wstrides, padding)
    else:
        s = jax.lax.reduce_window(x.astype(jnp.float32), 0.0, jax.lax.add, window, wstrides, padding)
        if op.attrs.get("exclusive", True) and (any(pads) or any(pads_hi)):
            ones = jnp.ones(x.shape, jnp.float32)
            cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, wstrides, padding)
            out = (s / cnt).astype(x.dtype)
        else:
            out = (s / float(np.prod(ksize))).astype(x.dtype)
    ctx.set_output(op, "Out", out)


@register("pool2d")
def _pool2d(ctx, op):
    _pool(ctx, op, 2)


@register("pool3d")
def _pool3d(ctx, op):
    _pool(ctx, op, 3)


@register("batch_norm")
def _batch_norm(ctx, op):
    import jax
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    scale = ctx.get_input(op, "Scale")
    bias = ctx.get_input(op, "Bias")
    mean = ctx.get_input(op, "Mean")
    var = ctx.get_input(op, "Variance")
    eps = op.attrs.get("epsilon", 1e-5)
    momentum = op.attrs.get("momentum", 0.9)
    is_test = op.attrs.get("is_test", False) or ctx.is_test
    layout = op.attrs.get("data_layout", "NCHW")
    axes = tuple(i for i in range(x.ndim) if i != (1 if layout == "NCHW" else x.ndim - 1))
    shape = [1] * x.ndim
    shape[1 if layout == "NCHW" else -1] = -1

    xf = x.astype(jnp.float32)
    if is_test:
        m, v = mean, var
        saved_m, saved_v = mean, var
    else:
        m = jnp.mean(xf, axis=axes)
        v = jnp.var(xf, axis=axes)
        saved_m, saved_v = m, v
        # f32 stat math, stored back in the stat vars' own dtype — a dtype
        # change between input and output state would retrigger jit
        new_mean = mean.astype(jnp.float32) * momentum + jax.lax.stop_gradient(m) * (1 - momentum)
        new_var = var.astype(jnp.float32) * momentum + jax.lax.stop_gradient(v) * (1 - momentum)
        ctx.set_output(op, "MeanOut", new_mean.astype(mean.dtype))
        ctx.set_output(op, "VarianceOut", new_var.astype(var.dtype))
    inv = jax.lax.rsqrt(v + eps)
    y = (xf - m.reshape(shape)) * inv.reshape(shape) * scale.reshape(shape) + bias.reshape(shape)
    ctx.set_output(op, "Y", y.astype(x.dtype))
    ctx.set_output(op, "SavedMean", saved_m)
    ctx.set_output(op, "SavedVariance", saved_v)
    if is_test:
        ctx.set_output(op, "MeanOut", mean)
        ctx.set_output(op, "VarianceOut", var)


@register("layer_norm")
def _layer_norm(ctx, op):
    import jax
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    begin = op.attrs.get("begin_norm_axis", 1)
    eps = op.attrs.get("epsilon", 1e-5)
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=axes, keepdims=True)
    v = jnp.var(xf, axis=axes, keepdims=True)
    y = (xf - m) * jax.lax.rsqrt(v + eps)
    scale = ctx.get_input(op, "Scale")
    bias = ctx.get_input(op, "Bias")
    norm_shape = (1,) * begin + tuple(x.shape[begin:])
    if scale is not None:
        y = y * scale.reshape(norm_shape)
    if bias is not None:
        y = y + bias.reshape(norm_shape)
    ctx.set_output(op, "Y", y.astype(x.dtype))
    ctx.set_output(op, "Mean", m.reshape(x.shape[:begin]))
    ctx.set_output(op, "Variance", v.reshape(x.shape[:begin]))


@register("lrn")
def _lrn(ctx, op):
    import jax
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")  # NCHW
    n = op.attrs.get("n", 5)
    k = op.attrs.get("k", 1.0)
    alpha = op.attrs.get("alpha", 1e-4)
    beta = op.attrs.get("beta", 0.75)
    sq = x * x
    half = n // 2
    acc = jax.lax.reduce_window(
        sq, 0.0, jax.lax.add, (1, n, 1, 1), (1, 1, 1, 1), ((0, 0), (half, half), (0, 0), (0, 0))
    )
    div = (k + alpha * acc) ** beta
    ctx.set_output(op, "Out", x / div)
    ctx.set_output(op, "MidOut", k + alpha * acc)
    del jnp


# training dropouts whose mask has been counted: an op is lowered more than
# once (the executor's discovery trace, a retrace at a new feed shape)
_MASKS_COUNTED = weakref.WeakSet()


def _keep_mask(ctx, op, keep_prob, shape):
    """A training dropout's keep bits, drawn ONCE as a one-byte array that the
    forward select and every backward consumer read.

    The bits come from XLA's ``RngBitGenerator`` (a key of implementation
    ``rbg``), which the compiler neither fuses nor duplicates.  A threefry
    key's bits are a pure elementwise function of a counter, so XLA
    re-derived all 20 rounds over the whole mask inside every fusion that read
    it: three to five times a mask in a Transformer's step.  The seed is what
    ``op_key`` gives every random op (positional, so a replay under
    ``jax.checkpoint`` draws the same bits; a new key every step; pinned by a
    ``seed`` attr), and the draw is ``jax.random.bernoulli``'s own: a 32-bit
    uniform against ``keep_prob``."""
    import jax

    key = ctx.op_key(op, op.attrs.get("seed", 0) or 0)
    words = jax.random.split(key).reshape(4)  # an rbg key is four words
    keep = jax.random.bernoulli(
        jax.random.wrap_key_data(words, impl="rbg"), keep_prob, shape)
    if op not in _MASKS_COUNTED:
        _MASKS_COUNTED.add(op)
        obs.counter("dropout.masks", labels={"impl": "rbg"}).inc()
        obs.counter("dropout.mask_elements").inc(int(np.prod(shape)))
    return keep


@register("dropout")
def _dropout(ctx, op):
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    p = op.attrs.get("dropout_prob", 0.5)
    is_test = op.attrs.get("is_test", False) or ctx.is_test
    impl = op.attrs.get("dropout_implementation", "downgrade_in_infer")
    if is_test:
        out = x * (1.0 - p) if impl == "downgrade_in_infer" else x
        ctx.set_output(op, "Out", out)
        return
    keep = _keep_mask(ctx, op, 1.0 - p, x.shape)
    kept = x / max(1.0 - p, 1e-8) if impl == "upscale_in_train" else x
    ctx.set_output(op, "Out", jnp.where(keep, kept, 0.0).astype(x.dtype))
    # for whoever fetches it; nothing in the step reads this copy
    ctx.set_output(op, "Mask", keep.astype(x.dtype))
    ctx.copy_lengths(op.inputs["X"][0], op.outputs["Out"][0])


@register("softmax")
def _softmax(ctx, op):
    import jax

    x = ctx.get_input(op, "X")
    ctx.set_output(op, "Out", jax.nn.softmax(x.astype("float32"), axis=-1).astype(x.dtype))


@register("cross_entropy")
def _cross_entropy(ctx, op):
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")  # probs [..., C]
    label = ctx.get_input(op, "Label")
    soft = op.attrs.get("soft_label", False)
    ignore = op.attrs.get("ignore_index", -100)
    xf = jnp.clip(x.astype(jnp.float32), 1e-20, 1.0)
    if soft:
        loss = -jnp.sum(label.astype(jnp.float32) * jnp.log(xf), axis=-1, keepdims=True)
    else:
        lab = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 else label
        picked = jnp.take_along_axis(jnp.log(xf), lab[..., None].astype("int32"), axis=-1)
        loss = -picked
        loss = jnp.where(lab[..., None] == ignore, 0.0, loss)
    ctx.set_output(op, "Y", loss.astype(x.dtype))


@register("softmax_with_cross_entropy")
def _softmax_with_cross_entropy(ctx, op):
    import jax
    import jax.numpy as jnp

    logits = ctx.get_input(op, "Logits")
    label = ctx.get_input(op, "Label")
    soft = op.attrs.get("soft_label", False)
    ignore = op.attrs.get("ignore_index", -100)
    lf = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(lf, axis=-1)
    if soft:
        loss = -jnp.sum(label.astype(jnp.float32) * logp, axis=-1, keepdims=True)
    else:
        lab = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 else label
        loss = -jnp.take_along_axis(logp, lab[..., None].astype("int32"), axis=-1)
        loss = jnp.where(lab[..., None] == ignore, 0.0, loss)
    ctx.set_output(op, "Softmax", jnp.exp(logp).astype(logits.dtype))
    ctx.set_output(op, "Loss", loss.astype(logits.dtype))


@register("square_error_cost")
def _square_error_cost(ctx, op):
    x = ctx.get_input(op, "X")
    y = ctx.get_input(op, "Y")
    d = x - y
    ctx.set_output(op, "Out", d * d)


@register("smooth_l1_loss")
def _smooth_l1(ctx, op):
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    y = ctx.get_input(op, "Y")
    sigma = op.attrs.get("sigma", 1.0)
    iw = ctx.get_input(op, "InsideWeight")
    ow = ctx.get_input(op, "OutsideWeight")
    s2 = sigma * sigma
    d = x - y
    if iw is not None:
        d = d * iw
    ad = jnp.abs(d)
    loss = jnp.where(ad < 1.0 / s2, 0.5 * d * d * s2, ad - 0.5 / s2)
    if ow is not None:
        loss = loss * ow
    out = jnp.sum(loss.reshape(loss.shape[0], -1), axis=1, keepdims=True)
    ctx.set_output(op, "Diff", d)
    ctx.set_output(op, "Out", out)


@register("dice_loss")
def _dice_loss(ctx, op):
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    label = ctx.get_input(op, "Label").astype(x.dtype)
    eps = op.attrs.get("epsilon", 1e-5)
    x2 = x.reshape(x.shape[0], -1)
    l2 = label.reshape(label.shape[0], -1)
    inter = jnp.sum(x2 * l2, axis=1)
    union = jnp.sum(x2, axis=1) + jnp.sum(l2, axis=1)
    dice = 1.0 - (2.0 * inter + eps) / (union + eps)
    ctx.set_output(op, "Out", jnp.mean(dice).reshape(1))


@register("rank_loss")
def _rank_loss(ctx, op):
    import jax.numpy as jnp

    label = ctx.get_input(op, "Label")
    left = ctx.get_input(op, "Left")
    right = ctx.get_input(op, "Right")
    d = left - right
    ctx.set_output(op, "Out", jnp.log1p(jnp.exp(d)) - label * d)


@register("margin_rank_loss")
def _margin_rank_loss(ctx, op):
    import jax.numpy as jnp

    label = ctx.get_input(op, "Label")
    x1 = ctx.get_input(op, "X1")
    x2 = ctx.get_input(op, "X2")
    m = op.attrs.get("margin", 0.1)
    ctx.set_output(op, "Out", jnp.maximum(0.0, -label * (x1 - x2) + m))


@register("huber_loss")
def _huber_loss(ctx, op):
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    y = ctx.get_input(op, "Y")
    delta = op.attrs.get("delta", 1.0)
    d = y - x
    ad = jnp.abs(d)
    loss = jnp.where(ad <= delta, 0.5 * d * d, delta * (ad - 0.5 * delta))
    ctx.set_output(op, "Out", loss)
    ctx.set_output(op, "Residual", d)


@register("log_loss")
def _log_loss(ctx, op):
    import jax.numpy as jnp

    p = ctx.get_input(op, "Predicted")
    label = ctx.get_input(op, "Labels")
    eps = op.attrs.get("epsilon", 1e-4)
    out = -label * jnp.log(p + eps) - (1 - label) * jnp.log(1 - p + eps)
    ctx.set_output(op, "Loss", out)


@register("sigmoid_cross_entropy_with_logits")
def _sigmoid_ce(ctx, op):
    import jax.numpy as jnp

    x = ctx.get_input(op, "X").astype(jnp.float32)
    label = ctx.get_input(op, "Label").astype(jnp.float32)
    loss = jnp.maximum(x, 0.0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = op.attrs.get("ignore_index", -100)
    loss = jnp.where(label == ignore, 0.0, loss)
    ctx.set_output(op, "Out", loss)


@register("lookup_table")
def _lookup_table(ctx, op):
    import jax.numpy as jnp

    w = ctx.get_input(op, "W")  # [V, D]
    ids = ctx.get_input(op, "Ids")
    padding_idx = op.attrs.get("padding_idx", -1)
    flat = ids.reshape(ids.shape[:-1]) if (ids.ndim > 1 and ids.shape[-1] == 1) else ids
    out = jnp.take(w, flat.astype("int32"), axis=0)
    if padding_idx is not None and padding_idx >= 0:
        out = jnp.where((flat == padding_idx)[..., None], 0.0, out)
    ctx.set_output(op, "Out", out)
    ctx.copy_lengths(op.inputs["Ids"][0], op.outputs["Out"][0])


@register("accuracy")
def _accuracy(ctx, op):
    import jax.numpy as jnp

    idx = ctx.get_input(op, "Indices")  # [N, k] topk indices
    label = ctx.get_input(op, "Label")  # [N, 1]
    correct = jnp.any(idx == label.astype(idx.dtype), axis=-1)
    n = correct.shape[0]
    num_correct = jnp.sum(correct.astype(jnp.float32))
    ctx.set_output(op, "Accuracy", (num_correct / n).reshape(1))
    ctx.set_output(op, "Correct", num_correct.astype("int32").reshape(1))
    ctx.set_output(op, "Total", jnp.asarray([n], dtype="int32"))


@register("auc")
def _auc(ctx, op):
    import jax.numpy as jnp

    prob = ctx.get_input(op, "Predict")  # [N, 2]
    label = ctx.get_input(op, "Label").reshape(-1)
    pos_score = prob[:, 1]
    num_bins = op.attrs.get("num_thresholds", 4095) + 1
    bins = jnp.clip((pos_score * num_bins).astype("int32"), 0, num_bins - 1)
    is_pos = (label > 0).astype(jnp.float32)
    pos_hist = jnp.zeros(num_bins).at[bins].add(is_pos)
    neg_hist = jnp.zeros(num_bins).at[bins].add(1.0 - is_pos)
    # stat accumulators threaded as persistable state
    stat_pos = ctx.get_input(op, "StatPos")
    stat_neg = ctx.get_input(op, "StatNeg")
    if stat_pos is not None:
        pos_hist = pos_hist + stat_pos
        neg_hist = neg_hist + stat_neg
        ctx.set_output(op, "StatPosOut", pos_hist)
        ctx.set_output(op, "StatNegOut", neg_hist)
    tot_pos = jnp.cumsum(pos_hist[::-1])[::-1]
    tot_neg = jnp.cumsum(neg_hist[::-1])[::-1]
    tp = jnp.concatenate([tot_pos, jnp.zeros(1)])
    fp = jnp.concatenate([tot_neg, jnp.zeros(1)])
    auc = jnp.sum((fp[:-1] - fp[1:]) * (tp[:-1] + tp[1:]) / 2.0)
    total_pos = tot_pos[0]
    total_neg = tot_neg[0]
    auc = jnp.where(total_pos * total_neg > 0, auc / jnp.maximum(total_pos * total_neg, 1.0), 0.5)
    ctx.set_output(op, "AUC", auc.reshape(1))


@register("mean_iou")
def _mean_iou(ctx, op):
    import jax.numpy as jnp

    pred = ctx.get_input(op, "Predictions").reshape(-1)
    label = ctx.get_input(op, "Labels").reshape(-1)
    n = op.attrs["num_classes"]
    idx = label.astype("int32") * n + pred.astype("int32")
    cm = jnp.zeros((n * n,)).at[idx].add(1.0).reshape(n, n)
    inter = jnp.diagonal(cm)
    union = cm.sum(0) + cm.sum(1) - inter
    valid = union > 0
    iou = jnp.where(valid, inter / jnp.maximum(union, 1.0), 0.0)
    miou = jnp.sum(iou) / jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
    ctx.set_output(op, "OutMeanIou", miou.reshape(1))
    ctx.set_output(op, "OutWrong", (cm.sum(1) - inter).astype("int32"))
    ctx.set_output(op, "OutCorrect", inter.astype("int32"))


@register("im2sequence")
def _im2sequence(ctx, op):
    import jax

    x = ctx.get_input(op, "X")  # NCHW
    kh, kw = _pair(op.attrs["kernels"])
    sh, sw = _pair(op.attrs.get("strides", [1, 1]))
    pt, pl, pb, pr = (op.attrs.get("paddings") or [0, 0, 0, 0])
    import jax.numpy as jnp

    xp = jnp.pad(x, [(0, 0), (0, 0), (pt, pb), (pl, pr)])
    patches = jax.lax.conv_general_dilated_patches(
        xp, (kh, kw), (sh, sw), "VALID", dimension_numbers=("NCHW", "OIHW", "NCHW")
    )  # [N, C*kh*kw, oh, ow]
    n, ckk, oh, ow = patches.shape
    out = patches.transpose(0, 2, 3, 1).reshape(n, oh * ow, ckk)
    # emit as padded sequence [N, oh*ow, C*kh*kw] with full lengths
    ctx.set_output(op, "Out", out)
    ctx.set_lengths(op.outputs["Out"][0], jnp.full((n,), oh * ow, dtype="int32"))


@register("bilinear_interp")
def _bilinear_interp(ctx, op):
    import jax

    x = ctx.get_input(op, "X")  # NCHW
    out_size = ctx.get_input(op, "OutSize")
    if out_size is not None:
        oh, ow = int(np.asarray(out_size)[0]), int(np.asarray(out_size)[1])
    else:
        oh, ow = op.attrs["out_h"], op.attrs["out_w"]
    out = jax.image.resize(x, (x.shape[0], x.shape[1], oh, ow), method="bilinear")
    ctx.set_output(op, "Out", out.astype(x.dtype))


@register("nearest_interp")
def _nearest_interp(ctx, op):
    import jax

    x = ctx.get_input(op, "X")
    oh, ow = op.attrs["out_h"], op.attrs["out_w"]
    out = jax.image.resize(x, (x.shape[0], x.shape[1], oh, ow), method="nearest")
    ctx.set_output(op, "Out", out.astype(x.dtype))


@register("roi_pool")
def _roi_pool(ctx, op):
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")  # [N, C, H, W]
    rois = ctx.get_input(op, "ROIs")  # [R, 4] (x1, y1, x2, y2); batch via lengths
    ph = op.attrs.get("pooled_height", 1)
    pw = op.attrs.get("pooled_width", 1)
    scale = op.attrs.get("spatial_scale", 1.0)
    roi_batch = ctx.get_lengths(op.inputs["ROIs"][0])
    n, c, h, w = x.shape
    r = rois.shape[0]
    if roi_batch is not None and roi_batch.shape[0] == r:
        batch_idx = roi_batch.astype("int32")
    else:
        batch_idx = jnp.zeros((r,), dtype="int32")

    x1 = jnp.round(rois[:, 0] * scale).astype("int32")
    y1 = jnp.round(rois[:, 1] * scale).astype("int32")
    x2 = jnp.round(rois[:, 2] * scale).astype("int32")
    y2 = jnp.round(rois[:, 3] * scale).astype("int32")
    rw = jnp.maximum(x2 - x1 + 1, 1)
    rh = jnp.maximum(y2 - y1 + 1, 1)

    ys = jnp.arange(h)
    xs = jnp.arange(w)

    def one_cell(i, j):
        hs = y1 + (i * rh) // ph
        he = y1 + ((i + 1) * rh + ph - 1) // ph
        ws = x1 + (j * rw) // pw
        we = x1 + ((j + 1) * rw + pw - 1) // pw
        ymask = (ys[None, :] >= hs[:, None]) & (ys[None, :] < jnp.maximum(he, hs + 1)[:, None])
        xmask = (xs[None, :] >= ws[:, None]) & (xs[None, :] < jnp.maximum(we, ws + 1)[:, None])
        m = ymask[:, None, :, None] & xmask[:, None, None, :]  # [R,1,H,W]
        feats = x[batch_idx]  # [R, C, H, W]
        return jnp.max(jnp.where(m, feats, -jnp.inf), axis=(2, 3))

    cells = [[one_cell(i, j) for j in range(pw)] for i in range(ph)]
    out = jnp.stack([jnp.stack(row, axis=-1) for row in cells], axis=-2)  # [R, C, ph, pw]
    ctx.set_output(op, "Out", out)
