"""Int8 EXECUTION for inference: quantized matmul/conv on the MXU.

The reference's int8 story stops at representation
(QuantizeTranspiler.convert_to_int8 stores int8 weights + scales;
inference dequantizes to float).  On TPU the MXU natively multiplies
int8 operands with int32 accumulation — 2× the bf16 MAC rate on v5e —
so this module goes the rest of the way:

- ``quantized_mul`` / ``quantized_conv2d`` op lowerings: dynamic
  per-tensor abs-max quantization of the activation (computed in-graph,
  fused by XLA), int8×int8 ``dot_general``/``conv_general_dilated`` with
  ``preferred_element_type=int32``, then one fused rescale
  ``acc * (sx * sw / 127²)`` with per-output-channel weight scales.
- ``Int8InferenceTranspiler``: rewrites an inference Program in place —
  each mul/conv2d weight is pre-quantized per output channel into
  ``<w>.int8`` + ``<w>.scale`` persistable vars and the op is switched to
  its quantized spelling.

Accuracy: symmetric per-channel weights + dynamic per-tensor activations
is the standard post-training recipe (~<1% top-1 loss on convnets).
"""
from __future__ import annotations

import os

import numpy as np

from ...core import cpu_backend
from ...registry import register
from .quantize_transpiler import quantize_weight_abs_max

__all__ = ["Int8InferenceTranspiler"]

_QMAX = 127.0


def _quantize_activation(x):
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    sx = jnp.maximum(jnp.abs(xf).max(), 1e-8)
    xq = jnp.clip(jnp.round(xf / sx * _QMAX), -_QMAX, _QMAX).astype(jnp.int8)
    return xq, sx


@register("quantized_mul")
def _quantized_mul(ctx, op):
    import jax.numpy as jnp
    from jax import lax

    x = ctx.get_input(op, "X")
    wq = ctx.get_input(op, "QWeight")   # int8 [K, N]
    ws = ctx.get_input(op, "WScale")    # f32 [N] per output channel
    xn = op.attrs.get("x_num_col_dims", 1)
    xs = x.shape
    from ...ops.common import dim_prod

    x2 = x.reshape((dim_prod(xs[:xn]), -1))
    xq, sx = _quantize_activation(x2)
    acc = lax.dot_general(
        xq, wq.astype(jnp.int8),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out = acc.astype(jnp.float32) * (sx / _QMAX) * (ws.reshape(-1) / _QMAX)[None, :]
    out = out.astype(x.dtype) if x.dtype == jnp.bfloat16 else out
    ctx.set_output(op, "Out", out.reshape(tuple(xs[:xn]) + (wq.shape[1],)))


# How the int8 conv reaches the MXU.  XLA maps int8×int8→int32
# ``dot_general`` onto the MXU's double-rate int8 path, but an integer
# ``conv_general_dilated`` may lower to a slow non-MXU path (the round-5
# on-chip capture measured the direct integer conv at ~1% of the bf16
# conv's throughput).  "matmul" decomposes the conv into kh·kw shifted
# int8 matmuls (same MACs, each one MXU-shaped); "conv" is the direct
# integer convolution; "dequant" skips activation quantization and runs
# a bf16 conv with dequantized weights (bf16 MAC rate, int8 STORAGE
# kept); "auto" picks per layer on TPU: matmul where the channel
# contraction is MXU-worthy, dequant for thin-channel convs (e.g. the
# RGB stem, whose per-tap K=3 matmuls would waste the 128-lane MXU),
# and conv elsewhere/CPU.
INT8_CONV_IMPL = os.environ.get("PADDLE_TPU_INT8_CONV_IMPL", "auto").strip().lower()
if INT8_CONV_IMPL not in ("auto", "matmul", "dequant", "conv"):
    import warnings

    warnings.warn(
        "PADDLE_TPU_INT8_CONV_IMPL=%r is not one of auto/matmul/dequant/"
        "conv; using 'auto'" % INT8_CONV_IMPL)
    INT8_CONV_IMPL = "auto"
_MATMUL_MIN_CIN = 16  # below this, per-tap K is too thin for the MXU


def _pick_conv_impl(on_tpu, groups, c_in):
    """Auto-mode per-layer engine choice (pure, unit-tested)."""
    if not on_tpu or groups != 1:
        return "conv"
    return "matmul" if c_in >= _MATMUL_MIN_CIN else "dequant"


def _int8_conv_as_matmuls(xq, wq, strides, pads, dil):
    """Integer conv via kernel-position decomposition: for each of the
    kh·kw filter taps, a strided slice of the (zero-padded) int8 input
    contracts its channel dim against that tap's [O, I] int8 matrix on
    the MXU (int32 accumulation); the kh·kw partial products sum in
    int32.  Symmetric abs-max quantization makes zero padding exact.
    Returns [N, O, OH, OW] int32."""
    import jax.numpy as jnp
    from jax import lax

    O, I, kh, kw = wq.shape
    sh, sw = strides
    ph, pw = pads
    dh, dw = dil
    xp = jnp.pad(xq, ((0, 0), (0, 0), (ph, ph), (pw, pw)))
    H, W = xp.shape[2], xp.shape[3]
    OH = (H - ((kh - 1) * dh + 1)) // sh + 1
    OW = (W - ((kw - 1) * dw + 1)) // sw + 1
    acc = None
    for di in range(kh):
        for dj in range(kw):
            xs = lax.slice(
                xp,
                (0, 0, di * dh, dj * dw),
                (xp.shape[0], xp.shape[1],
                 di * dh + (OH - 1) * sh + 1, dj * dw + (OW - 1) * sw + 1),
                (1, 1, sh, sw))                      # [N, I, OH, OW] int8
            # contract channels: [N, I, OH, OW] × [O, I] -> [N, OH, OW, O]
            part = lax.dot_general(
                xs, wq[:, :, di, dj],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
            acc = part if acc is None else acc + part
    return jnp.transpose(acc, (0, 3, 1, 2))


@register("quantized_conv2d")
def _quantized_conv2d(ctx, op):
    import jax
    import jax.numpy as jnp

    x = ctx.get_input(op, "Input")      # NCHW
    wq = ctx.get_input(op, "QWeight")   # int8 OIHW
    ws = ctx.get_input(op, "WScale")    # f32 [O]
    strides = list(op.attrs.get("strides", [1, 1]))
    pads = list(op.attrs.get("paddings", [0, 0]))
    dil = list(op.attrs.get("dilations", [1, 1]))
    groups = op.attrs.get("groups", 1) or 1
    impl = INT8_CONV_IMPL
    if impl == "auto":
        impl = _pick_conv_impl(not cpu_backend(), groups, int(wq.shape[1]))
    elif impl == "matmul" and groups > 1:
        import warnings

        warnings.warn(
            "PADDLE_TPU_INT8_CONV_IMPL=matmul does not cover grouped "
            "convolutions (groups=%d); this layer falls back to the direct "
            "integer conv, which is far slower on TPU" % groups)
    conv_kwargs = dict(
        window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dil,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
    )
    if impl == "dequant":
        # bf16 conv with dequantized weights: int8 storage preserved, MACs
        # at the bf16 rate — the right trade for thin-channel layers
        cdt = x.dtype if x.dtype == jnp.bfloat16 else jnp.float32
        wdq = (wq.astype(jnp.float32)
               * (ws.reshape(-1) / _QMAX)[:, None, None, None]).astype(cdt)
        out = jax.lax.conv_general_dilated(
            x.astype(cdt), wdq,
            preferred_element_type=jnp.float32, **conv_kwargs)
        out = out.astype(x.dtype) if x.dtype == jnp.bfloat16 else out
        ctx.set_output(op, "Output", out)
        return
    xq, sx = _quantize_activation(x)
    if impl == "matmul" and groups == 1:
        acc = _int8_conv_as_matmuls(xq, wq.astype(jnp.int8), strides, pads, dil)
    else:
        acc = jax.lax.conv_general_dilated(
            xq, wq.astype(jnp.int8),
            preferred_element_type=jnp.int32, **conv_kwargs)
    out = acc.astype(jnp.float32) * (sx / _QMAX) * (ws.reshape(-1) / _QMAX)[None, :, None, None]
    out = out.astype(x.dtype) if x.dtype == jnp.bfloat16 else out
    ctx.set_output(op, "Output", out)


class Int8InferenceTranspiler:
    """Rewrite an inference Program to execute int8 on the MXU.

    ``transpile(program, scope)`` pre-quantizes each mul/conv2d weight
    from ``scope`` (per output channel: axis 1 for mul's [K, N], axis 0
    for OIHW filters) into persistable ``<w>.int8`` / ``<w>.scale`` vars
    and switches the ops to quantized spellings.  Grouped/depthwise convs
    and ops whose weight is not a persistable parameter are left in
    float."""

    def __init__(self, weight_bits=8):
        if weight_bits != 8:
            raise ValueError("int8 execution supports weight_bits=8")

    def transpile(self, program, scope, quantize_ops=("mul", "conv2d")):
        blk = program.global_block()
        converted = {}
        for op in blk.ops:
            if op.type not in quantize_ops:
                continue
            slot = "Y" if op.type == "mul" else "Filter"
            in_slot = "X" if op.type == "mul" else "Input"
            wname = op.inputs[slot][0]
            wvar = blk.vars.get(wname)
            if wvar is None or not wvar.persistable:
                continue
            if op.type == "conv2d" and (op.attrs.get("groups", 1) or 1) != 1:
                continue
            if op.type == "mul" and op.attrs.get("y_num_col_dims", 1) != 1:
                continue
            if wname not in converted:
                w = np.asarray(scope[wname])
                axis = 1 if op.type == "mul" else 0
                q, s = quantize_weight_abs_max(w, 8, per_channel_axis=axis)
                qname, sname = wname + ".int8", wname + ".scale"
                scope[qname] = q
                scope[sname] = np.asarray(s, np.float32).reshape(-1)
                blk.create_var(name=qname, shape=list(q.shape), dtype="int8",
                               persistable=True)
                blk.create_var(name=sname, shape=[int(np.asarray(s).size)],
                               dtype="float32", persistable=True)
                converted[wname] = (qname, sname)
            qname, sname = converted[wname]
            op.type = "quantized_mul" if op.type == "mul" else "quantized_conv2d"
            op.inputs = {in_slot: list(op.inputs[in_slot]),
                         "QWeight": [qname], "WScale": [sname]}
        program._bump()
        return program
