"""Neural-network operators.

Parity with reference `src/operator/nn/` (Convolution, Deconvolution,
FullyConnected, BatchNorm, LayerNorm, Pooling, Activation, Dropout, LRN,
Softmax) plus the output/loss heads (`src/operator/softmax_output-inl.h`,
regression outputs) and the fused RNN op (`src/operator/rnn-inl.h:49`,
`cudnn_rnn-inl.h`).

TPU-first design notes:
- Convs/matmuls lower to `lax.conv_general_dilated` / `dot_general` so XLA
  tiles them onto the MXU; no im2col (reference `nn/im2col.h`) is needed.
- BatchNorm/bias/activation chains are left to XLA fusion instead of the
  reference's cuDNN fused kernels.
- The fused RNN op is a `lax.scan` over time — the compiler pipelines the
  per-step matmuls; this replaces cuDNN's fused multi-layer RNN.
- Output heads (SoftmaxOutput etc.) define their own gradient irrespective of
  the incoming cotangent, exactly like the reference ops; realised with
  `jax.custom_vjp`.
"""
from __future__ import annotations

import functools as _functools
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import register


# ---------------------------------------------------------------------------
# FullyConnected (reference nn/fully_connected-inl.h:84-104)
# ---------------------------------------------------------------------------
@register("FullyConnected")
def _fully_connected(params, data, weight, *bias):
    flatten = params.get("flatten", True)
    x = data.reshape(data.shape[0], -1) if flatten and data.ndim > 2 else data
    out = jnp.dot(x, weight.T)
    if not params.get("no_bias", False) and bias:
        out = out + bias[0]
    return (out,)


# ---------------------------------------------------------------------------
# Convolution (reference nn/convolution-inl.h; NCHW/OIHW layouts)
# ---------------------------------------------------------------------------
def _conv_dims(kernel):
    return len(kernel)


def _tup(v, n, default):
    if v is None or v == ():
        return (default,) * n
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


def _conv_dn(nd):
    spec = "DHW"[3 - nd:]
    return ("NC" + spec, "OI" + spec, "NC" + spec)


def _layout_spec(params, nd):
    """Resolve the op's `layout` attr (reference convolution-inl.h) to lax
    dimension-number specs + the channel axis.

    Channel-first (NCW/NCHW/NCDHW) keeps the reference default; channel-last
    (NWC/NHWC/NDHWC) is the TPU fast path — the feature dim lands on the
    lane (minor) dimension so XLA tiles the conv onto the MXU without
    relayout copies. Channel-last weights are O,spatial...,I (the reference's
    NHWC weight layout)."""
    spec = "DHW"[3 - nd:]
    layout = params.get("layout") or ("NC" + spec)
    if layout in (None, "None"):
        layout = "NC" + spec
    if layout == "NC" + spec:
        return ("NC" + spec, "OI" + spec, 1)
    if layout == "N" + spec + "C":
        return (layout, "O" + spec + "I", nd + 1)
    raise MXNetError("unsupported layout " + str(layout))


def _s2d_eligible(params, data, weight, kernel, stride, dilate, groups,
                  caxis):
    """True when the stride-2 small-input-channel stem rewrite applies
    (2-D NCHW conv, <=4 input channels, kernel <=8, no dilation/groups)
    and the op is lowering for a TPU — on the MXU a 3-channel conv wastes
    125 of 128 input lanes; the space-to-depth form packs 4x more.

    NHWC stems stay plain convolutions: the rewrite measured no faster
    there (PERF.md section 6, PR 30)."""
    if caxis != 1 or len(kernel) != 2 or groups != 1:
        return False
    if stride != (2, 2) or dilate != (1, 1):
        return False
    if weight.shape[1] > 4 or max(kernel) > 8:
        return False
    from .pallas_kernels import is_tpu
    if not is_tpu():
        return False
    ctx = params.get("_ctx")
    if ctx is not None and getattr(ctx, "device_type", None) \
            in ("cpu", "cpu_pinned", "cpu_shared"):
        return False
    return True


def _space_to_depth_conv(data, weight, pad):
    """EXACT rewrite of a stride-2 NCHW conv as a stride-1 conv over a
    2x2 space-to-depth input (the MLPerf-TPU ResNet stem trick): the 7x7x3
    kernel zero-pads to 8x8 and rearranges to 4x4x12, quadrupling MXU input
    -lane occupancy. Same function, same gradients — jax.vjp differentiates
    through the reshapes."""
    N, C, H, W = data.shape
    O, _, kh, kw = weight.shape
    ph, pw = pad
    out_h = (H + 2 * ph - kh) // 2 + 1
    out_w = (W + 2 * pw - kw) // 2 + 1
    kh8, kw8 = 2 * ((kh + 1) // 2), 2 * ((kw + 1) // 2)
    # padded input sized so the block-space valid conv covers every output
    need_h = 2 * (out_h - 1) + kh8
    need_w = 2 * (out_w - 1) + kw8
    eh, ew = max(need_h - H - ph, 0), max(need_w - W - pw, 0)
    # the 2x2 space-to-depth needs even padded extents; extra zero rows sit
    # beyond every tap the sliced output reads
    eh += (H + ph + eh) % 2
    ew += (W + pw + ew) % 2
    x = jnp.pad(data, ((0, 0), (0, 0), (ph, eh), (pw, ew)))
    Hp, Wp = x.shape[2], x.shape[3]
    # space-to-depth 2x2: channel order (c, a, b)
    x2 = x.reshape(N, C, Hp // 2, 2, Wp // 2, 2)
    x2 = x2.transpose(0, 1, 3, 5, 2, 4).reshape(N, C * 4, Hp // 2, Wp // 2)
    w8 = jnp.pad(weight, ((0, 0), (0, 0), (0, kh8 - kh), (0, kw8 - kw)))
    w2 = w8.reshape(O, C, kh8 // 2, 2, kw8 // 2, 2)
    w2 = w2.transpose(0, 1, 3, 5, 2, 4).reshape(O, C * 4, kh8 // 2, kw8 // 2)
    dn = lax.conv_dimension_numbers(x2.shape, w2.shape,
                                    ("NCHW", "OIHW", "NCHW"))
    out = lax.conv_general_dilated(x2, w2, (1, 1), [(0, 0), (0, 0)],
                                   dimension_numbers=dn)
    return out[:, :, :out_h, :out_w]


@register("Convolution")
def _convolution(params, data, weight, *bias):
    with jax.named_scope("convolution"):
        kernel = tuple(params["kernel"])
        nd = len(kernel)
        stride = _tup(params.get("stride"), nd, 1)
        dilate = _tup(params.get("dilate"), nd, 1)
        pad = _tup(params.get("pad"), nd, 0)
        groups = params.get("num_group", 1)
        dspec, wspec, caxis = _layout_spec(params, nd)
        if _s2d_eligible(params, data, weight, kernel, stride, dilate, groups,
                         caxis):
            out = _space_to_depth_conv(data, weight, pad)
        else:
            dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                            (dspec, wspec, dspec))
            # no preferred_element_type: the TPU MXU accumulates bf16 convs
            # in f32 natively, and forcing f32 here leaks an f32 cotangent
            # into the conv transpose rule, which rejects mixed bf16/f32
            # operands
            out = lax.conv_general_dilated(
                data, weight, window_strides=stride,
                padding=[(p, p) for p in pad],
                rhs_dilation=dilate, dimension_numbers=dn,
                feature_group_count=groups)
        if not params.get("no_bias", False) and bias:
            if caxis == 1:
                out = out + bias[0].reshape((1, -1) + (1,) * nd)
            else:
                out = out + bias[0]
        return (out,)


@register("Deconvolution")
def _deconvolution(params, data, weight, *bias):
    """Transposed conv via lhs-dilated conv (gradient-of-conv identity)."""
    kernel = tuple(params["kernel"])
    nd = len(kernel)
    stride = _tup(params.get("stride"), nd, 1)
    dilate = _tup(params.get("dilate"), nd, 1)
    pad = _tup(params.get("pad"), nd, 0)
    adj = _tup(params.get("adj"), nd, 0)
    groups = params.get("num_group", 1)
    # weight layout is (in_channels, out_channels//g, *kernel)
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    if groups == 1:
        w = jnp.swapaxes(w, 0, 1)
    else:
        ci, co_g = weight.shape[0], weight.shape[1]
        w = w.reshape((groups, ci // groups, co_g) + kernel)
        w = jnp.swapaxes(w, 1, 2).reshape((co_g * groups, ci // groups) + kernel)
    dn = lax.conv_dimension_numbers(data.shape, w.shape, _conv_dn(nd))
    padding = [(d * (k - 1) - p, d * (k - 1) - p + a)
               for k, p, a, d in zip(kernel, pad, adj, dilate)]
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd, padding=padding,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=groups)
    out = out.astype(data.dtype)
    if not params.get("no_bias", False) and bias:
        out = out + bias[0].reshape((1, -1) + (1,) * nd)
    return (out,)


# ---------------------------------------------------------------------------
# Pooling (reference nn/pooling-inl.h)
# ---------------------------------------------------------------------------
@register("Pooling", aliases=("Pooling_v1",))
def _pooling(params, data):
    with jax.named_scope("pooling"):
        pool_type = params.get("pool_type", "max")
        global_pool = params.get("global_pool", False)
        nd = data.ndim - 2
        _, _, caxis = _layout_spec(params, nd)
        spatial_axes = tuple(range(2, 2 + nd)) if caxis == 1 else \
            tuple(range(1, 1 + nd))
        if global_pool:
            kernel = tuple(data.shape[a] for a in spatial_axes)
            stride = (1,) * nd
            pad = pad_end = (0,) * nd
        else:
            kernel = _tup(params["kernel"], nd, 1)
            stride = _tup(params.get("stride"), nd, 1)
            pad = _tup(params.get("pad"), nd, 0)
            # pad_end: asymmetric begin/end padding (ONNX importer); padding
            # cells never join the max (init=-inf) and are excluded from the
            # avg count when count_include_pad=False, so semantics stay exact
            pad_end = _tup(params["pad_end"], nd, 0) if params.get("pad_end") \
                is not None else pad
            from ..base import MXNetError
            for i, (k, p, pe) in enumerate(zip(kernel, pad, pad_end)):
                if k > data.shape[spatial_axes[i]] + p + pe:
                    raise MXNetError(
                        "Pooling kernel %s exceeds padded input %s"
                        % (kernel, tuple(data.shape[a] for a in spatial_axes)))

        def _full(kern, strd, padd):
            if caxis == 1:
                return (1, 1) + tuple(kern), (1, 1) + tuple(strd), \
                    ((0, 0), (0, 0)) + tuple(padd)
            return (1,) + tuple(kern) + (1,), (1,) + tuple(strd) + (1,), \
                ((0, 0),) + tuple(padd) + ((0, 0),)

        window, strides, padding = _full(kernel, stride,
                                         list(zip(pad, pad_end)))
        if params.get("pooling_convention", "valid") == "full" \
                and not global_pool:
            # ceil-mode output: extend right/bottom padding as needed
            extra = []
            for i, (k, s, p, pe) in enumerate(zip(kernel, stride, pad,
                                                  pad_end)):
                in_sz = data.shape[spatial_axes[i]]
                out_full = int(np.ceil((in_sz + p + pe - k) / s)) + 1
                needed = (out_full - 1) * s + k - in_sz - p
                extra.append((p, max(needed, pe)))
            _, _, padding = _full(kernel, stride, extra)
        if pool_type == "max":
            init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) \
                else jnp.iinfo(data.dtype).min
            out = lax.reduce_window(data, init, lax.max, window, strides,
                                    padding)
            if params.get("_fold_relu"):
                # executor relu->maxpool fold: maxpool(relu(x)) ==
                # max(maxpool(x), 0); grads agree (see _plan_relu_pool_fold)
                out = jnp.maximum(out, jnp.zeros((), out.dtype))
        elif pool_type in ("avg", "sum"):
            out = lax.reduce_window(data, 0.0, lax.add, window, strides,
                                    padding)
            if pool_type == "avg":
                if params.get("count_include_pad", True):
                    out = out / float(np.prod(kernel))
                else:
                    ones = jnp.ones_like(data)
                    cnt = lax.reduce_window(ones, 0.0, lax.add, window,
                                            strides, padding)
                    out = out / cnt
        else:
            raise MXNetError("unsupported pool_type " + pool_type)
        return (out.astype(data.dtype),)


@register("_contrib_AdaptiveAvgPooling2D")
def _adaptive_avg_pool(params, data):
    oh, ow = _tup(params.get("output_size", 1), 2, 1)
    n, c, h, w = data.shape
    if h % oh == 0 and w % ow == 0:
        out = data.reshape(n, c, oh, h // oh, ow, w // ow).mean(axis=(3, 5))
    else:
        out = jax.image.resize(data, (n, c, oh, ow), method="linear")
    return (out,)


@register("_contrib_BilinearResize2D")
def _bilinear_resize(params, data):
    n, c, _, _ = data.shape
    h, w = params["height"], params["width"]
    return (jax.image.resize(data, (n, c, h, w), method="linear").astype(data.dtype),)


@register("UpSampling")
def _upsampling(params, *inputs):
    scale = params["scale"]
    sample_type = params.get("sample_type", "nearest")
    data = inputs[0]
    n, c, h, w = data.shape
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
    else:
        out = jax.image.resize(data, (n, c, h * scale, w * scale), method="linear")
    return (out.astype(data.dtype),)


# ---------------------------------------------------------------------------
# Normalisation
# ---------------------------------------------------------------------------
# -- fused-backward BN core (custom VJP) ------------------------------------
# Without this, autodiff saves the f32 activation-sized `diff` intermediate
# of the variance computation as a residual for EVERY BatchNorm: on bf16
# ResNet-50 bs128 that is ~4.8 GB written forward + re-read backward per
# step — the dominant HBM traffic of the whole train step (measured via
# mxnet_tpu.xplane: 'loop fusion' 16.6 ms/step at 959 GB/s before this
# change). The custom VJP keeps only (x, gamma, mean, inv_std) — x is the
# op input (no extra storage), the rest are per-channel — and recomputes
# x_hat inline in one fused backward pass with bf16 I/O and f32 math.

def _bn_stats(axis, eps, data):
    red_axes = tuple(i for i in range(data.ndim) if i != axis)
    bshape = tuple(-1 if i == axis else 1 for i in range(data.ndim))
    if os.environ.get("MXNET_BN_CENTERED_VAR", "0") == "1":
        # two-pass centered variance: immune to E[x^2]-E[x]^2
        # cancellation, but the second pass re-reads the activation.
        # The barrier stops XLA from fusing the two reductions into the
        # PRODUCING convolution — a conv+stats "convolution fusion" runs
        # the MXU at 6-12 TF/s (measured, xplane r50 trace) — so opting
        # into the safe form doesn't also buy that regression back
        sx = lax.optimization_barrier(data)
        mean = jnp.mean(sx, axis=red_axes, dtype=jnp.float32)
        diff = sx.astype(jnp.float32) - mean.reshape(bshape)
        var = jnp.mean(jnp.square(diff), axis=red_axes)
        return mean, var, red_axes, bshape
    # single-pass moments: sum and sum-of-squares fuse into ONE read of
    # the activation (usually straight into the producing convolution's
    # epilogue — measured ~2 ms/step cheaper than two-pass on bf16
    # ResNet-50 bs128). E[x^2]-mean^2 cancellation is bounded by f32
    # accumulation: it loses ~log2(mean^2/var) bits, fine for
    # normalization-scale activations; set MXNET_BN_CENTERED_VAR=1 for
    # the exact two-pass form (pathological large-mean/low-var inputs).
    x32 = data.astype(jnp.float32)
    n = 1.0
    for i in red_axes:
        n *= data.shape[i]
    s = jnp.sum(x32, axis=red_axes)
    ss = jnp.sum(x32 * x32, axis=red_axes)
    mean = s / n
    var = jnp.maximum(ss / n - mean * mean, 0.0)
    return mean, var, red_axes, bshape


def _bn_apply(data, g, beta, mean, var, eps, bshape):
    inv = lax.rsqrt(var + eps)
    scale = g.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - mean * scale
    return data * scale.astype(data.dtype).reshape(bshape) \
        + shift.astype(data.dtype).reshape(bshape)


def _bn_train_core_impl(axis, eps, data, g, beta):
    mean, var, _, bshape = _bn_stats(axis, eps, data)
    out = _bn_apply(data, g, beta, mean, var, eps, bshape)
    return out, mean, var


_bn_train_core = jax.custom_vjp(_bn_train_core_impl, nondiff_argnums=(0, 1))


def _bn_core_fwd(axis, eps, data, g, beta):
    with jax.named_scope("batch_norm"):
        mean, var, _, bshape = _bn_stats(axis, eps, data)
        inv = lax.rsqrt(var + eps)
        out = _bn_apply(data, g, beta, mean, var, eps, bshape)
        return (out, mean, var), (data, g, mean, inv)


def _bn_core_bwd(axis, eps, res, cts):
    with jax.named_scope("batch_norm"):
        data, g, mean, inv = res
        dy = cts[0]  # mean/var outputs are statistics, not differentiated
        # (cuDNN batch-norm backward likewise exposes no stat gradients)
        red_axes = tuple(i for i in range(data.ndim) if i != axis)
        bshape = tuple(-1 if i == axis else 1 for i in range(data.ndim))
        n = 1.0
        for i in red_axes:
            n *= data.shape[i]
        mean_b = mean.reshape(bshape)
        inv_b = inv.reshape(bshape)
        xhat = (data.astype(jnp.float32) - mean_b) * inv_b  # recomputed, fused
        dy32 = dy.astype(jnp.float32)
        # the two sums read dy through a convert of their own: with dx's
        # shared, the TPU compiler emits another program (PERF.md section
        # 6), so merging them is a change to measure, not a tidy-up
        sdy32 = dy.astype(jnp.float32)
        sum_dy = jnp.sum(sdy32, axis=red_axes)
        sum_dy_xhat = jnp.sum(sdy32 * xhat, axis=red_axes)
        coef = (g.astype(jnp.float32) * inv).reshape(bshape)
        dx = coef * (dy32 - sum_dy.reshape(bshape) / n
                     - xhat * (sum_dy_xhat.reshape(bshape) / n))
        return (dx.astype(data.dtype), sum_dy_xhat.astype(g.dtype),
                sum_dy.astype(g.dtype))


_bn_train_core.defvjp(_bn_core_fwd, _bn_core_bwd)


@register("BatchNorm", aliases=("BatchNorm_v1",), need_train_flag=True,
          num_outputs=3, mutate_aux=(3, 4), num_visible_outputs=1)
def _batch_norm(params, data, gamma, beta, moving_mean, moving_var):
    """Reference nn/batch_norm-inl.h. Outputs (out, mean, var); updates the
    moving stats aux inputs in place during training.

    TPU form: statistics accumulate in f32 through the reductions (the cast
    fuses into them — no f32 copy of the activation materializes), and the
    normalization applies as ONE scale/shift multiply-add in the data dtype.
    On bf16 ResNet-50 train this is worth ~20% end-to-end vs normalizing
    through an f32 intermediate (tools/perf/resnet_ablate.py 'bnmixed')."""
    with jax.named_scope("batch_norm"):
        eps = params.get("eps", 1e-3)
        momentum = params.get("momentum", 0.9)
        axis = params.get("axis", 1)
        fix_gamma = params.get("fix_gamma", True)
        use_global = params.get("use_global_stats", False) \
            or not params.get("_is_train", False)
        # bias folded out of the producing conv by the executor's
        # conv-bias->BN elision pass (executor._plan_conv_bias_bn_fold): our
        # input is x where the reference graph normalized x+b. Batch stats:
        # mean(x+b) = mean(x)+b and var is shift-invariant, so normalization is
        # unchanged; only the running-mean bookkeeping needs the +b.
        fold_b = params.get("_fold_bias")
        g = jnp.ones_like(gamma) if fix_gamma else gamma
        axis_n = axis % data.ndim
        bshape = tuple(-1 if i == axis_n else 1 for i in range(data.ndim))
        if use_global:
            mean, var = moving_mean, moving_var
            inv = lax.rsqrt(var.astype(jnp.float32) + eps)
            scale = g.astype(jnp.float32) * inv
            m32 = mean.astype(jnp.float32)
            if fold_b is not None:
                # running stats are in the x+b domain; our input is x
                m32 = m32 - fold_b.astype(jnp.float32)
            shift = beta.astype(jnp.float32) - m32 * scale
            out = data * scale.astype(data.dtype).reshape(bshape) \
                + shift.astype(data.dtype).reshape(bshape)
            return (out, mean.astype(jnp.float32), var.astype(jnp.float32),
                    moving_mean, moving_var)
        # training: fused-backward core (custom VJP, see _bn_train_core above)
        out, mean, var = _bn_train_core(axis_n, float(eps), data, g, beta)
        if fold_b is not None:
            # report/track stats in the x+b domain (running_mean parity with
            # the unfused reference graph); an O(C) add, not an O(NHWC) one
            mean = mean + lax.stop_gradient(fold_b).astype(mean.dtype)
        new_mm = lax.stop_gradient(
            momentum * moving_mean
            + (1 - momentum) * mean.astype(moving_mean.dtype))
        new_mv = lax.stop_gradient(
            momentum * moving_var
            + (1 - momentum) * var.astype(moving_var.dtype))
        # mean/var outputs stay f32 regardless of data dtype (cuDNN BN keeps
        # fp32 stats for fp16 inputs the same way)
        return (out, mean, var, new_mm, new_mv)


@register("LayerNorm", num_outputs=3, num_visible_outputs=1)
def _layer_norm(params, data, gamma, beta):
    """Reference nn/layer_norm.cc; statistics in fp32 for bf16 stability."""
    axis = params.get("axis", -1)
    eps = params.get("eps", 1e-5)
    x32 = data.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axis, keepdims=True)
    var = jnp.var(x32, axis=axis, keepdims=True)
    inv = lax.rsqrt(var + eps)
    out = ((x32 - mean) * inv).astype(data.dtype)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    out = out * gamma.reshape(shape) + beta.reshape(shape)
    return (out, jnp.squeeze(mean, axis), jnp.squeeze(jnp.sqrt(var + eps), axis))


@register("InstanceNorm")
def _instance_norm(params, data, gamma, beta):
    eps = params.get("eps", 1e-3)
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    out = (data - mean) * lax.rsqrt(var + eps)
    return (out * gamma.reshape(shape) + beta.reshape(shape),)


@register("LRN")
def _lrn(params, data):
    """Reference lrn-inl.h: cross-channel local response normalisation."""
    nsize = params["nsize"]
    alpha = params.get("alpha", 1e-4)
    beta = params.get("beta", 0.75)
    knorm = params.get("knorm", 2.0)
    sq = jnp.square(data)
    half = nsize // 2
    window = (1, nsize) + (1,) * (data.ndim - 2)
    strides = (1,) * data.ndim
    padding = ((0, 0), (half, half)) + ((0, 0),) * (data.ndim - 2)
    ssum = lax.reduce_window(sq, 0.0, lax.add, window, strides, padding)
    return (data / jnp.power(knorm + alpha / nsize * ssum, beta),)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------
@register("Activation")
def _activation(params, data):
    act = params["act_type"]
    if act == "relu":
        return (jax.nn.relu(data),)
    if act == "sigmoid":
        return (jax.nn.sigmoid(data),)
    if act == "tanh":
        return (jnp.tanh(data),)
    if act == "softrelu":
        return (jax.nn.softplus(data),)
    if act == "softsign":
        return (jax.nn.soft_sign(data),)
    raise MXNetError("unknown act_type " + act)


@register("LeakyReLU", need_rng=True, need_train_flag=True)
def _leaky_relu(params, data, *gamma):
    act = params.get("act_type", "leaky")
    slope = params.get("slope", 0.25)
    if act == "leaky":
        return (jnp.where(data >= 0, data, slope * data),)
    if act == "elu":
        return (jnp.where(data >= 0, data, slope * jnp.expm1(data)),)
    if act == "selu":
        a, s = 1.6732632423543772, 1.0507009873554805
        return (s * jnp.where(data >= 0, data, a * jnp.expm1(data)),)
    if act == "prelu":
        g = gamma[0].reshape((1, -1) + (1,) * (data.ndim - 2))
        return (jnp.where(data >= 0, data, g * data),)
    if act == "rrelu":
        lo, hi = params.get("lower_bound", 0.125), params.get("upper_bound", 0.334)
        if params.get("_is_train", False):
            key = params["_rng_key"]
            slopes = jax.random.uniform(key, data.shape, data.dtype, lo, hi)
        else:
            slopes = (lo + hi) / 2.0
        return (jnp.where(data >= 0, data, slopes * data),)
    raise MXNetError("unknown act_type " + act)


@register("softmax")
def _softmax(params, data):
    axis = params.get("axis", -1)
    t = params.get("temperature") or 1.0
    return (jax.nn.softmax(data / t, axis=axis),)


@register("log_softmax")
def _log_softmax(params, data):
    axis = params.get("axis", -1)
    t = params.get("temperature") or 1.0
    return (jax.nn.log_softmax(data / t, axis=axis),)


@register("SoftmaxActivation")
def _softmax_activation(params, data):
    mode = params.get("mode", "instance")
    if mode == "channel":
        return (jax.nn.softmax(data, axis=1),)
    return (jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape),)


@register("Dropout", need_rng=True, need_train_flag=True, num_outputs=2,
          num_visible_outputs=1)
def _dropout(params, data):
    """Reference nn/dropout-inl.h; outputs (out, mask)."""
    p = params.get("p", 0.5)
    mode = params.get("mode", "training")
    active = params.get("_is_train", False) or mode == "always"
    if not active or p <= 0:
        return (data, jnp.ones_like(data))
    key = params["_rng_key"]
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, data.shape).astype(data.dtype) / keep
    return (data * mask, mask)


# ---------------------------------------------------------------------------
# Output heads: ops that define their own gradient (loss layers)
# ---------------------------------------------------------------------------
def _attr_num(params, key, default):
    """Attr as float: symbol JSON carries every attr as a string
    (reference dmlc::Parameter parses on the C++ side; this is our parse
    point)."""
    v = params.get(key, default)
    if isinstance(v, bool):
        return float(v)
    try:
        return float(v)
    except (TypeError, ValueError):
        return default


def _attr_bool(params, key, default=False):
    v = params.get(key, default)
    if isinstance(v, str):
        return v.strip().lower() in ("1", "true", "yes")
    return bool(v)


def _normalize_grad(grad, label, params, per_example_dim):
    scale = _attr_num(params, "grad_scale", 1.0)
    norm = params.get("normalization", "null")
    if norm == "batch":
        scale = scale / label.shape[0]
    elif norm == "valid":
        ignore = _attr_num(params, "ignore_label", -1)
        valid = jnp.maximum(jnp.sum(label != ignore), 1).astype(grad.dtype)
        scale = scale / valid
    return grad * scale


def _params_key(params):
    """Hashable, order-independent view of the user attrs (drops internal
    keys and non-static values) for the per-attr-set head cache."""
    return tuple(sorted((k, v) for k, v in params.items()
                        if not k.startswith("_")
                        and isinstance(v, (int, float, bool, str))))


# The head functions close over their (static) attrs instead of taking the
# attr tuple as a traced argument — strings are not JAX types, and every
# attr is a string when the symbol came from JSON. One cached custom_vjp
# per distinct attr set keeps jit caches small.
@_functools.lru_cache(maxsize=None)
def _softmax_output_head(ptuple):
    params = dict(ptuple)

    @jax.custom_vjp
    def _fwd(data, label):
        return jax.nn.softmax(data, axis=-1)

    def _so_fwd(data, label):
        out = jax.nn.softmax(data, axis=-1)
        return out, (out, label)

    def _so_bwd(res, g):
        out, label = res
        return _so_grad(out, label, params)

    _fwd.defvjp(_so_fwd, _so_bwd)
    return _fwd


def _so_grad(out, label, params):
    n_class = out.shape[-1]
    oh = jax.nn.one_hot(label.astype(jnp.int32), n_class, dtype=out.dtype)
    grad = out - oh
    if _attr_bool(params, "use_ignore"):
        ignore = _attr_num(params, "ignore_label", -1)
        mask = (label != ignore).astype(out.dtype)
        grad = grad * mask[..., None]
    grad = _normalize_grad(grad, label, params, None)
    return grad, None


@register("SoftmaxOutput", aliases=("Softmax",))
def _softmax_output(params, data, label):
    """Reference softmax_output-inl.h: forward softmax, backward (p - y)."""
    head = _softmax_output_head(_params_key(params))
    if _attr_bool(params, "multi_output"):
        # data (N, C, d...) label (N, d...): softmax over axis 1
        perm = (0,) + tuple(range(2, data.ndim)) + (1,)
        inv = (0, data.ndim - 1) + tuple(range(1, data.ndim - 1))
        out = head(jnp.transpose(data, perm), label)
        return (jnp.transpose(out, inv),)
    if data.ndim > 2:
        out = head(data.reshape(-1, data.shape[-1]), label.reshape(-1))
        return (out.reshape(data.shape),)
    return (head(data, label),)


def _make_output_head(name, fwd_fn, grad_fn):
    @_functools.lru_cache(maxsize=None)
    def head(ptuple):
        params = dict(ptuple)

        @jax.custom_vjp
        def _f(data, label):
            return fwd_fn(data)

        def _f_fwd(data, label):
            out = fwd_fn(data)
            return out, (out, label)

        def _f_bwd(res, g):
            out, label = res
            grad = grad_fn(out, label, params)
            grad = _normalize_grad(grad, label, params, None)
            return grad, None

        _f.defvjp(_f_fwd, _f_bwd)
        return _f

    @register(name)
    def _op(params, data, label):
        return (head(_params_key(params))(data, label),)
    return _op


_make_output_head("LinearRegressionOutput", lambda x: x,
                  lambda o, l, p: (o - l) / 1.0)
_make_output_head("LogisticRegressionOutput", jax.nn.sigmoid,
                  lambda o, l, p: (o - l))
_make_output_head("MAERegressionOutput", lambda x: x,
                  lambda o, l, p: jnp.sign(o - l))
_make_output_head("SVMOutput", lambda x: x,
                  lambda o, l, p: _svm_grad(o, l, p))


def _svm_grad(out, label, params):
    """Reference svm_output-inl.h: hinge loss gradient with margin,
    regularization_coefficient (the C multiplier) and use_linear
    (L1-SVM: -C*y*1{margin - y*f > 0}; L2-SVM: -2C*y*max(0, margin-y*f))."""
    margin = _attr_num(params, "margin", 1.0)
    coef = _attr_num(params, "regularization_coefficient", 1.0)
    linear = _attr_bool(params, "use_linear", False)
    n_class = out.shape[-1]
    oh = jax.nn.one_hot(label.astype(jnp.int32), n_class, dtype=out.dtype)
    sign = 2 * oh - 1
    viol = jnp.maximum(margin - out * sign, 0.0)
    if linear:
        return -coef * sign * (viol > 0).astype(out.dtype)
    return -2.0 * coef * sign * viol


@register("softmax_cross_entropy")
def _softmax_cross_entropy(params, data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    oh = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1], dtype=data.dtype)
    return (-jnp.sum(oh * logp),)


@register("CTCLoss", aliases=("ctc_loss", "_contrib_CTCLoss", "_contrib_ctc_loss"))
def _ctc_loss(params, data, label, *lens):
    """Reference src/operator/contrib/ctc_loss-inl.h. data (T, B, C),
    label (B, L) padded with 0/-1. Forward-backward in log space via scan."""
    T, B, C = data.shape
    blank_first = params.get("blank_label", "first") == "first"
    blank = 0 if blank_first else C - 1
    lab = label.astype(jnp.int32)
    L = lab.shape[1]
    logp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)
    # extended label seq: blank l1 blank l2 ... blank => length 2L+1
    ext = jnp.full((B, 2 * L + 1), blank, dtype=jnp.int32)
    ext = ext.at[:, 1::2].set(lab)
    pad_val = 0 if blank_first else -1
    lab_valid = (lab != pad_val) if blank_first else (lab >= 0)
    lab_len = jnp.sum(lab_valid.astype(jnp.int32), axis=1)
    ext_len = 2 * lab_len + 1
    # optional length inputs, in reference order: data_lengths, label_lengths
    lens = list(lens)
    data_len = jnp.full((B,), T, jnp.int32)
    if params.get("use_data_lengths") and lens:
        data_len = lens.pop(0).astype(jnp.int32)
    if params.get("use_label_lengths") and lens:
        lab_len = lens.pop(0).astype(jnp.int32)
        ext_len = 2 * lab_len + 1
    NEG = -1e10
    S = 2 * L + 1
    # before frame 0 only the path start (position 0, shifted into 0/1 by
    # the first recurrence step) carries mass; the first scan iteration then
    # yields alpha_0 = emission at positions 0 and 1 only
    alpha0 = jnp.full((B, S), NEG, jnp.float32).at[:, 0].set(0.0)
    gather = jax.vmap(lambda lp, e: lp[e])  # (B,C),(B,S)->(B,S)

    def step(alpha, lp_t):
        em = gather(lp_t, ext)
        a0 = alpha
        a1 = jnp.concatenate([jnp.full((B, 1), NEG), alpha[:, :-1]], axis=1)
        a2 = jnp.concatenate([jnp.full((B, 2), NEG), alpha[:, :-2]], axis=1)
        ext_m2 = jnp.concatenate([jnp.full((B, 2), -1, jnp.int32), ext[:, :-2]], axis=1)
        allow_skip = (ext != blank) & (ext != ext_m2)
        a2 = jnp.where(allow_skip, a2, NEG)
        new = jnp.logaddexp(jnp.logaddexp(a0, a1), a2) + em
        return new, new

    _, alphas = lax.scan(step, alpha0, logp)
    # pick alpha at t = data_len-1, positions ext_len-1 and ext_len-2
    t_idx = jnp.clip(data_len - 1, 0, T - 1)
    final = jnp.take_along_axis(alphas, t_idx[None, :, None], axis=0)[0]  # (B, S)
    a_end = jnp.take_along_axis(final, (ext_len - 1)[:, None], axis=1)[:, 0]
    a_end2 = jnp.take_along_axis(final, jnp.maximum(ext_len - 2, 0)[:, None], axis=1)[:, 0]
    # an empty label (ext_len == 1) has only the all-blank path; don't
    # double-count the single end position
    a_end2 = jnp.where(ext_len >= 2, a_end2, NEG)
    loss = -jnp.logaddexp(a_end, a_end2)
    return (loss.astype(data.dtype),)


# ---------------------------------------------------------------------------
# Fused RNN (reference rnn-inl.h modes rnn_relu/rnn_tanh/lstm/gru)
# ---------------------------------------------------------------------------
def _rnn_nout(params):
    if not params.get("state_outputs", False):
        return 1
    return 3 if params["mode"] == "lstm" else 2


def _gates(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


def rnn_param_size(num_layers, input_size, state_size, bidirectional, mode):
    """Total flat parameter count, cuDNN layout (reference rnn-inl.h:106)."""
    g = _gates(mode)
    d = 2 if bidirectional else 1
    size = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * d
        for _ in range(d):
            size += g * state_size * (in_sz + state_size)  # i2h + h2h weights
            size += 2 * g * state_size                      # i2h + h2h biases
    return size


def _unpack_rnn_params(flat, num_layers, input_size, state_size, bidir, mode):
    g = _gates(mode)
    d = 2 if bidir else 1
    offset = 0
    weights = []
    # cuDNN layout: all weights (layer-major, dir-minor), then all biases
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * d
        for dr in range(d):
            w_i2h = lax.dynamic_slice(flat, (offset,), (g * state_size * in_sz,)).reshape(g * state_size, in_sz)
            offset += g * state_size * in_sz
            w_h2h = lax.dynamic_slice(flat, (offset,), (g * state_size * state_size,)).reshape(g * state_size, state_size)
            offset += g * state_size * state_size
            weights.append((w_i2h, w_h2h))
    biases = []
    for layer in range(num_layers):
        for dr in range(d):
            b_i2h = lax.dynamic_slice(flat, (offset,), (g * state_size,))
            offset += g * state_size
            b_h2h = lax.dynamic_slice(flat, (offset,), (g * state_size,))
            offset += g * state_size
            biases.append((b_i2h, b_h2h))
    return weights, biases


def _fused_lstm_ok(h0, ctx=None):
    """Use the Pallas fused-LSTM kernel (the cuDNN-RNN analog) when the
    computation actually lowers on a TPU and the per-step working set fits
    comfortably in VMEM; otherwise lax.scan.

    The platform check alone is not enough: on a TPU-attached host a
    cpu-context model still lowers for the CPU backend, where a
    non-interpret pallas_call fails to compile — so the op's context (the
    device its NDArrays are committed to, plumbed via params['_ctx'])
    must be an accelerator too."""
    from .pallas_kernels import is_tpu
    if not is_tpu():
        return False
    if ctx is not None and getattr(ctx, "device_type", None) \
            in ("cpu", "cpu_pinned", "cpu_shared"):
        return False
    B, H = h0.shape
    # gates block (B x 4H) + h/c scratch + recurrent weights, f32
    vmem = (B * 4 * H + 2 * B * H + H * 4 * H) * 4
    return vmem <= 8 * 1024 * 1024


def _rnn_cell_scan(mode, x_seq, h0, c0, w_i2h, w_h2h, b_i2h, b_h2h,
                   reverse=False, ctx=None):
    """One direction of one layer. x_seq (T,B,I) -> (T,B,H)."""
    H = h0.shape[-1]

    if mode == "lstm" and _fused_lstm_ok(h0, ctx):
        from .pallas_kernels import fused_lstm
        xs = jnp.flip(x_seq, 0) if reverse else x_seq
        # fused_lstm casts to its f32 working precision internally and
        # returns x's dtype
        ys, h_f, c_f = fused_lstm(xs, h0, c0, w_i2h.T, w_h2h.T,
                                  b_i2h + b_h2h)
        if reverse:
            ys = jnp.flip(ys, 0)
        return ys, h_f, c_f

    def cell(carry, x_t):
        h, c = carry
        gates = jnp.dot(x_t, w_i2h.T) + b_i2h + jnp.dot(h, w_h2h.T) + b_h2h
        if mode == "lstm":
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
            g = jnp.tanh(g)
            c_new = f * c + i * g
            h_new = o * jnp.tanh(c_new)
            return (h_new, c_new), h_new
        if mode == "gru":
            # cuDNN gru: r, z, n with separate h2h for n
            xr, xz, xn = jnp.split(jnp.dot(x_t, w_i2h.T) + b_i2h, 3, axis=-1)
            hr, hz, hn = jnp.split(jnp.dot(h, w_h2h.T) + b_h2h, 3, axis=-1)
            r = jax.nn.sigmoid(xr + hr)
            z = jax.nn.sigmoid(xz + hz)
            n = jnp.tanh(xn + r * hn)
            h_new = (1 - z) * n + z * h
            return (h_new, c), h_new
        act = jax.nn.relu if mode == "rnn_relu" else jnp.tanh
        h_new = act(gates)
        return (h_new, c), h_new

    (h_f, c_f), ys = lax.scan(cell, (h0, c0), x_seq, reverse=reverse)
    return ys, h_f, c_f


@register("RNN", num_outputs=_rnn_nout, need_train_flag=True, need_rng=True)
def _rnn(params, data, parameters, state, *state_cell):
    """Fused multi-layer (bi)RNN via lax.scan (replaces cudnn_rnn-inl.h)."""
    mode = params["mode"]
    H = params["state_size"]
    num_layers = params.get("num_layers", 1)
    bidir = params.get("bidirectional", False)
    p_drop = params.get("p", 0.0)
    d = 2 if bidir else 1
    T, B, I = data.shape
    if state.shape[1] == 1 and B != 1:
        # begin_state zeros are created batch-1 (symbolic shape inference
        # has no unknown-batch placeholder); broadcast to the data batch
        state = jnp.broadcast_to(state, (state.shape[0], B, state.shape[2]))
    c_in = state_cell[0] if (mode == "lstm" and state_cell) else jnp.zeros_like(state)
    if c_in.shape[1] == 1 and B != 1:
        c_in = jnp.broadcast_to(c_in, (c_in.shape[0], B, c_in.shape[2]))
    weights, biases = _unpack_rnn_params(parameters, num_layers, I, H, bidir, mode)
    x = data
    h_finals, c_finals = [], []
    for layer in range(num_layers):
        outs = []
        for dr in range(d):
            li = layer * d + dr
            h0 = state[li]
            c0 = c_in[li]
            w_i2h, w_h2h = weights[li]
            b_i2h, b_h2h = biases[li]
            ys, h_f, c_f = _rnn_cell_scan(mode, x, h0, c0, w_i2h, w_h2h,
                                          b_i2h, b_h2h, reverse=(dr == 1),
                                          ctx=params.get("_ctx"))
            outs.append(ys)
            h_finals.append(h_f)
            c_finals.append(c_f)
        x = jnp.concatenate(outs, axis=-1) if d == 2 else outs[0]
        if p_drop > 0 and params.get("_is_train", False) and layer < num_layers - 1:
            key = jax.random.fold_in(params["_rng_key"], layer)
            mask = jax.random.bernoulli(key, 1 - p_drop, x.shape).astype(x.dtype)
            x = x * mask / (1 - p_drop)
    h_out = jnp.stack(h_finals, axis=0)
    outs = (x,)
    if params.get("state_outputs", False):
        outs = outs + (h_out,)
        if mode == "lstm":
            outs = outs + (jnp.stack(c_finals, axis=0),)
    return outs
