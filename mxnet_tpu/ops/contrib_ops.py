"""Contrib / detection operators (first tranche).

Parity targets: reference `src/operator/contrib/` (bounding-box ops,
MultiBox SSD suite, ROIPooling, FFT, count_sketch, quadratic) and the
fork-specific detection ops. Expanded over rounds; see ops/detection.py for
the SSD/RCNN suite.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .registry import register


@register("_contrib_quadratic", aliases=("quadratic",))
def _quadratic(params, x):
    a, b, c = params.get("a", 0.0), params.get("b", 0.0), params.get("c", 0.0)
    return (a * x * x + b * x + c,)


@register("_contrib_fft", aliases=("fft",))
def _fft(params, x):
    out = jnp.fft.fft(x.astype(jnp.complex64), axis=-1)
    return (jnp.stack([out.real, out.imag], axis=-1).reshape(
        x.shape[:-1] + (2 * x.shape[-1],)).astype(jnp.float32),)


@register("_contrib_ifft", aliases=("ifft",))
def _ifft(params, x):
    n = x.shape[-1] // 2
    comp = x.reshape(x.shape[:-1] + (n, 2))
    out = jnp.fft.ifft(comp[..., 0] + 1j * comp[..., 1], axis=-1)
    return ((out.real * n).astype(jnp.float32),)


@register("_contrib_count_sketch", aliases=("count_sketch",))
def _count_sketch(params, data, h, s):
    out_dim = params["out_dim"]
    idx = h.astype(jnp.int32).reshape(-1)
    sign = s.reshape(-1)
    contrib = data * sign[None, :]
    out = jnp.zeros((data.shape[0], out_dim), data.dtype)
    return (out.at[:, idx].add(contrib),)


def box_iou_xyxy(a, b):
    """IoU of two corner-format box sets: a (..., N, 4), b (..., M, 4)."""
    tl = jnp.maximum(a[..., :, None, :2], b[..., None, :, :2])
    br = jnp.minimum(a[..., :, None, 2:4], b[..., None, :, 2:4])
    wh = jnp.maximum(br - tl, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    area_a = jnp.maximum(a[..., 2] - a[..., 0], 0) * jnp.maximum(a[..., 3] - a[..., 1], 0)
    area_b = jnp.maximum(b[..., 2] - b[..., 0], 0) * jnp.maximum(b[..., 3] - b[..., 1], 0)
    union = area_a[..., :, None] + area_b[..., None, :] - inter
    return inter / jnp.maximum(union, 1e-12)


@register("_contrib_box_iou", aliases=("box_iou",))
def _box_iou(params, lhs, rhs):
    fmt = params.get("format", "corner")
    a, b = lhs, rhs
    if fmt == "center":
        a = jnp.concatenate([a[..., :2] - a[..., 2:4] / 2,
                             a[..., :2] + a[..., 2:4] / 2], axis=-1)
        b = jnp.concatenate([b[..., :2] - b[..., 2:4] / 2,
                             b[..., :2] + b[..., 2:4] / 2], axis=-1)
    return (box_iou_xyxy(a, b),)


def greedy_nms_keep(boxes, scores, valid, class_id, thresh, topk, force):
    """Greedy NMS keep-mask (original input order) over (N,4) boxes.

    Score-sorted fori_loop suppression with a full IoU matrix — static
    shapes, TPU-friendly (reference contrib/bounding_box-inl.h). Shared by
    box_nms, MultiBoxDetection, and Proposal. `topk > 0` keeps only the
    topk highest-scoring candidates. Suppression is restricted to matching
    `class_id` unless `force`.
    """
    N = boxes.shape[0]
    order = jnp.argsort(-jnp.where(valid, scores, -jnp.inf))
    b = boxes[order]
    ious = box_iou_xyxy(b, b)
    if not force and class_id is not None:
        cid = class_id[order]
        ious = jnp.where(cid[:, None] == cid[None, :], ious, 0.0)
    keep0 = valid[order]
    if topk > 0:
        keep0 = keep0 & (jnp.arange(N) < topk)

    def body(i, keep):
        sup = (ious[i] > thresh) & (jnp.arange(N) > i) & keep[i]
        return keep & ~sup

    keep_sorted = lax.fori_loop(0, N, body, keep0)
    return jnp.zeros((N,), bool).at[order].set(keep_sorted)


@register("_contrib_box_nms", aliases=("box_nms",))
def _box_nms(params, data):
    """Greedy NMS over (B, N, K>=6) [id, score, x1,y1,x2,y2,...] boxes;
    output rows sorted by descending score, suppressed rows -1
    (reference contrib/bounding_box-inl.h)."""
    thresh = params.get("overlap_thresh", 0.5)
    vthresh = params.get("valid_thresh", 0.0)
    topk = params.get("topk", -1)
    coord = params.get("coord_start", 2)
    score_i = params.get("score_index", 1)
    id_i = params.get("id_index", -1)
    force = params.get("force_suppress", False)
    x = data
    squeeze = False
    if x.ndim == 2:
        x = x[None]
        squeeze = True

    def one(xb):
        scores = xb[:, score_i]
        cid = xb[:, id_i] if (not force and id_i >= 0) else None
        keep = greedy_nms_keep(xb[:, coord:coord + 4], scores,
                               scores > vthresh, cid, thresh, topk, force)
        order = jnp.argsort(-scores)
        return jnp.where(keep[order][:, None], xb[order], -1.0)

    out = jax.vmap(one)(x)
    if squeeze:
        out = out[0]
    return (out,)


@register("ROIPooling")
def _roi_pooling(params, data, rois):
    """Reference src/operator/roi_pooling.cc. data (B,C,H,W),
    rois (R,5) [batch_idx, x1, y1, x2, y2] in image coords."""
    ph, pw = params["pooled_size"]
    spatial_scale = params.get("spatial_scale", 1.0)
    B, C, H, W = data.shape

    def pool_one(roi):
        bi = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1] * spatial_scale).astype(jnp.int32)
        y1 = jnp.round(roi[2] * spatial_scale).astype(jnp.int32)
        x2 = jnp.round(roi[3] * spatial_scale).astype(jnp.int32)
        y2 = jnp.round(roi[4] * spatial_scale).astype(jnp.int32)
        rh = jnp.maximum(y2 - y1 + 1, 1)
        rw = jnp.maximum(x2 - x1 + 1, 1)
        img = data[bi]  # (C,H,W)
        ys = jnp.arange(H)
        xs_ = jnp.arange(W)

        def cell(iy, ix):
            hstart = y1 + (iy * rh) // ph
            hend = y1 + ((iy + 1) * rh + ph - 1) // ph
            wstart = x1 + (ix * rw) // pw
            wend = x1 + ((ix + 1) * rw + pw - 1) // pw
            mask = ((ys[:, None] >= hstart) & (ys[:, None] < hend) &
                    (xs_[None, :] >= wstart) & (xs_[None, :] < wend) &
                    (ys[:, None] >= 0) & (ys[:, None] < H) &
                    (xs_[None, :] >= 0) & (xs_[None, :] < W))
            masked = jnp.where(mask[None], img, -jnp.inf)
            m = jnp.max(masked, axis=(1, 2))
            return jnp.where(jnp.isfinite(m), m, 0.0)

        iy = jnp.arange(ph)
        ix = jnp.arange(pw)
        grid = jax.vmap(lambda y: jax.vmap(lambda x_: cell(y, x_))(ix))(iy)
        return jnp.transpose(grid, (2, 0, 1))  # (C,ph,pw)

    out = jax.vmap(pool_one)(rois)
    return (out.astype(data.dtype),)


def rope(x, theta):
    """Rotary position term on [B, T, H, D], rotate-half over all of D:
    position t turns the pair (x_i, x_(i + D/2)) by the angle
    ``t * theta^(-2i/D)``. Positions count from 0 at a row's start; the
    angles and the turn are float32, the result takes x's type."""
    t, d = x.shape[1], x.shape[-1]
    inv = jnp.asarray(theta ** (-np.arange(0, d, 2, dtype=np.float64) / d),
                      jnp.float32)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.tile(jnp.cos(angle), (1, 2))[None, :, None]
    sin = jnp.tile(jnp.sin(angle), (1, 2))[None, :, None]
    x32 = x.astype(jnp.float32)
    half = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]], axis=-1)
    return (x32 * cos + half * sin).astype(x.dtype)


@register("rope")
def _rope(params, x):
    """See `rope`; attr theta (10000)."""
    from .nn import _attr_num
    with jax.named_scope("rope"):
        return (rope(x, _attr_num(params, "theta", 10000.0)),)


@register("_contrib_flash_attention", aliases=("flash_attention",))
def _flash_attention_op(params, q, k, v):
    """Fused multi-head attention (Pallas flash kernel on TPU, interpreter
    elsewhere). Inputs [B, T, H, D], k and v with H or a divisor of it
    (grouped-query), v with a head width DV of its own (the output is
    [B, T, H, DV]); new capability — the reference has no
    attention op (its sequence stack is cudnn_rnn, SURVEY §2.4). Attrs:
    causal (bool), scale (float, default 1/sqrt(D)), block_q/block_k
    (the forward kernel's tile sizes), window (int, with causal: query i
    sees keys i - window < j <= i; none by default)."""
    from . import pallas_kernels
    from .nn import _attr_bool, _attr_num
    causal = _attr_bool(params, "causal")
    scale = params.get("scale")
    scale = None if scale in (None, "None") else float(scale)
    window = params.get("window")
    window = None if window in (None, "None") else int(window)
    block_q = int(_attr_num(params, "block_q", 512))
    block_k = int(_attr_num(params, "block_k", 512))
    with jax.named_scope("flash_attention"):
        return (pallas_kernels.flash_attention(
            q, k, v, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, window=window),)
