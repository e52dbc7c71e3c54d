#!/usr/bin/env python
"""Probe: does NHWC beat NCHW for a ResNet-style conv stack on this chip?

Runs a reduced-depth bottleneck ResNet (stem + one bottleneck block per
stage, same shapes as ResNet-50's stages) fwd+bwd+SGD in bf16 at batch 128
under both layouts, plus a bf16 matmul peak-FLOPs sanity line. Reduced depth
keeps compile time short while preserving the layout question.

Timing: chain K steps in a fori_loop, chain calls through the params
carry, one wait at the end.
"""
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax


def matmul_peak():
    n = 8192
    a = jnp.zeros((n, n), jnp.bfloat16)
    b = jnp.zeros((n, n), jnp.bfloat16)

    @jax.jit
    def loop(a, b):
        def body(i, acc):
            return jnp.dot(acc, b, preferred_element_type=jnp.bfloat16)
        return lax.fori_loop(0, 20, body, a)

    r = loop(a, b)
    float(r[0, 0].astype(jnp.float32))
    t0 = time.time()
    r = loop(a, b)
    float(r[0, 0].astype(jnp.float32))
    dt = time.time() - t0
    tflops = 20 * 2 * n**3 / dt / 1e12
    print("matmul bf16 %dx%d: %.1f TFLOP/s" % (n, n, tflops), flush=True)


def make_stack(layout):
    """Reduced ResNet-50: stem + 1 bottleneck per stage (4 stages)."""
    nhwc = layout == "NHWC"
    dn_l = ("NHWC", "HWIO", "NHWC") if nhwc else ("NCHW", "OIHW", "NCHW")
    caxis = 3 if nhwc else 1

    def conv(x, w, stride=1):
        dn = lax.conv_dimension_numbers(x.shape, w.shape, dn_l)
        k = w.shape[0] if nhwc else w.shape[2]
        p = (k - 1) // 2
        return lax.conv_general_dilated(
            x, w, (stride, stride), [(p, p), (p, p)], dimension_numbers=dn)

    def bn_relu(x, g, b):
        red = tuple(i for i in range(4) if i != caxis)
        sh = tuple(-1 if i == caxis else 1 for i in range(4))
        x32 = x.astype(jnp.float32)
        m = jnp.mean(x32, red)
        v = jnp.var(x32, red)
        y = (x32 - m.reshape(sh)) * lax.rsqrt(v.reshape(sh) + 1e-5)
        return jax.nn.relu(y.astype(x.dtype) * g.reshape(sh) + b.reshape(sh))

    def wshape(k, cin, cout):
        return (k, k, cin, cout) if nhwc else (cout, cin, k, k)

    rng = np.random.RandomState(0)

    def mk(shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.05,
                           jnp.bfloat16)

    params = []

    def add_conv(k, cin, cout):
        params.append(mk(wshape(k, cin, cout)))
        params.append(jnp.ones((cout,), jnp.bfloat16))
        params.append(jnp.zeros((cout,), jnp.bfloat16))
        return len(params) - 3

    stem = add_conv(7, 3, 64)
    blocks = []
    cin = 64
    for stage, (cmid, cout, stride) in enumerate(
            [(64, 256, 1), (128, 512, 2), (256, 1024, 2), (512, 2048, 2)]):
        b = dict(c1=add_conv(1, cin, cmid), c2=add_conv(3, cmid, cmid),
                 c3=add_conv(1, cmid, cout), proj=add_conv(1, cin, cout),
                 stride=stride)
        blocks.append(b)
        cin = cout
    fc = mk((2048, 1000))
    params.append(fc)

    def apply_conv(x, pv, idx, stride=1, relu=True):
        y = conv(x, pv[idx], stride)
        g, b = pv[idx + 1], pv[idx + 2]
        if relu:
            return bn_relu(y, g, b)
        red = tuple(i for i in range(4) if i != caxis)
        sh = tuple(-1 if i == caxis else 1 for i in range(4))
        x32 = y.astype(jnp.float32)
        m = jnp.mean(x32, red)
        v = jnp.var(x32, red)
        out = (x32 - m.reshape(sh)) * lax.rsqrt(v.reshape(sh) + 1e-5)
        return out.astype(y.dtype) * g.reshape(sh) + b.reshape(sh)

    def forward(pv, x):
        y = apply_conv(x, pv, stem, stride=2)
        window = (1, 3, 3, 1) if nhwc else (1, 1, 3, 3)
        strides = (1, 2, 2, 1) if nhwc else (1, 1, 2, 2)
        pad = ((0, 0), (1, 1), (1, 1), (0, 0)) if nhwc else \
            ((0, 0), (0, 0), (1, 1), (1, 1))
        y = lax.reduce_window(y, -jnp.inf, lax.max, window, strides, pad)
        for b in blocks:
            sc = apply_conv(y, pv, b["proj"], stride=b["stride"], relu=False)
            y = apply_conv(y, pv, b["c1"])
            y = apply_conv(y, pv, b["c2"], stride=b["stride"])
            y = apply_conv(y, pv, b["c3"], relu=False)
            y = jax.nn.relu(y + sc)
        red = (1, 2) if nhwc else (2, 3)
        y = jnp.mean(y.astype(jnp.float32), red).astype(y.dtype)
        return jnp.dot(y, pv[-1])

    return params, forward


def bench_layout(layout, batch=128, k=10, calls=3):
    params, forward = make_stack(layout)
    shape = (batch, 224, 224, 3) if layout == "NHWC" else (batch, 3, 224, 224)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.rand(*shape).astype(np.float32), jnp.bfloat16)
    y = jnp.asarray(rng.randint(0, 1000, batch).astype(np.int32))

    def loss_fn(pv, xv, yv):
        logits = forward(pv, xv).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(logp, yv[:, None], 1))

    @jax.jit
    def k_steps(pv, xv, yv):
        def body(i, carry):
            pv, _ = carry
            xi = jnp.roll(xv, i, axis=0)
            loss, g = jax.value_and_grad(loss_fn)(pv, xi, yv)
            pv = [p - 0.01 * gg.astype(p.dtype) for p, gg in zip(pv, g)]
            return pv, loss
        return lax.fori_loop(0, k, body, (pv, jnp.float32(0)))

    t0 = time.time()
    params, loss = k_steps(params, x, y)
    float(loss)
    print("%s: compiled in %.1fs" % (layout, time.time() - t0), flush=True)
    t0 = time.time()
    for _ in range(calls):
        params, loss = k_steps(params, x, y)
    float(loss)
    dt = time.time() - t0
    rate = calls * k * batch / dt
    print("%s: %.1f img/s (reduced-depth resnet bf16 bs%d)"
          % (layout, rate, batch), flush=True)
    return rate


if __name__ == "__main__":
    print(jax.devices(), flush=True)
    matmul_peak()
    r_nchw = bench_layout("NCHW")
    r_nhwc = bench_layout("NHWC")
    print("NHWC/NCHW speedup: %.3f" % (r_nhwc / r_nchw), flush=True)
