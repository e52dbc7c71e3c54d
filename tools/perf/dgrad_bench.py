#!/usr/bin/env python
"""Microbenchmark: 1x1-conv input-gradient formulations at ResNet-50
bs128 NHWC shapes (the round-4 attribution table's weak spot — stage-entry
stride-2 dgrads at 6-12 TF/s, 56x56-stage dgrads at 10-23 TF/s).

Per shape, times three formulations of the SAME contraction:
  xla     — jax.vjp through lax.conv_general_dilated (the default path:
            XLA's lhs-dilated conv-transpose emitter)
  pad_dot — interior-pad(dy @ W^T) (round-4's rejected matmul form:
            extra materialized intermediate)
  pallas  — ops.conv_kernels.conv1x1_s2_dgrad (compact matmul + fused
            interleaved store; stride-2 shapes only)
  dot     — dy @ W^T reshaped (stride-1 shapes only)

Measurement: K iterations chained inside ONE jitted lax.scan — the weight
is scaled by a carried scalar that depends on the previous output, so
iterations serialize and CSE can't collapse them; the host's dispatch
cost is paid once per timed call, not per iteration.  Reports the
fastest of R timed calls.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

# (name, Ho/Wo, K=Cout, C=Cin, stride) — bs128 NHWC ResNet-50 dgrad shapes
SHAPES = [
    ("c3_entry_1x1s2", 28, 128, 256, 2),
    ("c3_down_1x1s2", 28, 512, 256, 2),
    ("c4_entry_1x1s2", 14, 256, 512, 2),
    ("c4_down_1x1s2", 14, 1024, 512, 2),
    ("c5_entry_1x1s2", 7, 512, 1024, 2),
    ("c5_down_1x1s2", 7, 2048, 1024, 2),
    ("c2_conv1_1x1s1", 56, 64, 256, 1),
    ("c2_conv3_1x1s1", 56, 256, 64, 1),
    ("c3_conv3_1x1s1", 28, 512, 128, 1),
]


def make_fns(Ho, K, C, stride, dtype):
    """name -> fn(dy, w2) computing dx for this shape."""
    H = stride * Ho
    N = 128

    def conv_fwd(x, w2):
        w4 = w2.reshape(K, 1, 1, C)
        dn = lax.conv_dimension_numbers((N, H, H, C), w4.shape,
                                        ("NHWC", "OHWI", "NHWC"))
        return lax.conv_general_dilated(
            x, w4, window_strides=(stride, stride),
            padding=[(0, 0), (0, 0)], dimension_numbers=dn)

    def xla(dy, w2):
        x = jnp.zeros((N, H, H, C), dtype)
        _, vjp = jax.vjp(lambda d: conv_fwd(d, w2), x)
        return vjp(dy)[0]

    def pad_dot(dy, w2):
        dz = lax.dot_general(dy, w2, (((3,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32).astype(dtype)
        if stride == 1:
            return dz
        pads = [(0, 0, 0),
                (0, H - (2 * (Ho - 1) + 1), 1),
                (0, H - (2 * (Ho - 1) + 1), 1),
                (0, 0, 0)]
        return lax.pad(dz, jnp.zeros((), dtype), pads)

    fns = {"xla": xla, "pad_dot": pad_dot}
    if stride == 2:
        from mxnet_tpu.ops.conv_kernels import conv1x1_s2_dgrad
        fns["pallas"] = lambda dy, w2: conv1x1_s2_dgrad(dy, w2, H, H)
    else:
        fns["dot"] = pad_dot
        del fns["pad_dot"]
    return fns


def time_fn(fn, dy, w2, iters, rounds, calls=6):
    """Per-op seconds: `calls` chained scan dispatches of `iters`
    iterations each, one wait at the end."""
    @jax.jit
    def run(c, dy, w2):
        # dy/w2 as ARGUMENTS: closing over them bakes multi-MB constants
        # into the MLIR payload (25 MB for the c3 shapes)
        def body(c, _):
            dx = fn(dy, (w2 * c).astype(w2.dtype))
            # the carry must consume ALL of dx: a single-element read
            # lets XLA slice straight through the conv/dot (slice-of-conv
            # -> tiny conv) and the "measurement" times dead code.  The
            # full-array sum costs one extra dx read — identical across
            # variants of the same shape.
            return 1.0 + jnp.sum(dx.astype(jnp.float32)) * 1e-30, ()
        return lax.scan(body, c, None, length=iters)[0]

    float(run(jnp.float32(1.0), dy, w2))  # compile + warm
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        c = jnp.float32(1.0)
        for _ in range(calls):
            c = run(c, dy, w2)
        c.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / (iters * calls)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--iters", type=int, default=100)
    p.add_argument("--rounds", type=int, default=2)
    p.add_argument("--calls", type=int, default=6)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--only", default=None, help="substring filter on shape")
    p.add_argument("--variants", default=None,
                   help="comma list: xla,pad_dot,pallas,dot")
    args = p.parse_args()

    dtype = jnp.dtype(args.dtype)
    rng = np.random.RandomState(0)
    N = 128
    for name, Ho, K, C, stride in SHAPES:
        if args.only and args.only not in name:
            continue
        dy = jnp.asarray(rng.randn(N, Ho, Ho, K), dtype)
        w2 = jnp.asarray(rng.randn(K, C), dtype)
        gflop = 2.0 * N * Ho * Ho * K * C / 1e9
        for vname, fn in make_fns(Ho, K, C, stride, dtype).items():
            if args.variants and vname not in args.variants.split(","):
                continue
            try:
                sec = time_fn(fn, dy, w2, args.iters, args.rounds,
                              args.calls)
            except Exception as e:
                print(json.dumps({"shape": name, "variant": vname,
                                  "error": str(e)[:2000]}), flush=True)
                continue
            print(json.dumps({
                "shape": name, "variant": vname,
                "us": round(sec * 1e6, 1),
                "tf_s": round(gflop / sec / 1e3, 1)}), flush=True)


if __name__ == "__main__":
    main()
