#!/usr/bin/env python
"""Per-op performance harness (reference `tests/cpp/operator/coreop_perf.cc`
+ `python/mxnet/test_utils.py:1133 check_speed`): sweeps the hot operator
families at benchmark shapes and prints a per-op microsecond table, plus
one JSON line per op for regression diffing.

Run on the chip (plain `python tools/perf/op_bench.py`) for real numbers,
or `--preset tiny` on CPU for a smoke sweep. Measurement discipline: each
op compiles once (warmup), then N timed iterations end with ONE fence
(`test_utils.check_speed` semantics).

Each iteration is one dispatch, so the host's per-dispatch cost floors
the small ops: read the table RELATIVELY (the cheapest op's time is the
dispatch floor).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def sweep(preset):
    """(name, symbol-factory, shape-kwargs) per hot op family."""
    import mxnet_tpu as mx
    sym = mx.sym
    t = preset == "tiny"
    B = 4 if t else 32
    C = 8 if t else 64
    HW = 16 if t else 56
    H = 64 if t else 1024
    T = 8 if t else 128
    V = 100 if t else 10000

    d = sym.Variable("data")
    cases = [
        ("Convolution3x3", sym.Convolution(
            d, kernel=(3, 3), num_filter=C, pad=(1, 1), name="conv"),
            {"data": (B, C, HW, HW)}),
        ("Convolution1x1", sym.Convolution(
            d, kernel=(1, 1), num_filter=C, name="conv1"),
            {"data": (B, C, HW, HW)}),
        ("FullyConnected", sym.FullyConnected(d, num_hidden=H, name="fc"),
            {"data": (B, H)}),
        ("BatchNorm", sym.BatchNorm(d, fix_gamma=False, name="bn"),
            {"data": (B, C, HW, HW)}),
        ("Pooling_max", sym.Pooling(d, kernel=(2, 2), stride=(2, 2),
                                    pool_type="max"),
            {"data": (B, C, HW, HW)}),
        ("Activation_relu", sym.Activation(d, act_type="relu"),
            {"data": (B, C, HW, HW)}),
        ("SoftmaxOutput", sym.SoftmaxOutput(d, name="softmax"),
            {"data": (B, V)}),
        ("elemwise_add", d + d * 2.0, {"data": (B, C, HW, HW)}),
        ("sum_reduce", sym.sum(d, axis=(1, 2, 3)), {"data": (B, C, HW, HW)}),
        ("dot", sym.dot(d, sym.Variable("rhs")),
            {"data": (H, H), "rhs": (H, H)}),
        ("Embedding", sym.Embedding(d, sym.Variable("weight"),
                                    input_dim=V, output_dim=C),
            {"data": (B, T), "weight": (V, C)}),
        ("LayerNorm", sym.LayerNorm(d, sym.Variable("gamma"),
                                    sym.Variable("beta")),
            {"data": (B, T, H), "gamma": (H,), "beta": (H,)}),
        ("Dropout", sym.Dropout(d, p=0.5), {"data": (B, T, H)}),
        ("transpose", sym.transpose(d, axes=(0, 2, 1)),
            {"data": (B, T, H)}),
    ]
    return cases


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--preset", choices=["tiny", "bench"], default="bench")
    p.add_argument("-N", type=int, default=20, help="timed iters per op")
    p.add_argument("--typ", choices=["whole", "forward"], default="whole")
    p.add_argument("--json-out", type=str, default=None)
    args = p.parse_args()

    import mxnet_tpu as mx
    from mxnet_tpu.test_utils import check_speed

    ctx = mx.tpu() if mx.context.num_tpus() else mx.cpu()
    rows = []
    hdr = "%-20s %-28s %12s" % ("Op", "Shapes", "us/iter")
    print(hdr)
    print("-" * len(hdr))
    for name, sym, shapes in sweep(args.preset):
        try:
            sec = check_speed(sym, ctx=ctx, N=args.N, typ=args.typ, **shapes)
        except Exception as e:  # keep sweeping; report the failure
            print("%-20s %-28s %12s (%s)" % (name, shapes, "FAIL", e))
            rows.append({"op": name, "error": str(e)})
            continue
        us = sec * 1e6
        print("%-20s %-28s %12.1f"
              % (name, ",".join(str(s) for s in shapes.values()), us))
        rows.append({"op": name, "us_per_iter": round(us, 2),
                     "typ": args.typ, "shapes": {k: list(v)
                                                 for k, v in shapes.items()}})
    for r in rows:
        print(json.dumps({"metric": "op_us", **r}))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
