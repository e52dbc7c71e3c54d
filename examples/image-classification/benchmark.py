#!/usr/bin/env python
"""Training-throughput benchmark THROUGH the framework's own train path
(reference example/image-classification/benchmark.py: trains model-zoo nets
on synthetic data and reports img/s; the reference's published train numbers
are BASELINE.md's AlexNet / Inception-v3 / ResNet-152 scaling tables).

Unlike a hand-rolled JAX loop, every measured step here is
`Module._step`/`Module._step_scan` — the same code path `Module.fit` runs —
so the number is the framework's: symbol trace -> simple_bind executor ->
fused fwd+bwd+SGD-momentum in one XLA program, with
`--batches-per-dispatch K` chaining K steps into one `lax.scan` dispatch
(Module's scan feature) so sustained device throughput isn't hidden behind
the host's per-dispatch cost.

Measures the chip: with no TPU it exits with an error (bench.py and
tools/bench_all.py read its rate as a device number).

`--dtype bfloat16` binds params + activations in bf16 — the MXU-native
dtype — via Module.bind's type_dict; BN statistics/aux stay f32 (the op
computes stats in f32 internally, matching cuDNN's fp16 BN).
"""
from __future__ import print_function

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def build_module(model, batch, shape, num_classes, dtype, ctx, lr,
                 layout="NCHW"):
    """Gluon zoo net -> traced Symbol -> Module bound at `dtype`."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    if layout != "NCHW" and not model.startswith("resnet"):
        raise SystemExit("--layout NHWC is implemented for the resnet "
                         "family only (model %s is NCHW)" % model)
    kwargs = {} if layout == "NCHW" else {"layout": layout}
    net = vision.get_model(model, classes=num_classes, **kwargs)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net(mx.nd.zeros((batch,) + shape, ctx=ctx))  # materialize params
    sym = net._trace_symbol()
    sym = mx.sym.SoftmaxOutput(sym, name="softmax")

    mod = mx.mod.Module(sym, context=ctx)
    type_dict = None
    if dtype != "float32":
        type_dict = {"data": dtype}
        type_dict.update({p: dtype for p in mod._param_names})
    mod.bind(data_shapes=[("data", (batch,) + shape)],
             label_shapes=[("softmax_label", (batch,))],
             type_dict=type_dict)
    arg_params = {k: v.data() for k, v in net.collect_params().items()}
    mod.init_params(initializer=mx.init.Xavier(), arg_params=arg_params,
                    allow_missing=True)
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": lr,
                                         "momentum": 0.9})
    return mod


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", type=str, default="resnet50_v1")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--image-shape", type=str, default="3,224,224")
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--dtype", type=str, default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--batches-per-dispatch", type=int, default=10)
    p.add_argument("--num-calls", type=int, default=3)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--layout", default="NCHW", choices=["NCHW", "NHWC"],
                   help="NHWC is the TPU-native conv layout")
    p.add_argument("--scan-unroll", type=int, default=1,
                   help="unroll factor for the K-step lax.scan (removes "
                        "while-loop carry copies; larger compile)")
    p.add_argument("--donate", action="store_true",
                   help="donate the params carry into the scan program "
                        "(in-place weight update; benchmark holds no "
                        "views of old buffers)")
    p.add_argument("--prestack", action="store_true",
                   help="stage the K-batch superbatch once via "
                        "Module.stack_batches and reuse it each call — "
                        "measures sustained step throughput with input "
                        "staging off the critical path (a real pipeline "
                        "stages superbatch N+1 while N trains)")
    p.add_argument("--profile", type=str, default=None, metavar="DIR",
                   help="capture an XPlane trace of the timed region into "
                        "DIR; analyze with python -m mxnet_tpu.xplane DIR")
    args = p.parse_args()

    import mxnet_tpu as mx
    from mxnet_tpu.compiled import enable_compile_cache
    from mxnet_tpu.io import DataBatch

    enable_compile_cache()
    shape = tuple(int(s) for s in args.image_shape.split(","))
    if args.layout == "NHWC":
        shape = (shape[1], shape[2], shape[0])
    batch = args.batch_size
    ctx = mx.tpu()
    device = ctx.jax_device()
    if device.platform != "tpu":
        raise SystemExit("this benchmark measures the chip and found "
                         "platform %r" % device.platform)

    mod = build_module(args.model, batch, shape, args.num_classes,
                       args.dtype, ctx, args.lr, layout=args.layout)
    mod.scan_unroll = args.scan_unroll
    mod.scan_donate_params = args.donate

    rng = np.random.RandomState(0)
    K = args.batches_per_dispatch
    batches = [DataBatch(
        data=[mx.nd.array(rng.rand(batch, *shape), ctx=ctx,
                          dtype=args.dtype)],
        label=[mx.nd.array(
            rng.randint(0, args.num_classes, batch).astype(np.float32),
            ctx=ctx)])
        for _ in range(K)]

    print("compiling %d-step scanned Module train program..." % K,
          flush=True)
    feed = batches
    t0 = time.time()
    if K > 1:
        if args.prestack:
            feed = mod.stack_batches(batches)
        out = mod._step_scan(feed)
        assert out is not False, "fused scan plan unavailable"
    else:
        mod._step(batches[0])
    mod.get_outputs()[0].wait_to_read()
    compile_s = time.time() - t0
    print("compiled in %.1fs" % compile_s, flush=True)

    calls = max(1, args.num_calls)
    if args.profile:
        import jax
        jax.profiler.start_trace(args.profile)
    # two timed rounds (one when profiling); the headline is their mean
    # and every round is printed
    rates = []
    for _ in range(1 if args.profile else 2):
        t0 = time.perf_counter()
        for _ in range(calls):
            if K > 1:
                mod._step_scan(feed)
            else:
                mod._step(batches[0])
        # one wait ends the chain (steps depend on the params carry)
        out = mod.get_outputs()[0]
        out.wait_to_read()
        dt = time.perf_counter() - t0
        rates.append(calls * K * batch / dt)
        assert np.isfinite(out.asnumpy().astype(np.float32)).all()
    rate = sum(rates) / len(rates)
    if args.profile:
        jax.profiler.stop_trace()
        print("trace captured in %s; run: python -m mxnet_tpu.xplane %s "
              "--line 'XLA Ops'" % (args.profile, args.profile))

    # -- step-time anatomy attribution pass (mxnet_tpu.stepprof) --------
    # Runs AFTER the timed rounds so the headline rate stays
    # uninstrumented: every step here forces a device sync
    # (sync_every=1) so device_compute is a measured wall tile, and the
    # K-batch superbatch is re-staged per step so h2d is visible. Emits
    # one JSON line bench_all.py attaches to the TRAIN metric record.
    _bench_phase_breakdown(args, mod, batches, att_calls=2)

    # MFU: fwd MACs x2 (flops per MAC) x3 (fwd + bwd costs ~2x fwd; the
    # optimizer is O(params), noise). The commonly-quoted "4.09 GFLOPs"
    # for ResNet-50 is actually GMACs (torchvision convention) — true
    # FLOPs are double that.
    FWD_GMAC = {"resnet50_v1": 4.09, "resnet50_v2": 4.09,
                "resnet101_v1": 7.8, "resnet152_v1": 11.5,
                "alexnet": 0.72, "inception_v3": 5.7, "vgg16": 15.5}
    peak_tflops = 197.0 if args.dtype == "bfloat16" else 49.0  # v5e chip
    gmac = FWD_GMAC.get(args.model)
    mfu = ""
    if gmac and "224" in args.image_shape:
        mfu_val = rate * 3 * 2 * gmac * 1e9 / (peak_tflops * 1e12)
        mfu = ", MFU %.1f%% of %.0f TF/s" % (100 * mfu_val, peak_tflops)
    print("model %s dtype %s batch %d: %.1f img/s train via Module._step_scan "
          "on %s (mean of rounds %s; compile %.1fs, %d steps/dispatch "
          "x %d calls%s)"
          % (args.model, args.dtype, batch, rate, device.device_kind,
             ", ".join("%.1f" % r for r in rates), compile_s, K, calls,
             mfu))


def _bench_phase_breakdown(args, mod, batches, att_calls=2):
    """Short instrumented pass: p50 phase shares + bottleneck verdict as
    one JSON line (`bench_all.py` folds it into the TRAIN record so the
    BENCH history carries attribution)."""
    import json
    import numpy as np
    from mxnet_tpu import memprof, runprof, stepprof, telemetry

    K = args.batches_per_dispatch
    stepprof.enable(sync_every=1)
    stepprof.reset()
    # run anatomy over the attribution window only: compile/warmup
    # already happened, so the goodput fraction recorded with the TRAIN
    # metric reflects steady-state training, not this process's startup
    runprof.reset()
    for _ in range(max(1, att_calls)):
        with stepprof.step(batches=K):
            if K > 1:
                mod._step_scan(batches)
            else:
                mod._step(batches[0])
            # wait for the step INSIDE it, bracketed as device_compute:
            # without it the device time would leak out of the record
            # and the verdict would call a compute-bound run
            # dispatch-bound
            with stepprof.phase("device_compute", via="wait"):
                mod.get_outputs()[0].wait_to_read()
    shares = stepprof.shares(basis="p50")
    retr = telemetry.get_metric("jit_retraces_total")
    verdict, hint = stepprof.classify(
        shares, retraces=retr.value if retr else 0,
        fused=mod._fused_plan is not False,
        donated=bool(getattr(mod, "scan_donate_params", False)))
    run_snap = runprof.snapshot()
    # memory anatomy: a forced sample over the steady-state window, so
    # the TRAIN record carries the worst-device peak + scope waterfall
    memprof.sample("bench", force=True)
    print(json.dumps({
        "metric": "train_phase_breakdown", "unit": "share",
        "phases": {k: round(v, 4) for k, v in shares.items()},
        "verdict": verdict, "hint": hint,
        "goodput_fraction": round(run_snap["goodput_fraction"], 4),
        "run_states": {k: round(v, 4)
                       for k, v in run_snap["states"].items()},
        "peak_hbm_bytes": memprof.peak_hbm_bytes(),
        "memory_scopes": memprof.attribution()}),
        flush=True)
    stepprof.write_host_snapshot(force=True)  # telemetry dir, if armed
    runprof.write_host_snapshot(force=True)
    memprof.write_host_snapshot(force=True)


if __name__ == "__main__":
    main()
