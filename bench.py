"""Headline benchmarks: ResNet-50 train (bf16 bs128, the north-star
metric) and ResNet-50 inference (bs32).

Inference matches the reference's benchmark_score.py configuration
(`/root/reference/example/image-classification/README.md:147-156`:
ResNet-50, batch 32, 1 chip — reference scores 109 img/s on a K80).
Train is the driver-defined A100-class target (BASELINE.md: 2,900
img/s/chip) measured through the framework's own Module._step_scan path
(`examples/image-classification/benchmark.py`, the bench_all.py config).

Measures DEVICE throughput: the timed iterations run inside one compiled
program (lax.fori_loop over the hybridized forward); a timed round chains
several invocations through a data dependency and ends in
``block_until_ready``.

One process for each chip: the train benchmark is a child process, so it
runs FIRST, before this process imports jax and takes the chip; its
record is held and printed last. No chip is an error, and so is a train
child that fails: nothing here runs on the CPU instead.

Prints one JSON line per metric ({"metric", "value", "unit",
"vs_baseline"}); the TRAIN line prints last — it is the north-star
number the driver records.
"""
from __future__ import annotations

import json
import os
import sys
import time

BASELINE_IMG_S = 109.0  # K80 ResNet-50 batch-32 inference (BASELINE.md)
TRAIN_TARGET_IMG_S = 2900.0  # A100-class train target (BASELINE.md)


RECORDS = []  # every JSON metric line this run printed (for --gate)


def emit(rec):
    RECORDS.append(rec)
    print(json.dumps(rec), flush=True)


def bench_train():
    """ResNet-50 bf16 bs128 NHWC train img/s via Module._step_scan.

    The config lives in ONE place — tools/bench_all.py's
    bench_resnet50_train, which runs it as a child process. Called
    before this process has imported jax: the child needs the chip.
    Returns the record; a failing child raises.
    """
    assert "jax" not in sys.modules, "the train child needs the chip"
    return tools_import("bench_all").bench_resnet50_train()


def emit_train(rec):
    emit(rec)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bench_stepprof.json")
    if rec.get("phases"):
        # leave the anatomy where `python -m mxnet_tpu.stepprof report`
        # finds it with no arguments (next to bench_telemetry.prom)
        with open(path, "w", encoding="utf-8") as fh:
            json.dump({"metric": "train_phase_breakdown",
                       "phases": rec["phases"],
                       "verdict": rec.get("verdict"),
                       "source_metric": rec["metric"],
                       "updated": time.time()}, fh)
    elif os.path.exists(path):
        # a previous round's anatomy must never masquerade as this run's
        os.remove(path)


def tools_import(name):
    """Import a module out of the repo's tools/ dir (idempotent path
    setup shared by the train/serve gate paths)."""
    import importlib
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools")
    if path not in sys.path:
        sys.path.insert(0, path)
    return importlib.import_module(name)


def run_gate(*metrics):
    """Gate this run's RECORDS against the repo history (one
    gate_records pass per metric; default metric selection when none
    given); exits with the worst result."""
    gate = tools_import("bench_gate")
    if not metrics:
        raise SystemExit(gate.gate_records(RECORDS))
    raise SystemExit(max(gate.gate_records(RECORDS, metric=m)
                         for m in metrics))


def bench_serve():
    """--serve mode: closed+open-loop load against the dynamic-batching
    inference engine (`tools/serve_bench.py`), emitted as the same JSON
    metric lines as the train/infer benches so `--gate` and the BENCH
    history tooling parse them unchanged."""
    for rec in tools_import("serve_bench").bench_records():
        emit(rec)


def main():
    if "--serve" in sys.argv:
        bench_serve()
        write_telemetry_snapshot()
        if "--gate" in sys.argv:
            # gate the serving headlines, not the TRAIN metric this run
            # never emitted (which would skip-pass unconditionally):
            # throughput down OR p99 latency up both fail the round
            run_gate("serving_closed_rps", "serving_closed_p99_ms")
        return
    train_rec = None if "--infer-only" in sys.argv else bench_train()

    import jax
    import jax.numpy as jnp
    from jax import lax

    import mxnet_tpu as mx
    from mxnet_tpu.compiled import enable_compile_cache
    from mxnet_tpu.gluon.model_zoo import vision

    enable_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit("bench.py measures the chip and found platform "
                         "%r: no number is taken on another device"
                         % device.platform)
    batch, iters = 32, 100
    ctx = mx.tpu()
    # NCHW measured FASTER than NHWC for bs32 fp32 inference (10,033 vs
    # 9,956 img/s): the space-to-depth stem rewrite is NCHW-only and
    # outweighs the channel-minor layout win at this batch size
    layout = os.environ.get("MXNET_BENCH_LAYOUT", "NCHW")
    if layout not in ("NCHW", "NHWC"):
        raise SystemExit("MXNET_BENCH_LAYOUT must be NCHW or NHWC, got %r"
                         % layout)
    kwargs = {"layout": layout} if layout != "NCHW" else {}
    net = vision.resnet50_v1(**kwargs)
    net.initialize(ctx=ctx)
    net.hybridize()

    shape = (batch, 3, 224, 224) if layout == "NCHW" \
        else (batch, 224, 224, 3)
    x = mx.nd.random.uniform(shape=shape, ctx=ctx)
    net(x).asnumpy()  # build + warm the cached jit

    cached = net._cached_jit
    params = tuple(net.collect_params()[n].data()._data
                   for n in net._param_order)
    key = jax.random.PRNGKey(0)

    @jax.jit
    def loop(pv, xv, acc0):
        # roll the batch each iteration so the forward depends on the loop
        # counter — otherwise XLA's invariant code motion hoists the whole
        # network out of the loop and we'd time ONE forward, not `iters`.
        # (Tried: feeding the dependence through the accumulator instead —
        # the roll's 0.083 ms of slice traffic disappears from the trace
        # but measured THROUGHPUT drops ~0.7%: the roll depends only on
        # `i`, so consecutive forwards overlap; an acc-dependent input
        # strictly serializes them.)
        def body(i, acc):
            xi = jnp.roll(xv, i, axis=0)
            return acc + cached(pv, key, False, ctx, xi)[0][0].sum()
        return lax.fori_loop(0, iters, body, acc0)

    xv = x._data
    # each timed round chains `calls` loop invocations through the
    # accumulator (a data dependency, so the device runs them
    # back-to-back) and waits once
    calls = 8
    # AOT-compile the timed loop: one compile (same executable the timed
    # calls run) and its cost_analysis gives the MFU/goodput numerator
    from mxnet_tpu import xla_stats
    compiled, info = xla_stats.aot_compile(loop, params, xv,
                                           jnp.float32(0))
    run = compiled if compiled is not None else loop
    run(params, xv, jnp.float32(0)).block_until_ready()  # compile / warm
    rounds = []
    for _ in range(2):
        t0 = time.perf_counter()
        acc = jnp.float32(0)
        for _ in range(calls):
            acc = run(params, xv, acc)
        acc.block_until_ready()
        rounds.append(time.perf_counter() - t0)
    dt = sum(rounds) / len(rounds)
    rate = batch * iters * calls / dt

    emit({
        "metric": "resnet50_infer_imgs_per_sec_bs32",
        "value": round(rate, 2),
        "unit": "img/s",
        "vs_baseline": round(rate / BASELINE_IMG_S, 3),
        "platform": device.platform, "device_kind": device.device_kind,
        "rounds_s": [round(r, 4) for r in rounds],
    })
    write_goodput(info, calls, dt)
    if train_rec is not None:
        emit_train(train_rec)
    write_telemetry_snapshot()
    if "--gate" in sys.argv:
        run_gate()


def write_goodput(info, calls, dt):
    """`model_flops_per_second` and `mfu` metric lines for the measured
    inference loop (flops from the compiled executable's cost_analysis;
    peak table / MXNET_PEAK_FLOPS from `xla_stats`). Degrades to zeros
    when the backend reports no cost analysis."""
    import jax
    from mxnet_tpu import xla_stats
    flops = (info or {}).get("flops") or 0.0
    mfps = flops * calls / dt if dt else 0.0
    peak = xla_stats.peak_flops_total()
    platform = jax.devices()[0].platform
    g = xla_stats.publish_goodput(mfps)  # the one gauge publisher
    emit({"metric": "model_flops_per_second", "value": round(mfps, 3),
          "unit": "FLOP/s", "platform": platform})
    emit({"metric": "mfu", "value": round(g["mfu"], 5),
          "unit": "ratio", "platform": platform,
          "peak_flops_total": peak})


def write_telemetry_snapshot():
    """Drop the run's telemetry registry (Prometheus text) next to the
    JSON metric lines, so a bench round leaves machine-readable runtime
    series (kvstore traffic, dispatch timings, fit phases) behind, not
    just the headline numbers."""
    from mxnet_tpu import telemetry
    path = telemetry.write_snapshot(
        None if telemetry.configured_dir()
        else os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "bench_telemetry.prom"))
    print(json.dumps({"metric": "telemetry_snapshot", "value": path,
                      "unit": "path"}), flush=True)


if __name__ == "__main__":
    main()
