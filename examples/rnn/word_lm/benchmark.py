#!/usr/bin/env python
"""LSTM-PTB training-throughput benchmark in tokens/s (the driver's second
metric, BASELINE.json LSTM-PTB; reference example/rnn/word_lm/train.py).

Medium PTB config by default (vocab 10k, 2x650 LSTM, seq 35, batch 32 —
the classic Zaremba et al. setup the reference's word_lm example trains).
The fused RNN op dispatches to the Pallas fused-LSTM kernel on TPU, with
the Pallas backward for training.

Every measured step is the FRAMEWORK's own train path —
`Module._step_scan`: symbolic Embedding -> fused RNN -> decoder ->
SoftmaxOutput, fwd+bwd+SGD fused per step, K steps per `lax.scan`
dispatch (`Module.fit(batches_per_dispatch=K)`'s engine), so the host's
per-dispatch cost doesn't hide sustained device throughput.

Measures the chip: with no TPU it exits with an error
(tools/bench_all.py reads its rate as a device number).
"""
from __future__ import print_function

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "..", ".."))

import numpy as np


def main():
    p = argparse.ArgumentParser(
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    p.add_argument("--vocab", type=int, default=10000)
    p.add_argument("--num-hidden", type=int, default=650)
    p.add_argument("--num-embed", type=int, default=650)
    p.add_argument("--num-layers", type=int, default=2)
    p.add_argument("--seq-len", type=int, default=35)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--batches-per-dispatch", type=int, default=20)
    p.add_argument("--num-calls", type=int, default=4)
    p.add_argument("--lr", type=float, default=1.0)
    args = p.parse_args()

    import mxnet_tpu as mx
    from mxnet_tpu.compiled import enable_compile_cache
    from mxnet_tpu.io import DataBatch

    enable_compile_cache()
    ctx = mx.tpu()
    device = ctx.jax_device()
    if device.platform != "tpu":
        raise SystemExit("this benchmark measures the chip and found "
                         "platform %r" % device.platform)
    T, B, V = args.seq_len, args.batch_size, args.vocab
    H, E = args.num_hidden, args.num_embed

    data = mx.sym.Variable("data")                    # (T, B) token ids
    emb = mx.sym.Embedding(data, input_dim=V, output_dim=E, name="embed")
    rnn = mx.sym.RNN(emb, state_size=H, num_layers=args.num_layers,
                     mode="lstm", name="lstm")        # (T, B, H)
    dec = mx.sym.FullyConnected(mx.sym.Reshape(rnn, shape=(-1, H)),
                                num_hidden=V, name="decoder")
    net = mx.sym.SoftmaxOutput(dec, name="softmax")

    mod = mx.mod.Module(net, context=ctx)
    type_dict = None
    if args.dtype != "float32":
        type_dict = {p_: args.dtype for p_ in mod._param_names}
    mod.bind(data_shapes=[("data", (T, B))],
             label_shapes=[("softmax_label", (T * B,))],
             type_dict=type_dict)
    mod.init_params(initializer=mx.init.Xavier())
    # ELEMENTWISE gradient clipping for numerical stability: without it,
    # lr=1 SGD on random tokens can blow up mid-benchmark and fail the
    # finiteness check. (The reference word_lm recipe clips the GLOBAL
    # norm instead — a different op that needs all grads at once; the
    # fused per-param update path clips per element, which is stronger.
    # Throughput is what's measured; the update-rule flop cost matches.)
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": args.lr,
                                         "clip_gradient": 0.25})

    rng = np.random.RandomState(0)
    K = args.batches_per_dispatch
    batches = [DataBatch(
        data=[mx.nd.array(rng.randint(0, V, (T, B)).astype(np.float32),
                          ctx=ctx)],
        label=[mx.nd.array(rng.randint(0, V, T * B).astype(np.float32),
                           ctx=ctx)]) for _ in range(K)]

    print("compiling %d-step scanned Module LSTM program..." % K,
          flush=True)
    t0 = time.time()
    if K > 1:
        out = mod._step_scan(batches)
        assert out is not False, "fused scan plan unavailable"
    else:
        mod._step(batches[0])
    mod.get_outputs()[0].wait_to_read()
    compile_s = time.time() - t0
    print("compiled in %.1fs" % compile_s, flush=True)

    calls = max(1, args.num_calls)
    # three timed rounds; the headline is their median and every round
    # is printed
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(calls):
            if K > 1:
                mod._step_scan(batches)
            else:
                mod._step(batches[0])
        out = mod.get_outputs()[0]
        out.wait_to_read()
        dt = time.perf_counter() - t0
        rates.append(calls * K * B * T / dt)
        assert np.isfinite(out.asnumpy().astype(np.float32)).all()
    rate = sorted(rates)[len(rates) // 2]
    print("PTB LSTM %dx%d vocab %d dtype %s batch %d seq %d: "
          "%.0f tokens/s train via Module._step_scan on %s "
          "(median of rounds %s; compile %.1fs)"
          % (args.num_layers, H, V, args.dtype, B, T, rate,
             device.device_kind, ", ".join("%.0f" % r for r in rates),
             compile_s))


if __name__ == "__main__":
    main()
