#!/usr/bin/env python
"""Model-parallel stacked LSTM over a device mesh.

Reference analog: example/model-parallel/lstm (group2ctx placing each
LSTM layer on its own GPU, docs/faq/model_parallel_lstm.md). The
TPU-native mapping (SURVEY.md §2.8): instead of placing layers on
devices and copying activations across, every layer's weight matrices
are sharded over the 'mp' mesh axis and the batch over 'dp'; XLA inserts
the collectives that the reference's _CrossDeviceCopy nodes did by hand.

Runs on a virtual CPU mesh by default:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python lstm_sharded.py
"""
from __future__ import print_function

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main():
    parser = argparse.ArgumentParser(
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("--num-hidden", type=int, default=64)
    parser.add_argument("--num-layers", type=int, default=2)
    parser.add_argument("--seq-len", type=int, default=16)
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--steps", type=int, default=5)
    parser.add_argument("--dp", type=int, default=0,
                        help="data-parallel mesh size (0 = devices/mp)")
    parser.add_argument("--mp", type=int, default=2,
                        help="model-parallel mesh size")
    args = parser.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import rnn, nn

    np.random.seed(0)  # initializers draw from numpy's global RNG

    devices = jax.devices()
    mp = min(args.mp, len(devices))
    dp = args.dp or max(1, len(devices) // mp)
    mesh = Mesh(np.asarray(devices[:dp * mp]).reshape(dp, mp), ("dp", "mp"))
    print("mesh:", dict(dp=dp, mp=mp), "on", len(devices), "devices")

    V, E, H = 128, 32, args.num_hidden
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Embedding(V, E))
        net.add(rnn.LSTM(H, num_layers=args.num_layers, layout="NTC"))
        net.add(nn.Dense(V, flatten=False))
    net.initialize()
    net.hybridize()
    B, T = args.batch_size * dp, args.seq_len
    net(mx.nd.zeros((B, T)))  # build the cached jit
    names = net._param_order
    params = net.collect_params()
    cached = net._cached_jit
    key = jax.random.PRNGKey(0)

    def spec(name, v):
        # LSTM gate blocks (4H, in) shard their output rows over mp; the
        # recurrent weight shards both dims; biases shard over mp.
        if "i2h_weight" in name or "h2h_weight" in name:
            return P("mp", None)
        if "i2h_bias" in name or "h2h_bias" in name:
            return P("mp")
        if v.ndim == 2 and v.shape[1] == H:   # output Dense (V, H)
            return P(None, "mp")
        return P()

    pvals = [params[n].data()._data for n in names]
    pshard = [NamedSharding(mesh, spec(n, v))
              for n, v in zip(names, pvals)]
    pvals = [jax.device_put(v, s) for v, s in zip(pvals, pshard)]
    bshard = NamedSharding(mesh, P("dp"))

    def loss_fn(pv, x, y):
        logits = cached(tuple(pv), key, True, mx.cpu(), x)[0][0]   # (B, T, V)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(
            logp, y[..., None].astype(jnp.int32), axis=-1))

    def train_step(pv, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(pv, x, y)
        return loss, [p - 0.1 * g for p, g in zip(pv, grads)]

    step = jax.jit(train_step,
                   in_shardings=(pshard, bshard, bshard),
                   out_shardings=(NamedSharding(mesh, P()), pshard))

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(0, V, (B, T)), jnp.float32)
    y = jnp.asarray(rng.randint(0, V, (B, T)), jnp.float32)
    x = jax.device_put(x, bshard)
    y = jax.device_put(y, bshard)
    losses = []
    for _ in range(args.steps):
        loss, pvals = step(pvals, x, y)
        losses.append(float(loss))
    print("losses:", ["%.4f" % l for l in losses])
    assert losses[-1] < losses[0], "loss should decrease"
    print("sharded LSTM train OK; layer-0 i2h sharding:",
          pvals[names.index([n for n in names if "l0_i2h_weight" in n][0])]
          .sharding)
    return losses


if __name__ == "__main__":
    main()
