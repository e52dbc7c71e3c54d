#!/usr/bin/env python
"""Long-context LM training with ring-attention sequence parallelism.

The capability the reference lacks (SURVEY.md §2.8: its longest-sequence
tooling is bucketing + cuDNN RNN): a causal transformer LM trained on
sequences longer than one device's memory/compute budget by sharding the
SEQUENCE axis over a ('dp', 'sp') mesh. Attention runs as a ring —
K/V blocks rotate over ICI neighbours via lax.ppermute while each device
accumulates its query block's streaming softmax — so activation memory per
device scales as seq/sp_size and communication overlaps compute.

Runs on the 8-virtual-CPU-device mesh for demonstration:
    env JAX_PLATFORMS=cpu \
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python train_long_lm.py --seq-len 1024
On a real pod slice the same code shards over ICI.
"""
from __future__ import print_function

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--dim", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--dp", type=int, default=2)
    p.add_argument("--sp", type=int, default=4)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--lr", type=float, default=1e-2)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.ring_attention import ring_attention

    ndev = args.dp * args.sp
    if len(jax.devices()) < ndev:
        raise SystemExit("need %d devices (set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=%d "
                         "JAX_PLATFORMS=cpu)" % (ndev, ndev))
    mesh = make_mesh({"dp": args.dp, "sp": args.sp})
    S, D, H = args.seq_len, args.dim, args.heads
    assert S % args.sp == 0

    rng = np.random.RandomState(0)
    # synthetic copy-task-ish data: next token = current token + 1 mod V,
    # with occasional noise — enough structure for the loss to fall fast
    tokens = rng.randint(0, args.vocab, (args.batch * 8, S + 1))
    tokens[:, 1:] = (tokens[:, :1] + np.arange(1, S + 1)) % args.vocab

    def init(key):
        ks = jax.random.split(key, 4 + 4 * args.layers)
        params = {
            "emb": jax.random.normal(ks[0], (args.vocab, D)) * 0.02,
            "out": jax.random.normal(ks[1], (D, args.vocab)) * 0.02,
        }
        for i in range(args.layers):
            params["qkv%d" % i] = \
                jax.random.normal(ks[4 + 4 * i], (D, 3 * D)) * 0.02
            params["proj%d" % i] = \
                jax.random.normal(ks[5 + 4 * i], (D, D)) * 0.02
            params["mlp_in%d" % i] = \
                jax.random.normal(ks[6 + 4 * i], (D, 4 * D)) * 0.02
            params["mlp_out%d" % i] = \
                jax.random.normal(ks[7 + 4 * i], (4 * D, D)) * 0.02
        return params

    def forward(params, toks):
        x = params["emb"][toks]                      # (B, S, D)
        B = x.shape[0]
        for i in range(args.layers):
            h = x / (1e-6 + jnp.sqrt((x * x).mean(-1, keepdims=True)))
            qkv = h @ params["qkv%d" % i]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            to_h = lambda t: t.reshape(B, S, H, D // H)
            # ring attention over the sp-sharded sequence axis
            att = ring_attention(to_h(q), to_h(k), to_h(v), mesh=mesh,
                                 axis="sp", causal=True)
            x = x + att.reshape(B, S, D) @ params["proj%d" % i]
            h = x / (1e-6 + jnp.sqrt((x * x).mean(-1, keepdims=True)))
            x = x + jax.nn.gelu(h @ params["mlp_in%d" % i]) \
                @ params["mlp_out%d" % i]
        return x @ params["out"]

    def loss_fn(params, toks, targets):
        logits = forward(params, toks)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))

    params = init(jax.random.PRNGKey(0))
    params = jax.device_put(params, NamedSharding(mesh, P()))
    tok_sharding = NamedSharding(mesh, P("dp", "sp"))

    adam_m = jax.tree.map(jnp.zeros_like, params)
    adam_v = jax.tree.map(jnp.zeros_like, params)

    @jax.jit
    def step(params, m, v, t, toks, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, toks, targets)
        b1, b2, eps = 0.9, 0.999, 1e-8
        m = jax.tree.map(lambda mm, g: b1 * mm + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda vv, g: b2 * vv + (1 - b2) * g * g, v, grads)
        lr_t = args.lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        params = jax.tree.map(
            lambda p, mm, vv: p - lr_t * mm / (jnp.sqrt(vv) + eps),
            params, m, v)
        return loss, params, m, v

    first = None
    t0 = time.time()
    for it in range(args.steps):
        i = (it * args.batch) % (tokens.shape[0] - args.batch)
        toks = jax.device_put(
            jnp.asarray(tokens[i:i + args.batch, :S]), tok_sharding)
        tgts = jax.device_put(
            jnp.asarray(tokens[i:i + args.batch, 1:S + 1]), tok_sharding)
        loss, params, adam_m, adam_v = step(params, adam_m, adam_v,
                                            float(it + 1), toks, tgts)
        loss = float(loss)
        first = loss if first is None else first
        if it % 4 == 0:
            print("step %d loss %.4f" % (it, loss))
    dt = time.time() - t0
    print("seq %d over %d-way ring: loss %.4f -> %.4f, %.1f tok/s"
          % (S, args.sp, first, loss,
             args.steps * args.batch * S / dt))
    assert loss < first, "loss did not improve"
    print("LONG-CONTEXT TRAINING OK")


if __name__ == "__main__":
    main()
