"""_contrib_flash_attention op: nd/symbol/grad integration (the kernel
itself is covered by tests/test_pallas.py; this is the registry surface)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd


def _oracle(q, k, v, causal):
    B, T, H, D = q.shape
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    if causal:
        mask = np.tril(np.ones((T, T), bool))
        s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_nd_matches_oracle(causal):
    rng = np.random.RandomState(0)
    q, k, v = (rng.randn(2, 16, 4, 8).astype("f") for _ in range(3))
    out = mx.nd.contrib.flash_attention(
        mx.nd.array(q), mx.nd.array(k), mx.nd.array(v), causal=causal)
    np.testing.assert_allclose(out.asnumpy(), _oracle(q, k, v, causal),
                               rtol=1e-4, atol=1e-5)


def test_gradient_flows():
    rng = np.random.RandomState(1)
    q = mx.nd.array(rng.randn(1, 8, 2, 8).astype("f"))
    k = mx.nd.array(rng.randn(1, 8, 2, 8).astype("f"))
    v = mx.nd.array(rng.randn(1, 8, 2, 8).astype("f"))
    for x in (q, k, v):
        x.attach_grad()
    with autograd.record():
        out = mx.nd.contrib.flash_attention(q, k, v, causal=True)
    out.backward()
    for x in (q, k, v):
        g = x.grad.asnumpy()
        assert np.isfinite(g).all() and np.abs(g).sum() > 0


def test_symbol_binds():
    rng = np.random.RandomState(2)
    qn, kn, vn = (rng.randn(2, 12, 2, 8).astype("f") for _ in range(3))
    sym = mx.sym.contrib.flash_attention(
        mx.sym.var("q"), mx.sym.var("k"), mx.sym.var("v"), causal=False)
    ex = sym.simple_bind(mx.cpu(), q=qn.shape, k=kn.shape, v=vn.shape)
    ex.arg_dict["q"][:] = qn
    ex.arg_dict["k"][:] = kn
    ex.arg_dict["v"][:] = vn
    out = ex.forward()[0].asnumpy()
    np.testing.assert_allclose(out, _oracle(qn, kn, vn, False),
                               rtol=1e-4, atol=1e-5)


def _grads(fn, q, k, v, w):
    import jax
    import jax.numpy as jnp
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * w),
                    argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("heads_kv", [4, 2, 1])
def test_grouped_query_matches_repeated_heads(heads_kv):
    """k and v with a divisor of q's heads: the output and all three
    gradients are those of the heads repeated by hand, at the model's own
    scale (not 1/sqrt(D))."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import flash_attention, _dense_attention
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 16, 4, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, 16, heads_kv, 8), jnp.float32)
    v = jnp.asarray(rng.randn(2, 16, heads_kv, 8), jnp.float32)
    w = jnp.asarray(rng.randn(2, 16, 4, 8), jnp.float32)

    def dense(q, k, v):
        rep = 4 // heads_kv
        kk, vv = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        bh = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(8, 16, 8)
        o = _dense_attention(bh(q), bh(kk), bh(vv), 1 / 64, True)
        return jnp.transpose(o.reshape(2, 4, 16, 8), (0, 2, 1, 3))

    flash = lambda q, k, v: flash_attention(q, k, v, scale=1 / 64,
                                            causal=True, block_q=8, block_k=8)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v),
                               rtol=1e-4, atol=1e-5)
    for got, want in zip(_grads(flash, q, k, v, w), _grads(dense, q, k, v, w)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_long_sequence_backward_goes_over_query_blocks(causal):
    """The backward works a block of queries at a time: the same gradients
    as the dense math, and no [T, T] array of scores in its program."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import flash_attention, _dense_attention
    rng = np.random.RandomState(4)
    t, blk = 256, 32
    q, k, v, w = (jnp.asarray(rng.randn(1, t, 2, 8), jnp.float32)
                  for _ in range(4))
    bh = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(2, t, 8)

    def dense(q, k, v):
        o = _dense_attention(bh(q), bh(k), bh(v), 8 ** -0.5, causal)
        return jnp.transpose(o.reshape(1, 2, t, 8), (0, 2, 1, 3))

    flash = lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                            block_q=blk, block_k=blk)
    for got, want in zip(_grads(flash, q, k, v, w), _grads(dense, q, k, v, w)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    text = jax.jit(lambda *a: _grads(flash, *a)).lower(q, k, v, w).as_text()
    assert "%dx%dxf32" % (t, t) not in text
    assert "%dx%dxf32" % (blk, t) in text
