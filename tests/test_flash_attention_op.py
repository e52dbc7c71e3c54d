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


def _dense_attention(q, k, v, scale, causal, window=None):
    """The dense math on [BH, T, D], scores of all keys at once: the
    oracle of the kernels' output and, through autodiff, their
    gradients. ``window``: query i sees keys i - window < j <= i."""
    import jax
    import jax.numpy as jnp
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        i, j = jnp.arange(tq)[:, None], jnp.arange(tk)[None, :]
        mask = i >= j
        if window is not None:
            mask = mask & (i - j < window)
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(q.dtype), v)


@pytest.mark.parametrize("heads_kv", [4, 2, 1])
def test_grouped_query_matches_repeated_heads(heads_kv):
    """k and v with a divisor of q's heads: the output and all three
    gradients are those of the heads repeated by hand, at the model's own
    scale (not 1/sqrt(D))."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import flash_attention
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


@pytest.mark.parametrize("causal,heads_kv,t,scale", [
    (False, 4, 256, None),
    (True, 4, 256, None),
    (True, 2, 256, 1 / 64),
    (True, 1, 256, 1 / 64),
    (True, 2, 200, 1 / 64),
    (False, 1, 200, None),
], ids=["dense", "causal", "grouped2-model-scale", "grouped1-model-scale",
        "ragged-causal", "ragged-dense-grouped1"])
def test_backward_kernels_match_dense_math(monkeypatch, causal, heads_kv, t,
                                           scale):
    """The backward's two kernels, over many blocks of queries and keys
    (32 a block here, forward and backward): the gradients of the dense
    math, causal or not, over 4, 2 or 1 key-value heads, at a length that
    is not a block multiple and at the model's own scale. Scores stay
    inside the kernels: the grad's program holds no [T, T] and no
    [block, T] float32 array."""
    import re
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    blk, heads = 32, 4
    monkeypatch.setattr(pk, "_BWD_BLOCK_Q", blk)
    monkeypatch.setattr(pk, "_BWD_BLOCK_K", blk)
    rng = np.random.RandomState(4)
    q, w = (jnp.asarray(rng.randn(1, t, heads, 8), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.randn(1, t, heads_kv, 8), jnp.float32)
            for _ in range(2))
    bh = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(heads, t, 8)

    def dense(q, k, v):
        rep = heads // heads_kv
        kk, vv = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        o = _dense_attention(bh(q), bh(kk), bh(vv),
                             8 ** -0.5 if scale is None else scale, causal)
        return jnp.transpose(o.reshape(1, heads, t, 8), (0, 2, 1, 3))

    flash = lambda q, k, v: pk.flash_attention(
        q, k, v, scale=scale, causal=causal, block_q=blk, block_k=blk)
    for got, want in zip(_grads(flash, q, k, v, w), _grads(dense, q, k, v, w)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    text = jax.jit(lambda *a: _grads(flash, *a)).lower(q, k, v, w).as_text()
    for rows in (t, blk):
        assert not re.search(r"(?<!\d)%dx%dxf32" % (rows, t), text), rows


@pytest.mark.parametrize("t,window,heads_kv", [
    (256, 40, 2),
    (256, 64, 4),
    (200, 72, 1),
    (200, 200, 2),
    (256, 300, 4),
], ids=["under-grouped2", "block-multiple", "ragged-grouped1", "equal-to-t",
        "over-t"])
def test_window_kernels_match_dense_math(monkeypatch, t, window, heads_kv):
    """The sliding window in the forward and both backward kernels, over
    many 32 x 32 blocks: a window under T (whole blocks below it skipped),
    one that is a block multiple, one over a ragged length with one
    key-value head, one equal to T and one over it (both the causal
    result). Output and the three gradients are the dense math's."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    blk, heads = 32, 4
    monkeypatch.setattr(pk, "_BWD_BLOCK_Q", blk)
    monkeypatch.setattr(pk, "_BWD_BLOCK_K", blk)
    rng = np.random.RandomState(6)
    q, w = (jnp.asarray(rng.randn(1, t, heads, 8), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.randn(1, t, heads_kv, 8), jnp.float32)
            for _ in range(2))
    bh = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(heads, t, 8)

    def dense(q, k, v):
        rep = heads // heads_kv
        kk, vv = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        o = _dense_attention(bh(q), bh(kk), bh(vv), 1 / 64, True, window)
        return jnp.transpose(o.reshape(1, heads, t, 8), (0, 2, 1, 3))

    flash = lambda q, k, v: pk.flash_attention(
        q, k, v, scale=1 / 64, causal=True, block_q=blk, block_k=blk,
        window=window)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), rtol=1e-4,
                               atol=1e-5)
    for got, want in zip(_grads(flash, q, k, v, w), _grads(dense, q, k, v, w)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    if window >= t:
        causal = lambda q, k, v: pk.flash_attention(
            q, k, v, scale=1 / 64, causal=True, block_q=blk, block_k=blk)
        np.testing.assert_array_equal(flash(q, k, v), causal(q, k, v))


@pytest.mark.parametrize("window", [1, 33, 64, 100, 4096])
def test_window_runs_exactly_the_blocks_that_hold_a_pair(window):
    """`_block_runs` skips a (q block, k block) pair exactly when none of
    its (query, key) pairs is causal and inside the window; the backward's
    index maps name, for a skipped block, a block of its sweep that runs."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    bq, bk, t = 32, 16, 256
    i, j = np.arange(t)[:, None], np.arange(t)[None]
    admitted = (j <= i) & (i - j < window)
    for qi in range(t // bq):
        for ki in range(t // bk):
            holds = admitted[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            runs = pk._block_runs(jnp.int32(qi), jnp.int32(ki), causal=True,
                                  window=window, block_q=bq, block_k=bk)
            assert bool(runs) == bool(holds.any()), (qi, ki)


def test_window_attr_through_the_op():
    rng = np.random.RandomState(7)
    q, k, v = (rng.randn(1, 24, 2, 8).astype("f") for _ in range(3))
    out = mx.nd.contrib.flash_attention(
        mx.nd.array(q), mx.nd.array(k), mx.nd.array(v), causal=True,
        window=5).asnumpy()
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(8)
    i, j = np.arange(24)[:, None], np.arange(24)[None]
    s = np.where(((j <= i) & (i - j < 5))[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(out, np.einsum("bhqk,bkhd->bqhd", p, v),
                               rtol=1e-4, atol=1e-5)
