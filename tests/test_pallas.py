"""Pallas kernel tests (interpret mode on CPU).

Oracle is dense JAX math, mirroring how the reference cross-checks cuDNN
kernels against CPU (`tests/python/gpu/test_operator_gpu.py`
check_consistency).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.ops.pallas_kernels import flash_attention, fused_lstm
from mxnet_tpu.parallel.ring_attention import local_attention


def _qkv(b=2, t=64, h=2, d=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_dense(causal):
    q, k, v = _qkv()
    got = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    want = local_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_ragged_blocks():
    # T not a multiple of the block size exercises the tail-padding mask
    q, k, v = _qkv(t=40)
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    want = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t", [32, 40], ids=["blocks", "ragged"])
def test_flash_attention_grad(monkeypatch, t):
    # the backward's kernels over several 16-wide blocks each way
    from mxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(pk, "_BWD_BLOCK_Q", 16)
    monkeypatch.setattr(pk, "_BWD_BLOCK_K", 16)
    q, k, v = _qkv(t=t)
    f = lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True, block_q=16, block_k=16) ** 2)
    fd = lambda q, k, v: jnp.sum(local_attention(q, k, v, causal=True) ** 2)
    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gw = jax.grad(fd, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gw):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_log_sum_exp(causal):
    """The forward's second output, the backward's residual: each row's
    log-sum-exp of its scaled, masked scores, the same in all 128 lanes;
    the attention output is what the one-output forward gives."""
    from mxnet_tpu.ops import pallas_kernels as pk
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(3, 40, 8), jnp.float32)
               for _ in range(3))
    o, lse = pk._flash_fwd(q, k, v, 0.3, causal, 16, 16)
    s = jnp.einsum("bqd,bkd->bqk", q, k) * 0.3
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((40, 40), bool))[None], s, -1e30)
    assert lse.shape == (3, 40, 128)
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(jnp.broadcast_to(
                                   jax.nn.logsumexp(s, axis=-1)[..., None],
                                   lse.shape)), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(o), np.asarray(pk._flash(q, k, v, 0.3, causal, 16, 16)))


def _lstm_ref(x, h0, c0, wx, wh, b):
    hs = []
    h, c = h0, c0
    hid = wh.shape[0]
    for t in range(x.shape[0]):
        gates = x[t] @ wx + h @ wh + b
        i = jax.nn.sigmoid(gates[:, :hid])
        f = jax.nn.sigmoid(gates[:, hid:2 * hid])
        g = jnp.tanh(gates[:, 2 * hid:3 * hid])
        o = jax.nn.sigmoid(gates[:, 3 * hid:])
        c = f * c + i * g
        h = o * jnp.tanh(c)
        hs.append(h)
    return jnp.stack(hs), h, c


def test_fused_lstm_matches_scan():
    rng = np.random.RandomState(1)
    t, bs, inp, hid = 5, 4, 6, 8
    x = jnp.asarray(rng.randn(t, bs, inp).astype(np.float32))
    h0 = jnp.asarray(rng.randn(bs, hid).astype(np.float32))
    c0 = jnp.asarray(rng.randn(bs, hid).astype(np.float32))
    wx = jnp.asarray(rng.randn(inp, 4 * hid).astype(np.float32) * 0.1)
    wh = jnp.asarray(rng.randn(hid, 4 * hid).astype(np.float32) * 0.1)
    b = jnp.asarray(rng.randn(4 * hid).astype(np.float32) * 0.1)
    hseq, hn, cn = fused_lstm(x, h0, c0, wx, wh, b)
    hseq_w, hn_w, cn_w = _lstm_ref(x, h0, c0, wx, wh, b)
    np.testing.assert_allclose(np.asarray(hseq), np.asarray(hseq_w),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hn), np.asarray(hn_w),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cn), np.asarray(cn_w),
                               rtol=1e-5, atol=1e-5)


def test_rtc_pallas_module():
    import mxnet_tpu as mx
    from mxnet_tpu import rtc

    mod = rtc.PallasModule("""
def axpy(x_ref, y_ref, out_ref):
    out_ref[:] = 2.0 * x_ref[:] + y_ref[:]
""")
    k = mod.get_kernel("axpy")
    x = mx.nd.array(np.arange(8, dtype=np.float32))
    y = mx.nd.ones((8,))
    out = k.launch((x, y), out_shapes=[((8,), "float32")])
    np.testing.assert_allclose(out.asnumpy(),
                               2 * np.arange(8, dtype=np.float32) + 1)
    with pytest.raises(ValueError):
        mod.get_kernel("missing")


def test_fused_lstm_grad():
    rng = np.random.RandomState(2)
    t, bs, inp, hid = 3, 2, 4, 5
    args = (jnp.asarray(rng.randn(t, bs, inp).astype(np.float32)),
            jnp.zeros((bs, hid), jnp.float32),
            jnp.zeros((bs, hid), jnp.float32),
            jnp.asarray(rng.randn(inp, 4 * hid).astype(np.float32) * 0.1),
            jnp.asarray(rng.randn(hid, 4 * hid).astype(np.float32) * 0.1),
            jnp.zeros((4 * hid,), jnp.float32))
    loss = lambda *a: jnp.sum(fused_lstm(*a)[0] ** 2)
    from mxnet_tpu.ops.pallas_kernels import _lstm_scan_ref
    loss_ref = lambda *a: jnp.sum(_lstm_scan_ref(*a)[0] ** 2)
    g = jax.grad(loss, argnums=tuple(range(6)))(*args)
    gw = jax.grad(loss_ref, argnums=tuple(range(6)))(*args)
    for a, b in zip(g, gw):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
