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


def _block_steps():
    from mxnet_tpu import telemetry
    steps = {}
    for kernel in ("fwd", "dkv", "dq"):
        for kind in ("run", "dense"):
            m = telemetry.get_metric("flash_attention_block_steps_total",
                                     kernel=kernel, kind=kind)
            steps[kernel, kind] = m.value if m is not None else 0.0
    return steps


@pytest.mark.parametrize("t,window,heads_kv", [
    (256, 40, 2),
    (256, 64, 4),
    (200, 72, 1),
    (200, 200, 2),
    (256, 300, 4),
    (256, 45, 1),
    (200, 45, 1),
], ids=["under-grouped2", "block-multiple", "ragged-grouped1", "equal-to-t",
        "over-t", "off-block-grouped1", "off-block-ragged-grouped1"])
def test_window_kernels_match_dense_math(monkeypatch, t, window, heads_kv):
    """The sliding window in the forward and both backward kernels, over
    many 32 x 32 blocks: a window under T (whole blocks below it not
    visited), one that is a block multiple, one over a ragged length with
    one key-value head, one equal to T and one over it (both the causal
    result), one that is no block multiple over one key-value head. Output,
    the forward's log-sum-exp and the three gradients are the dense math's,
    and each kernel visits fewer grid steps than the dense grid has."""
    import jax
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
    before = _block_steps()
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), rtol=1e-4,
                               atol=1e-5)
    for got, want in zip(_grads(flash, q, k, v, w), _grads(dense, q, k, v, w)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    after = _block_steps()
    for kernel in ("fwd", "dkv", "dq"):
        run, full = (after[kernel, kind] - before[kernel, kind]
                     for kind in ("run", "dense"))
        assert 0 < run < full, kernel
    kk = jnp.repeat(k, heads // heads_kv, axis=2)
    lse = pk._flash_fwd(bh(q), bh(kk), bh(kk), 1 / 64, True, blk, blk,
                        window)[1]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None]
    s = jnp.einsum("bqd,bkd->bqk", bh(q), bh(kk)) / 64
    s = jnp.where(((j <= i) & (i - j < window))[None], s, -1e30)
    np.testing.assert_allclose(lse[..., 0], jax.nn.logsumexp(s, axis=-1),
                               rtol=1e-5, atol=1e-5)
    if window >= t:
        causal = lambda q, k, v: pk.flash_attention(
            q, k, v, scale=1 / 64, causal=True, block_q=blk, block_k=blk)
        np.testing.assert_array_equal(flash(q, k, v), causal(q, k, v))


@pytest.mark.parametrize("causal,window,seq_q,seq_k", [
    (True, 1, 256, 256),
    (True, 33, 256, 256),
    (True, 64, 256, 256),
    (True, 100, 256, 256),
    (True, 4096, 256, 256),
    (True, None, 256, 256),
    (False, None, 256, 256),
    (True, 45, 200, 200),
    (False, None, 200, 200),
    (True, None, 100, 256),
], ids=["1", "33", "64", "100", "4096", "causal", "dense", "ragged-window",
        "ragged-dense", "keys-past-queries"])
def test_window_runs_exactly_the_blocks_that_hold_a_pair(causal, window,
                                                         seq_q, seq_k):
    """`_schedule` lists, in each sweep's order (q-major with k ascending,
    k-major with q ascending), exactly the (q block, k block) pairs that
    hold a (query, key) pair that counts; `_FIRST` and `_LAST` mark each
    output block's first and last pair, `_EDGE` the pairs that also hold
    one that does not count (above the diagonal, below the window, or
    padding), and an output block with none gets one `_EMPTY` entry. Found
    here pair by pair; without ``causal`` the table is the dense grid."""
    from mxnet_tpu.ops import pallas_kernels as pk
    bq, bk = 32, 16
    nq, nk = -(-seq_q // bq), -(-seq_k // bk)
    i, j = np.arange(nq * bq)[:, None], np.arange(nk * bk)[None]
    admitted = (i < seq_q) & (j < seq_k)
    if causal:
        admitted &= j <= i
    if window is not None:
        admitted &= i - j < window
    blocks = admitted.reshape(nq, bq, nk, bk)
    holds, whole = blocks.any(axis=(1, 3)), blocks.all(axis=(1, 3))
    for k_major in (False, True):
        want = []
        for out in range(nk if k_major else nq):
            pairs = ([(q, out) for q in range(nq)] if k_major
                     else [(out, k) for k in range(nk)])
            run = [[p, 0 if whole[p] else pk._EDGE]
                   for p in pairs if holds[p]]
            run = run or [[pairs[0], pk._EMPTY]]
            run[0][1] |= pk._FIRST
            run[-1][1] |= pk._LAST
            want += [(p, f) for p, f in run]
        qb, kb, fl = pk._schedule(causal=causal, window=window, block_q=bq,
                                  block_k=bk, seq_q=seq_q, seq_k=seq_k,
                                  k_major=k_major)
        assert qb.dtype == kb.dtype == fl.dtype == np.int32
        got = [((q, k), f) for q, k, f in zip(qb.tolist(), kb.tolist(),
                                                fl.tolist())]
        assert got == want, k_major
        if not causal:
            assert len(fl) == nq * nk
        if seq_q == seq_k:
            assert not (fl & pk._EMPTY).any()


def test_keys_past_the_queries(monkeypatch):
    """Causal attention over more keys than queries (query i sees the keys
    j <= i): the k blocks past the last query hold no pair, so dK/dV's
    schedule gives each of them one `_EMPTY` entry, which writes their
    gradients as the zeros of the dense math."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    blk, tq, tk = 16, 40, 100
    monkeypatch.setattr(pk, "_BWD_BLOCK_Q", blk)
    monkeypatch.setattr(pk, "_BWD_BLOCK_K", blk)
    fl = pk._schedule(causal=True, window=None, block_q=blk, block_k=blk,
                      seq_q=tq, seq_k=tk, k_major=True)[2]
    assert np.count_nonzero(fl & pk._EMPTY) == 4
    rng = np.random.RandomState(9)
    q, w = (jnp.asarray(rng.randn(1, tq, 2, 8), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.randn(1, tk, 2, 8), jnp.float32)
            for _ in range(2))
    bh = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(2, -1, 8)

    def dense(q, k, v):
        o = _dense_attention(bh(q), bh(k), bh(v), 1 / 8, True)
        return jnp.transpose(o.reshape(1, 2, tq, 8), (0, 2, 1, 3))

    flash = lambda q, k, v: pk.flash_attention(
        q, k, v, scale=1 / 8, causal=True, block_q=blk, block_k=blk)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), rtol=1e-4,
                               atol=1e-5)
    grads = _grads(flash, q, k, v, w)
    for got, want in zip(grads, _grads(dense, q, k, v, w)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert not np.asarray(grads[1][:, 3 * blk:]).any()


def test_block_steps_at_smallthinker_shapes():
    """`flash_attention_block_steps_total` over one SmallThinker period's
    attention (two rows of 8,192 tokens, 28 query heads over 4 key-value
    heads of 128: one full causal layer, three with a 4,096-key window),
    traced and not run: the schedules visit 460 / 1,024 of the forward's
    dense grid steps and 252 / 512 of each backward kernel's."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    spec = lambda heads: jax.ShapeDtypeStruct((2, 8192, heads, 128),
                                              jnp.bfloat16)

    def loss(q, k, v):
        return sum(jnp.sum(pk.flash_attention(
            q, k, v, scale=128 ** -0.5, causal=True, window=window)
            .astype(jnp.float32)) for window in (None, 4096, 4096, 4096))

    before = _block_steps()
    jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), spec(28), spec(4),
                   spec(4))
    after = _block_steps()
    steps = {key: after[key] - before[key] for key in after}
    assert steps["fwd", "dense"] == 56 * 4 * 16 * 16
    assert steps["fwd", "run"] == 56 * (136 + 3 * 108)
    for kernel in ("dkv", "dq"):
        assert steps[kernel, "dense"] == 56 * 4 * 8 * 16
        assert steps[kernel, "run"] == 56 * (72 + 3 * 60)
    share = {k: steps[k, "run"] / steps[k, "dense"]
             for k in ("fwd", "dkv", "dq")}
    assert round(share["fwd"], 3) == 0.449
    assert round(share["dkv"], 3) == round(share["dq"], 3) == 0.492


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


@pytest.mark.parametrize("causal,dv,t", [
    (True, 16, 96), (False, 16, 96), (True, 8, 80), (True, 32, 64),
], ids=["causal-narrow", "dense-narrow", "causal-ragged", "causal-wide"])
def test_value_width_apart_from_key_width(monkeypatch, causal, dv, t):
    """Latent attention's heads: queries and keys 24 wide (16 without
    position, 8 rotary), values ``dv`` wide. The output is ``dv`` wide and
    it and all three gradients are the dense math's, over several blocks
    (32 a block, forward and backward), causal or not, at a length that is
    not a block multiple, at the scale 1/sqrt(24) given explicitly."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    blk, heads, d = 32, 2, 24
    monkeypatch.setattr(pk, "_BWD_BLOCK_Q", blk)
    monkeypatch.setattr(pk, "_BWD_BLOCK_K", blk)
    rng = np.random.RandomState(8)
    q, k = (jnp.asarray(rng.randn(2, t, heads, d), jnp.float32)
            for _ in range(2))
    v, w = (jnp.asarray(rng.randn(2, t, heads, dv), jnp.float32)
            for _ in range(2))
    bh = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(
        2 * heads, t, x.shape[-1])

    def dense(q, k, v):
        o = _dense_attention(bh(q), bh(k), bh(v), d ** -0.5, causal)
        return jnp.transpose(o.reshape(2, heads, t, dv), (0, 2, 1, 3))

    flash = lambda q, k, v: pk.flash_attention(
        q, k, v, scale=d ** -0.5, causal=causal, block_q=blk, block_k=blk)
    out = flash(q, k, v)
    assert out.shape == (2, t, heads, dv)
    np.testing.assert_allclose(out, dense(q, k, v), rtol=1e-4, atol=1e-5)
    for got, want in zip(_grads(flash, q, k, v, w), _grads(dense, q, k, v, w)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_value_width_through_the_op():
    """The symbol's output takes v's head width; the scale is the attr."""
    rng = np.random.RandomState(9)
    qn, kn = (rng.randn(1, 16, 2, 12).astype("f") for _ in range(2))
    vn = rng.randn(1, 16, 2, 4).astype("f")
    sym = mx.sym.contrib.flash_attention(
        mx.sym.var("q"), mx.sym.var("k"), mx.sym.var("v"), causal=True,
        scale=12 ** -0.5)
    _, out_shapes, _ = sym.infer_shape(q=qn.shape, k=kn.shape, v=vn.shape)
    assert tuple(out_shapes[0]) == (1, 16, 2, 4)
    ex = sym.simple_bind(mx.cpu(), q=qn.shape, k=kn.shape, v=vn.shape)
    for name, x in (("q", qn), ("k", kn), ("v", vn)):
        ex.arg_dict[name][:] = x
    np.testing.assert_allclose(ex.forward()[0].asnumpy(),
                               _oracle(qn, kn, vn, True), rtol=1e-4,
                               atol=1e-5)
