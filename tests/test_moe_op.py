"""The `moe` op's second router and second activation: sigmoid scores with
a selection bias that selects and does not weigh, the routed scale, the
bias's sign rule, the sequence-wise balance loss riding the router's
gradient, SwiGLU experts, and the softmax router left as it was. Each held
to plain `jax.numpy` written here, on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import moe_ops

EXPERTS, DIM, WIDTH, TOP = 8, 16, 8, 3


def _inputs(seed=0, rows=2, tokens=24, held=EXPERTS):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    return (f(rows, tokens, DIM), f(rows, tokens, DIM), f(EXPERTS, DIM),
            0.3 * f(held, 2 * WIDTH, DIM), 0.3 * f(held, DIM, WIDTH))


def _written(router_x, x, router_w, gate_up, down, bias, first, scale=1.0):
    """Sigmoid routing and SwiGLU experts as the equations say: every held
    expert on every token, weighted by its weight where chosen."""
    s = jax.nn.sigmoid(router_x @ router_w.T)
    chosen = jax.lax.top_k(s + bias, TOP)[1]
    top = jnp.take_along_axis(s, chosen, axis=-1)
    top = scale * top / jnp.sum(top, axis=-1, keepdims=True)
    out = jnp.zeros(x.shape, jnp.float32)
    for i in range(gate_up.shape[0]):
        w = jnp.sum(jnp.where(chosen == first + i, top, 0.0), axis=-1)
        both = x @ gate_up[i].T
        out = out + w[..., None] * (
            (jax.nn.silu(both[..., :WIDTH]) * both[..., WIDTH:]) @ down[i].T)
    return out


def _sigmoid(*args, first=0, **kwargs):
    return moe_ops.moe(*args, first_held=first, top_k=TOP, score="sigmoid",
                       act="silu", **kwargs)


@pytest.mark.parametrize("first,held", [(0, 8), (2, 4), (6, 2)])
def test_sigmoid_swiglu_held_experts_are_the_written_layer(first, held):
    """Output and the five inputs' gradients against every held expert on
    every token, under a bias that is not zero."""
    router_x, x, router_w, gate_up, down = _inputs()
    args = (router_x, x, router_w, gate_up[first:first + held],
            down[first:first + held])
    bias = jnp.asarray(np.random.RandomState(4).randn(EXPERTS) * 0.2,
                       jnp.float32)
    w = jnp.asarray(np.random.RandomState(9).randn(2, 24, DIM), jnp.float32)
    op = lambda *a: _sigmoid(*a, bias, first=first)[0]
    dense = lambda *a: _written(*a, bias, first)
    np.testing.assert_allclose(op(*args), dense(*args), rtol=1e-5,
                               atol=1e-5)
    grads = [jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=range(5))(
        *args) for fn in (op, dense)]
    for got, want in zip(*grads):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_bias_selects_but_does_not_weigh():
    """A bias that lifts expert 5 above every score brings it into every
    token's choice, with the weight of its SCORE among the chosen: the
    result is the written layer's, and not the one whose weights read the
    biased scores."""
    args = _inputs(1)
    bias = jnp.zeros((EXPERTS,), jnp.float32).at[5].set(10.0)
    s = jax.nn.sigmoid(args[0] @ args[2].T)
    unbiased = jax.lax.top_k(s, TOP)[1]
    biased = jax.lax.top_k(s + bias, TOP)[1]
    assert not bool(jnp.all(jnp.any(unbiased == 5, axis=-1)))
    assert bool(jnp.all(jnp.any(biased == 5, axis=-1)))
    got = _sigmoid(*args, bias)[0]
    np.testing.assert_allclose(got, _written(*args, bias, 0), rtol=1e-5,
                               atol=1e-5)
    # the same choice, weighed by the biased scores, is another result
    top = jnp.take_along_axis(s + bias, biased, axis=-1)
    assert float(jnp.max(jnp.abs(top / jnp.sum(top, -1, keepdims=True)
                                 - jnp.take_along_axis(s, biased, -1)
                                 / jnp.sum(jnp.take_along_axis(
                                     s, biased, -1), -1, keepdims=True)))) \
        > 0.1


@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
def test_routed_scale_scales_the_routed_part(score):
    args = _inputs(2)
    bias = (jnp.zeros((EXPERTS,), jnp.float32),) if score == "sigmoid" \
        else ()
    run = lambda scale: moe_ops.moe(*args, *bias, first_held=0, top_k=TOP,
                                    score=score, routed_scale=scale)
    one, scaled = run(1.0), run(2.446)
    if score == "sigmoid":
        one, scaled = one[0], scaled[0]
    np.testing.assert_allclose(scaled, 2.446 * one, rtol=1e-5, atol=1e-6)


def test_bias_moves_by_the_sign_of_the_load_gap():
    """b_e + rate sign(mean load - load_e), the loads the assignments of
    every token of the call; an expert on the mean does not move."""
    args = _inputs(3, rows=2, tokens=32)
    bias = jnp.asarray(np.linspace(-0.1, 0.1, EXPERTS), jnp.float32)
    moved = _sigmoid(*args, bias, bias_rate=1e-3)[1]
    s = jax.nn.sigmoid(args[0] @ args[2].T)
    chosen = np.asarray(jax.lax.top_k(s + bias, TOP)[1]).ravel()
    load = np.bincount(chosen, minlength=EXPERTS).astype(np.float32)
    want = np.asarray(bias) + np.float32(1e-3) * np.sign(load.mean() - load)
    np.testing.assert_array_equal(np.asarray(moved), want)
    assert load.sum() == 2 * 32 * TOP
    # no gradient reaches the bias
    g = jax.grad(lambda b: jnp.sum(_sigmoid(*args, b)[0]))(bias)
    assert not np.asarray(g).any()


def test_balance_gradient_is_the_written_loss_gradient():
    """With ``balance_alpha`` the router's gradients are those of the
    output's loss plus alpha times the sequence-wise balance loss written
    here (f the counts, P the mean share of the scores, per row); the
    output itself is unchanged."""
    args = _inputs(5, rows=3, tokens=16)
    bias = jnp.asarray(np.random.RandomState(6).randn(EXPERTS) * 0.1,
                       jnp.float32)
    alpha = 0.3
    w = jnp.asarray(np.random.RandomState(7).randn(3, 16, DIM), jnp.float32)

    def balance(router_x, router_w):
        s = jax.nn.sigmoid(router_x @ router_w.T)           # [R, T, E]
        chosen = jax.lax.top_k(s + bias, TOP)[1]
        counts = jnp.sum(jax.nn.one_hot(chosen, EXPERTS), axis=(1, 2))
        f = EXPERTS / (TOP * 16) * counts
        p = jnp.mean(s / jnp.sum(s, -1, keepdims=True), axis=1)
        return jnp.mean(jnp.sum(jax.lax.stop_gradient(f) * p, axis=-1))

    def with_op(router_x, router_w):
        y = _sigmoid(router_x, args[1], router_w, *args[3:], bias,
                     balance_alpha=alpha)[0]
        return jnp.sum(y * w)

    def written(router_x, router_w):
        y = _written(router_x, args[1], router_w, *args[3:], bias, 0)
        return jnp.sum(y * w) + alpha * balance(router_x, router_w)

    got = jax.grad(with_op, argnums=(0, 1))(args[0], args[2])
    want = jax.grad(written, argnums=(0, 1))(args[0], args[2])
    for g, e in zip(got, want):
        np.testing.assert_allclose(g, e, rtol=1e-4, atol=1e-6)
    plain = jax.grad(lambda rw: jnp.sum(_sigmoid(
        args[0], args[1], rw, *args[3:], bias)[0] * w))(args[2])
    assert float(jnp.linalg.norm(got[1] - plain)) > 1e-3
    np.testing.assert_array_equal(
        _sigmoid(*args, bias, balance_alpha=alpha)[0],
        _sigmoid(*args, bias)[0])


def test_softmax_router_is_unchanged():
    """The softmax router with its defaults given explicitly traces the
    same program and gives the same bits, forward and backward, as the
    call that names none of them: no sigmoid, no router scope, no scale."""
    args = _inputs(8)
    plain = lambda *a: moe_ops.moe(*a, first_held=0, top_k=TOP)
    named = lambda *a: moe_ops.moe(*a, first_held=0, top_k=TOP,
                                   score="softmax", routed_scale=1.0,
                                   act="relu", bias_rate=0.0,
                                   balance_alpha=0.0)
    loss = lambda fn: jax.grad(lambda *a: jnp.sum(fn(*a) ** 2),
                               argnums=range(5))
    assert str(jax.make_jaxpr(loss(plain))(*args)) \
        == str(jax.make_jaxpr(loss(named))(*args))
    text = str(jax.make_jaxpr(loss(plain))(*args))
    assert "logistic" not in text and "moe_router" not in text
    for got, want in zip([plain(*args)] + list(loss(plain)(*args)),
                         [named(*args)] + list(loss(named)(*args))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_op_pairs_the_bias_with_the_sigmoid_router():
    """Through the registry: the bias is the sixth input under sigmoid
    and only then; the symbol lists it as an auxiliary state."""
    names = ["rx", "x", "rw", "gu", "dn"]
    v = [mx.sym.var(n) for n in names]
    s = mx.sym.moe(*v, mx.sym.var("b"), first_held=0, top_k=TOP,
                   score="sigmoid", act="silu")
    assert s.list_auxiliary_states() == ["b"]
    assert s.list_arguments() == names
    args = [mx.nd.array(np.asarray(a)) for a in _inputs(9)]
    with pytest.raises(MXNetError):
        mx.nd.moe(*args, mx.nd.zeros((EXPERTS,)), first_held=0, top_k=TOP)
    with pytest.raises(MXNetError):
        mx.nd.moe(*args, first_held=0, top_k=TOP, score="sigmoid")
    with pytest.raises(MXNetError):
        mx.nd.moe(*args, first_held=0, top_k=TOP, act="gelu")
