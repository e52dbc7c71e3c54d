"""Granite-4.0-H's layers through the program, held to the plain reference
(`benchmark/refs/granite_hybrid.py`: float32, the recurrence
position by position, attention a dense masked softmax, Adam written out)
at a tiny size on the CPU: the chunked state-space scan, the model through
`Module`, recomputation, Adam on float32 master weights, the multipliers,
and the metrics that reduce on the device."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from benchmark.refs import granite_hybrid as ref
from mxnet_tpu import telemetry
from mxnet_tpu.models import granite_hybrid as model
from mxnet_tpu.ops import ssm_ops

TINY = dict(
    vocab_size=96, hidden_size=64, shared_intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, mamba_expand=2,
    mamba_n_heads=8, mamba_d_head=16, mamba_n_groups=1, mamba_d_state=16,
    mamba_d_conv=4, mamba_chunk_size=8, rms_norm_eps=1e-5,
    layer_types=["mamba", "attention", "mamba"], num_hidden_layers=3,
    embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=1 / 16, logits_scaling=8)
ADAM = {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8}


# -- the chunked scan against the position-by-position one --------------------

def _ssd_inputs(t, seed=0, heads=8, groups=2):
    """Decays exp(dt A) that span (0.05, 0.999), as the issue asks."""
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    decay = rng.uniform(np.log(0.05), np.log(0.999), (2, t, heads))
    a = -rng.uniform(1, 16, heads)
    return (f(2, t, heads, 4), jnp.asarray(decay / a, jnp.float32),
            jnp.asarray(a, jnp.float32), f(2, t, groups, 16),
            f(2, t, groups, 16), f(heads)), f(2, t, heads, 4)


def _position_scan(x, dt, a, b, c, d):
    rep = x.shape[2] // b.shape[2]
    return ref.recurrence(x, dt, a, jnp.repeat(b, rep, 2),
                          jnp.repeat(c, rep, 2), d, block=8)


def _ssd_gaps(t, chunk):
    """Largest gap of the output and of each input's gradient, over the
    reference's largest magnitude."""
    args, w = _ssd_inputs(t)
    gaps = []
    for fn in (lambda *a: ssm_ops.mamba2_ssd(*a, chunk), _position_scan):
        out = fn(*args)
        grads = jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                         argnums=range(6))(*args)
        gaps.append((out,) + grads)
    return [float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
            for got, want in zip(*gaps)]


@pytest.mark.parametrize("t", [32, 64])
@pytest.mark.parametrize("parts", [1, 2, 8])
def test_chunked_scan_is_the_position_scan(t, parts):
    """Chunks of T, T/2 and T/8: output and all six inputs' gradients, to
    1e-5. One reading stands above it and is held to 3e-4: A's gradient
    under chunks of 32 and 64 positions (6e-5, 1.1e-4). A decay inside a
    chunk is exp of the DIFFERENCE of two float32 running sums (as in the
    authors' kernels), which reach -96 and -190 here at the strongest
    decay: 6e-8 of that is 1e-5 in an exponent, and A's gradient adds up
    every pair of positions of a chunk."""
    out, gx, gdt, ga, gb, gc, gd = _ssd_gaps(t, t // parts)
    assert max(out, gx, gdt, gb, gc, gd) < 1e-5
    assert ga < (1e-5 if t // parts < 32 else 3e-4)


@pytest.mark.parametrize("parts", [2, 8])
def test_a_scan_that_drops_the_carried_state_fails(parts, monkeypatch):
    """The planted fault of this mechanism: every chunk starts from zero.
    With one chunk there is nothing to carry; with more the same
    comparison must fail."""
    monkeypatch.setattr(
        ssm_ops, "_carry_states",
        lambda states, chunk_decay: jnp.zeros_like(states))
    assert max(_ssd_gaps(32, 32)) < 1e-5
    assert _ssd_gaps(32, 32 // parts)[0] > 1e-2


def test_scan_refuses_a_sequence_that_is_no_multiple_of_the_chunk():
    args, _ = _ssd_inputs(24)
    with pytest.raises(mx.MXNetError, match="multiple of the chunk"):
        ssm_ops.mamba2_ssd(*args, 16)
    with pytest.raises(mx.MXNetError, match="multiple of the chunk"):
        mx.nd.mamba2_ssd(*[mx.nd.array(np.asarray(a)) for a in args],
                         chunk_size=16)


def test_new_ops_are_registered_for_nd_and_sym():
    rng = np.random.RandomState(1)
    x = rng.randn(2, 6, 8).astype("f")
    w = rng.rand(8).astype("f") + 0.5
    z = rng.randn(2, 6, 8).astype("f")
    want = ref.rms_norm(x, w, 1e-5)
    got = mx.nd.rms_norm(mx.nd.array(x), mx.nd.array(w), eps=1e-5)
    np.testing.assert_allclose(got.asnumpy(), want, rtol=1e-5, atol=1e-6)
    gated = mx.nd.rms_norm(mx.nd.array(x), mx.nd.array(w), mx.nd.array(z),
                           eps=1e-5, dtype="bfloat16")
    assert gated.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        gated.asnumpy().astype("f"),
        ref.rms_norm(x * np.asarray(jax.nn.silu(z)), w, 1e-5), rtol=2e-2,
        atol=2e-2)
    filt, bias = rng.randn(8, 4).astype("f"), rng.randn(8).astype("f")
    conv = mx.nd.causal_conv1d(mx.nd.array(x), mx.nd.array(filt),
                               mx.nd.array(bias))
    np.testing.assert_allclose(conv.asnumpy(),
                               ref.causal_conv1d(x, filt, bias),
                               rtol=1e-5, atol=1e-5)
    # position 0 sees its own input alone
    np.testing.assert_allclose(conv.asnumpy()[:, 0],
                               x[:, 0] * filt[:, 3] + bias, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(mx.nd.silu(mx.nd.array(x)).asnumpy(),
                               np.asarray(jax.nn.silu(x)), rtol=1e-6)
    out = mx.sym.silu(mx.sym.var("x"))
    assert out.list_arguments() == ["x"]


# -- the model through Module -------------------------------------------------

def _module(config=TINY, rows=2, tokens=32, seed=5, init=None,
            **symbol_args):
    """(bound module with the reference's seeded weights, those weights,
    the seeded (ids, labels) batches)."""
    sym = model.symbol(config, **symbol_args)
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (rows, tokens))],
             label_shapes=[("softmax_label", (rows, tokens))],
             type_dict={"data": "int32"})
    w0 = {n: np.asarray(v)
          for n, v in ref.init_params(config, seed, init).items()}
    mod.init_params(arg_params={n: mx.nd.array(v) for n, v in w0.items()})
    ids, labels = ref.make_pool(config, seed, 4, rows, tokens)
    return mod, w0, list(zip(np.asarray(ids), np.asarray(labels)))


def _batch(ids, labels):
    return mx.io.DataBatch(data=[mx.nd.array(ids, dtype="int32")],
                           label=[mx.nd.array(labels)])


def _forward_backward(mod, ids, labels):
    mod.forward_backward(_batch(ids, labels))
    return (mod.get_outputs()[0].asnumpy().astype("f"),
            {n: mod._exec.grad_dict[n].asnumpy().astype("f")
             for n in mod._param_names})


def _reference(w0, ids, labels, config=TINY):
    (loss, probs), grads = jax.value_and_grad(ref.loss_fn, has_aux=True)(
        {n: jnp.asarray(v) for n, v in w0.items()}, jnp.asarray(ids),
        jnp.asarray(labels), config)
    return float(loss), np.asarray(probs), \
        {n: np.asarray(g) for n, g in grads.items()}


def _leaf_gaps(got, want):
    return {n: float(np.linalg.norm(got[n] - want[n])
                     / max(np.linalg.norm(want[n]), 1e-30)) for n in want}


def test_symbol_and_reference_name_the_same_parameters():
    sym = model.symbol(TINY)
    shapes = ref.param_shapes(TINY)
    assert [n for n in sym.list_arguments()
            if n not in ("data", "softmax_label")] == list(shapes)
    assert model.param_shapes(TINY) == shapes
    # the tied embedding is one variable used twice
    assert sym.list_arguments().count("embed_weight") == 1


def test_published_widths_count_the_issues_parameters():
    """The cut the benchmark's cell runs: one period at published widths,
    an eighth of the vocabulary: 772,160,448 parameters."""
    import json
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", "granite_4_0_h_micro_pp4_vp8.json")
    with open(path) as f:
        config = json.load(f)
    shapes = ref.param_shapes(config)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 772160448
    assert shapes["l0_in_proj_weight"] == (8512, 2048)
    assert shapes["l5_k_weight"] == (512, 2048)
    assert model.param_shapes(config) == shapes


@pytest.mark.parametrize("tokens", [32, 64])
def test_module_is_the_reference_in_float32(tokens):
    mod, w0, batches = _module(tokens=tokens)
    probs, grads = _forward_backward(mod, *batches[0])
    _, want_probs, want_grads = _reference(w0, *batches[0])
    logit_gap = np.abs(np.log(probs) - np.log(want_probs)).max()
    assert logit_gap < 1e-4
    gaps = _leaf_gaps(grads, want_grads)
    assert max(gaps.values()) < 1e-3, max(gaps, key=gaps.get)


def test_module_with_bfloat16_operands_stays_in_its_band():
    """bfloat16 operands round every product's inputs to 8 bits: a leaf's
    gradient is off by a few parts in a hundred at worst and by under one
    in the median, the softmax outputs by parts in ten thousand (CPU
    readings at this size: 0.014, 0.004, 7e-5). The float32 master weights
    are untouched by the casts."""
    mod, w0, batches = _module(dtype="bfloat16")
    probs, grads = _forward_backward(mod, *batches[0])
    _, want_probs, want_grads = _reference(w0, *batches[0])
    assert np.linalg.norm(probs - want_probs) \
        / np.linalg.norm(want_probs) < 1e-3
    gaps = _leaf_gaps(grads, want_grads)
    assert max(gaps.values()) < 0.06 and np.median(list(gaps.values())) < 0.02
    assert all(mod._exec.arg_dict[n].dtype == np.float32 for n in w0)
    assert all(g.dtype == np.float32 for g in grads.values())


def _temporaries(mod):
    exec_ = mod._exec
    args, aux = exec_._gather()
    grad_args = {n: args[n] for n in exec_._grad_names}
    others = {n: v for n, v in args.items() if n not in exec_._grad_names}
    compiled = jax.jit(exec_._fwd_bwd_impl).lower(
        grad_args, others, aux, jax.random.PRNGKey(0), (None,)).compile()
    return compiled.memory_analysis().temp_size_in_bytes


def test_recomputation_changes_no_value_and_needs_less_memory():
    """`mirror_stage` on every layer: three checkpointed segments, the same
    loss and gradients, a lower peak of XLA's temporaries at 8 rows of 64."""
    seen = {}
    for recompute in (False, True):
        mod, _, batches = _module(rows=8, tokens=64, recompute=recompute)
        seen[recompute] = _forward_backward(mod, *batches[0]) + (
            telemetry.get_metric("remat_segments").value, _temporaries(mod))
    (probs, grads, segments, temp), (r_probs, r_grads, r_segments, r_temp) = \
        seen[False], seen[True]
    assert (segments, r_segments) == (0, 3)
    np.testing.assert_allclose(r_probs, probs, rtol=1e-6, atol=1e-9)
    assert max(_leaf_gaps(r_grads, grads).values()) < 1e-6
    assert r_temp < temp, (r_temp, temp)


def test_a_symbol_without_the_attribute_is_evaluated_as_before():
    data = mx.sym.var("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=4, name="fc"), name="softmax")
    ex = net.simple_bind(mx.cpu(), data=(2, 3))
    assert telemetry.get_metric("remat_segments").value == 0
    args, aux = ex._gather()
    text = str(jax.make_jaxpr(
        lambda a: ex._eval_fn(a, aux, jax.random.PRNGKey(0), True))(args))
    assert "checkpoint" not in text and "remat" not in text


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_fit_steps_of_adam_follow_the_reference(dtype):
    """`Module.fit` under Adam on the fused step: parameters and both
    moments after three steps are the reference's (written-out Adam, the
    same policy); the moments and the weights stay float32."""
    mod, w0, batches = _module(dtype=dtype, recompute=True)
    descs = ([mx.io.DataDesc("data", (2, 32), np.int32)],
             [mx.io.DataDesc("softmax_label", (2, 32), np.float32)])

    class Three(mx.io.DataIter):
        def __init__(self):
            super().__init__(2)
            self.provide_data, self.provide_label = descs
            self.n = 0

        def next(self):
            if self.n == 3:
                raise StopIteration
            self.n += 1
            return _batch(*batches[self.n - 1])

    mod.fit(Three(), eval_metric="ce", optimizer="adam",
            optimizer_params=dict(ADAM, wd=0.0, rescale_grad=1.0),
            num_epoch=1)
    assert mod._fused_plan not in (None, False)
    policy = "f32" if dtype == "float32" else "bf16"
    want = ref.follow(TINY, ADAM, {n: jnp.asarray(v) for n, v in w0.items()},
                      [(jnp.asarray(i), jnp.asarray(l))
                       for i, l in batches[:3]], policy)
    got, _ = mod.get_params()
    tol = 1e-3 if dtype == "float32" else 0.05
    moved = lambda p: {n: np.asarray(p[n], "f") - w0[n] for n in w0}
    got = {n: v.asnumpy() for n, v in got.items()}
    gaps = _leaf_gaps(moved(got), moved(want["params"]))
    assert np.median(list(gaps.values())) < tol, gaps
    for i, name in enumerate(mod._param_names):
        mean, var = mod._updater.states[i]
        assert mean.dtype == var.dtype == np.float32
        assert got[name].dtype == np.float32
    means = {n: mod._updater.states[i][0].asnumpy()
             for i, n in enumerate(mod._param_names)}
    gaps = _leaf_gaps(means, {n: np.asarray(v)
                              for n, v in want["mean"].items()})
    assert np.median(list(gaps.values())) < tol, gaps


def test_the_master_weight_keeps_a_move_smaller_than_bfloat16_can_hold():
    """Three Adam steps of 3e-4 move a norm's weight of 1.0 by about 9e-4;
    bfloat16's spacing at 1.0 is 7.8e-3, so a bfloat16 weight would not
    move at all. The float32 master does, by every step's worth."""
    mod, w0, batches = _module(dtype="bfloat16")
    mod.init_optimizer(optimizer="adam",
                       optimizer_params=dict(ADAM, wd=0.0, rescale_grad=1.0))
    name, track = "l0_norm1_weight", []
    for ids, labels in batches[:3]:
        mod._step(_batch(ids, labels))
        track.append(mod._exec.arg_dict[name].asnumpy().copy())
    steps = np.abs(np.diff(np.stack([w0[name]] + track), axis=0))
    assert steps.max() < 7.8e-3 / 2
    assert (steps > 1e-4).mean() > 0.8          # the steps were kept
    total = np.abs(track[-1] - w0[name])
    assert total.max() < 7.8e-3 / 2 and np.median(total) > 5e-4
    as_bf16 = np.asarray(jnp.asarray(track[-1]).astype(jnp.bfloat16), "f")
    assert (as_bf16 == 1.0).mean() > 0.9        # what bfloat16 would keep


@pytest.mark.parametrize("key", ["embedding_multiplier",
                                 "residual_multiplier",
                                 "attention_multiplier", "logits_scaling"])
def test_each_multiplier_reaches_the_logits(key):
    """None of the four is silently 1: changed alone, it changes the
    outputs, in the program and in the reference alike."""
    changed = dict(TINY, **{key: TINY[key] * 1.5})
    # matrices ten times the usual: at 0.02 and this width attention's
    # scores are all but equal and its scale moves nothing
    base, w0, batches = _module(init={"std": 0.2})
    mod, _, _ = _module(config=changed, init={"std": 0.2})
    base.forward(_batch(*batches[0]), is_train=False)
    mod.forward(_batch(*batches[0]), is_train=False)
    # logits up to a row's constant
    logits = lambda p: np.log(p) - np.log(p).mean(axis=-1, keepdims=True)
    base_logits = logits(base.get_outputs()[0].asnumpy())
    moved = mod.get_outputs()[0].asnumpy()
    assert np.linalg.norm(logits(moved) - base_logits) \
        / np.linalg.norm(base_logits) > 1e-3
    _, want, _ = _reference(w0, *batches[0], config=changed)
    assert np.linalg.norm(logits(moved) - logits(want)) \
        / np.linalg.norm(logits(want)) < 1e-3


# -- the metrics reduce on the device -----------------------------------------

def _host_values(name, labels, probs, ignore=None):
    label = labels.reshape(-1).astype("int64")
    picked = probs.reshape(-1, probs.shape[-1])[np.arange(label.size), label]
    if name == "ce":
        return float((-np.log(picked + 1e-12)).sum() / label.size)
    keep = label != ignore if ignore is not None else np.ones(label.size, bool)
    loss = -np.log(np.maximum(1e-10, np.where(keep, picked, 1.0))).sum()
    return float(np.exp(loss / keep.sum()))


@pytest.mark.parametrize("lag", [0, 1])
@pytest.mark.parametrize("name,ignore", [("ce", None), ("perplexity", None),
                                         ("perplexity", 3)])
def test_metrics_pick_and_sum_on_the_device(name, ignore, lag, monkeypatch):
    """`CrossEntropy` and `Perplexity` give the numpy arithmetic's values
    and bring no [rows, classes] array to the host, queued (`fit`'s lagged
    fold) or at once."""
    from mxnet_tpu import metric as metric_mod
    from mxnet_tpu.ndarray import NDArray
    rng = np.random.RandomState(7)
    probs = rng.dirichlet(np.ones(50), size=(3, 64)).astype("f")
    labels = rng.randint(0, 50, (3, 64)).astype("f")
    crossed = []
    asnumpy, held = NDArray.asnumpy, metric_mod._held

    def spy_asnumpy(self):
        crossed.append(self.shape)
        return asnumpy(self)

    def spy_held(arrays):
        crossed.extend(a.shape for a in arrays)
        return held(arrays)

    monkeypatch.setattr(NDArray, "asnumpy", spy_asnumpy)
    monkeypatch.setattr(metric_mod, "_held", spy_held)
    kwargs = {} if ignore is None else {"ignore_label": ignore}
    metric = mx.metric.create(name, **kwargs)
    metric._defer(lag)
    for label, prob in zip(labels, probs):
        metric.update_dict({"softmax_label": mx.nd.array(label)},
                           {"softmax_output": mx.nd.array(prob)})
    value = metric.get()[1]
    assert value == pytest.approx(
        _host_values(name, labels, probs, ignore), rel=1e-5)
    assert all(int(np.prod(s)) <= 2 for s in crossed), crossed


def test_a_metric_pair_the_device_cannot_take_falls_back_to_numpy():
    metric = mx.metric.create("ce")
    probs = np.full((4, 5), 0.2, "f")
    metric.update([mx.nd.array(np.arange(4.))], [mx.nd.array(probs)])
    assert metric.get()[1] == pytest.approx(-np.log(0.2), rel=1e-6)
    from mxnet_tpu.metric import _PickedLogSum
    assert _PickedLogSum.of(mx.nd.array(np.arange(4.)),
                            mx.nd.array(np.ones(4))) is None
